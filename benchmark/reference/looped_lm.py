"""Plain reference of a looped language model (Ouro, "Scaling Latent Reasoning
via Looped Language Models", arXiv:2510.25741), and its weights.

Written from the published description and the names of the published
modelling file, in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`.  No kernels, no cache, no batching:
one sequence at a time, the passes a Python loop over ONE list of layer
weights.  It shares no code with `torchft_tpu/`; the only thing the two
have in common is the layout of the weight tree (`make_weights`).

With `RMS(x; g) = x / sqrt(mean(x^2) + eps) * g`, L layers and T =
`total_ut_steps` passes:

- `h_0 = embed[tokens]`.  Pass t = 1..T starts from `x = h_{t-1}` and runs the
  L layers, THE SAME WEIGHTS in every pass; layer l:
  `a = Wo causal_softmax(q k^T / sqrt(head_dim)) v` with `q, k, v = RoPE(u Wq),
  RoPE(u Wk), u Wv`, `u = RMS(x; g1_l)`; `x = x + RMS(a; g2_l)`;
  `m = W_down(silu(W_gate u) * W_up u)`, `u = RMS(x; g3_l)`; `x = x + RMS(m; g4_l)`
  (four norms a block: `input_layernorm`, `input_layernorm_2`,
  `post_attention_layernorm`, `post_attention_layernorm_2`).
  `h_t = RMS(x; g_final)`: this pass's output AND the next pass's input.
- After every pass the one untied head and the one gate: `z_t = h_t W_head`,
  the row's next-token loss `l_t = logsumexp(z_t) - z_t[y]`, and for t < T
  `lambda_t = sigmoid(h_t . w_g + b_g)` (`early_exit_gate`).
- A token's exit distribution: `p_t = lambda_t prod_{j<t} (1 - lambda_j)` for
  t < T and `p_T = prod_{j<T} (1 - lambda_j)`: the last pass takes what is left.
- The loss (the paper's entropy-regularised objective of pre-training):
  `mean_i [sum_t p_t l_t - beta H(p)]`, `H(p) = -sum_t p_t log p_t`.

Departures from the published description, each without effect on the
arithmetic or noted where it has one:

- `jax.checkpoint` around each block, each head's attention and each pass's
  head: values are recomputed in the backward pass, not computed differently.
- Inside a pass the layers are a `lax.scan` over the stacked weights and a
  block's heads a `lax.map`: loops of the compiled program, not of the
  mathematics.  Written out in Python, as `reference/dense_lm.py` writes its
  four layers, the four passes are 32 block applications of 16 heads each, 512
  attention graphs forward and as many backward, and the reference compiled for
  four minutes in every run (builder's chip runs, PR 63).
- The published file's `forward` computes a plain loss on the last pass's
  logits (fine-tuning); this is the pre-training objective of the paper's first
  stage.  `early_exit_threshold` and the per-pass KV cache are inference's.

`precision` selects what the matmul operands are rounded to before each
matrix product ("float32" the reference, "bfloat16", "float8" the control), as
`reference/dense_lm.py` does; the gate's product and the exit distribution stay
float32 in every precision, as the configuration states for the program.
`left_out` names one piece of the mathematics to leave out (`LEFT_OUT`): what a
wrong program would compute, for the checks that the comparison sees it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Weights = Dict[str, Any]

# The pieces `left_out` may name: "passes" (one pass alone: T = 1), "entropy" (beta = 0), "exit_weights" (the last
# pass's mean loss alone, no gate), "post_norms" (g2 and g4 not applied), "norm_between_passes" (the final norm
# after the last pass only), "untied" (a copy of the layers a pass: `weights["layers"]` then holds [T, L, ...]).
LEFT_OUT = ("passes", "entropy", "exit_weights", "post_norms", "norm_between_passes", "untied")


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by their published names.  Raises on
    what this file does not write down."""
    if config.get("use_sliding_window") or config.get("rope_scaling") is not None:
        raise ValueError("this reference attends over all of the past under plain RoPE")
    if config.get("tie_word_embeddings"):
        raise ValueError("this reference has an untied head")
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "ffn": config["intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "passes": int(config["total_ut_steps"]),
        "beta": float(config["exit_loss"]["beta"]),
    }


_TREE = ("vocab", "hidden", "layers", "heads", "kv_heads", "head_dim", "ffn")


@functools.partial(jax.jit, static_argnames=_TREE)
def _weights(key, *, vocab, hidden, layers, heads, kv_heads, head_dim, ffn) -> Weights:
    ks = jax.random.split(key, 10)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)

    ones = lambda: jnp.ones((layers, hidden), jnp.float32)  # noqa: E731 — a buffer a leaf
    return {
        "embed": jax.random.normal(ks[0], (vocab, hidden), jnp.float32),
        "layers": {
            "attn_norm": ones(),
            "wq": normal(ks[1], (layers, hidden, heads * head_dim), hidden),
            "wk": normal(ks[2], (layers, hidden, kv_heads * head_dim), hidden),
            "wv": normal(ks[3], (layers, hidden, kv_heads * head_dim), hidden),
            "wo": normal(ks[4], (layers, heads * head_dim, hidden), heads * head_dim),
            "attn_post_norm": ones(),
            "mlp_norm": ones(),
            "w_gate": normal(ks[5], (layers, hidden, ffn), hidden),
            "w_up": normal(ks[6], (layers, hidden, ffn), hidden),
            "w_down": normal(ks[7], (layers, ffn, hidden), ffn),
            "mlp_post_norm": ones(),
        },
        "final_norm": jnp.ones((hidden,), jnp.float32),
        "lm_head": normal(ks[8], (hidden, vocab), hidden),
        "exit_gate": {"w": normal(ks[9], (hidden,), hidden), "b": jnp.zeros((1,), jnp.float32)},
    }


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device: matrices normal with standard deviation fan_in**-0.5, norms at one,
    the per-layer matrices stacked on a leading axis, embedding rows at unit
    scale (as `reference/dense_lm.py`).  The projections that write into the
    residual stream (Wo, W_down) are NOT made smaller by the depth: a norm
    stands behind each of them, so what the stream takes has a root mean square
    of one whatever their scale.  The gate's vector at hidden**-0.5 and its
    bias 0: on a normed state the gate's logit is near a unit normal, lambda
    near 1/2 and p near (1/2, 1/4, 1/8, 1/8) over four passes."""
    s = sizes_of(config)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _weights(key, **{k: s[k] for k in _TREE})


# -- the mathematics ---------------------------------------------------------


def _quantize(x, precision: str):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30  # e4m3's largest finite value
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded(x, precision: str):
    return _quantize(x, precision)


# A matmul in a lower precision rounds its operands in the backward pass too.
_rounded.defvjp(lambda x, precision: (_quantize(x, precision), None),
                lambda precision, _, g: (_quantize(g, precision),))


def _round(x, precision: str):
    return x if precision == "float32" else _rounded(x, precision)


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision))


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [S, H, D]; rotates the pair (x[..., i], x[..., i + D/2]) of every
    position p by the angle p * theta**(-2i/D)."""
    seq, _, dim = x.shape
    half = dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _attend(q, k, v, precision: str):
    """One KV head: q [S, G, D] shares k, v [S, D].  Causal softmax attention."""
    seq, _, dim = q.shape
    scores = jnp.einsum("sgd,td->gst", _round(q, precision), _round(k, precision)) * dim ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool))[None], scores, -jnp.inf)
    return jnp.einsum("gst,td->sgd", _round(jax.nn.softmax(scores, axis=-1), precision), _round(v, precision))


def _block(x, w, s, precision: str, post_norms: bool):
    seq = x.shape[0]
    heads, kv, dim = s["heads"], s["kv_heads"], s["head_dim"]
    u = _rms(x, w["attn_norm"], s["eps"])
    q = _rope(_mm(u, w["wq"], precision).reshape(seq, heads, dim), s["rope_theta"]).reshape(seq, kv, heads // kv, dim)
    k = _rope(_mm(u, w["wk"], precision).reshape(seq, kv, dim), s["rope_theta"])
    v = _mm(u, w["wv"], precision).reshape(seq, kv, dim)
    attend = jax.checkpoint(functools.partial(_attend, precision=precision))
    per_head = [a.swapaxes(0, 1) for a in (q, k, v)]  # the KV head leads: a head at a time
    heads_out = jax.lax.map(lambda qkv: attend(*qkv), tuple(per_head)).swapaxes(0, 1)
    a = _mm(heads_out.reshape(seq, heads * dim), w["wo"], precision)
    x = x + (_rms(a, w["attn_post_norm"], s["eps"]) if post_norms else a)
    u = _rms(x, w["mlp_norm"], s["eps"])
    m = _mm(jax.nn.silu(_mm(u, w["w_gate"], precision)) * _mm(u, w["w_up"], precision), w["w_down"], precision)
    return x + (_rms(m, w["mlp_post_norm"], s["eps"]) if post_norms else m)


def _row_losses(h, head, targets, precision: str):
    logits = _mm(h, head, precision)
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]


def exit_distribution(lambdas):
    """[T - 1, N] gate values -> [T, N]: p_t = lambda_t prod_{j<t} (1 -
    lambda_j), and the last pass takes what is left."""
    left, out = jnp.ones_like(lambdas[0]), []
    for lam in lambdas:
        out.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(out + [left])


def states(weights: Weights, tokens, s: Dict[str, Any], precision: str = "float32", left_out: str = ""):
    """Every pass's state h_t [S, hidden], t = 1..T, of one sequence."""
    passes = 1 if left_out == "passes" else s["passes"]
    block = jax.checkpoint(functools.partial(_block, s=s, precision=precision, post_norms=left_out != "post_norms"))
    x, found = _round(weights["embed"], precision)[tokens], []
    for t in range(passes):
        layers = jax.tree.map(lambda stacked: stacked[t], weights["layers"]) if left_out == "untied" else weights["layers"]
        x, _ = jax.lax.scan(lambda x, w: (block(x, w), None), x, layers)  # the L layers, first to last
        if left_out != "norm_between_passes" or t == passes - 1:
            x = _rms(x, weights["final_norm"], s["eps"])
        found.append(x)
    return found


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32", left_out: str = ""):
    """The exit-weighted loss of one sequence; tokens, targets: [S]."""
    assert not left_out or left_out in LEFT_OUT, left_out
    with jax.default_matmul_precision("highest"):
        found = states(weights, tokens, s, precision, left_out)
        rows = jax.checkpoint(functools.partial(_row_losses, precision=precision))
        losses = jnp.stack([rows(h, weights["lm_head"], targets) for h in found])  # [T, S]
        if left_out == "exit_weights":
            return jnp.mean(losses[-1])
        gate = weights["exit_gate"]
        lambdas = [jax.nn.sigmoid(h @ gate["w"] + gate["b"][0]) for h in found[:-1]]
        if not lambdas:  # one pass: it takes everything
            return jnp.mean(losses[0]) + 0.0 * (gate["w"][0] + gate["b"][0])
        p = exit_distribution(lambdas)
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        beta = 0.0 if left_out == "entropy" else s["beta"]
        return jnp.mean(jnp.sum(p * losses, axis=0) - beta * entropy)


def loss_and_grads(weights: Weights, tokens, targets, config: Dict[str, Any],
                   precision: str = "float32") -> Tuple[jax.Array, Weights]:
    """Loss and its gradient for a batch [B, S], one sequence at a time,
    averaged over the sequences as the mean loss of the batch is."""
    one = one_sequence_fn(config, precision)
    runs = [one(weights, tokens[i], targets[i]) for i in range(tokens.shape[0])]
    mean = lambda *leaves: sum(leaves) / len(runs)  # noqa: E731
    return mean(*[l for l, _ in runs]), jax.tree.map(mean, *[g for _, g in runs])


def one_sequence_fn(config: Dict[str, Any], precision: str = "float32", left_out: str = ""):
    """The jitted (weights, tokens[S], targets[S]) -> (loss, gradient tree)."""
    return _one_sequence(tuple(sorted(sizes_of(config).items())), precision, left_out)


@functools.lru_cache(maxsize=None)
def _one_sequence(frozen_sizes, precision: str, left_out: str):
    s = dict(frozen_sizes)
    return jax.jit(jax.value_and_grad(functools.partial(loss, s=s, precision=precision, left_out=left_out)))
