"""Plain reference of a dense decoder-only language model, and its weights.

Written from the published description of the InternLM2 / Mistral family
(pre-norm decoder blocks: RMSNorm, grouped-query causal attention with rotary
position embedding in the half-split convention, SwiGLU feed-forward, untied
output head, mean next-token cross-entropy) in straightforward `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`.  No kernels, no scan, no
cache, no batching: one sequence at a time.  It shares no code with
`torchft_tpu/models/transformer.py`; the only thing the two have in common is
the layout of the weight tree (`make_weights`), which the benchmark makes from
the seed and hands to both.

Departures from the published description, each without effect on the
arithmetic or noted where it has one:

- `jax.checkpoint` around each block and around each group of heads: values
  are recomputed in the backward pass, not computed differently.  Without it
  the float32 score matrices of one 4096-token sequence (heads x 4096 x 4096
  x 4 bytes a layer) do not fit beside the weights.
- The sliding window of Mistral-7B (4096) is not applied: the benchmark's
  sequences are 4096 tokens, so the window covers every position and full
  causal attention is the published computation (`assumed` in the config).
- RMSNorm's epsilon is the published `rms_norm_eps` (1e-5).  The program's is
  fixed at 1e-6 in `ops/rmsnorm.py`; at unit-scale activations the difference
  is 5e-6 relative, three orders under bf16 rounding (PERF.md, Open questions).

`precision` selects what the matmul operands are rounded to before each
matrix product: "float32" is the reference; "bfloat16" imitates what the
configuration states for the program; "float8" (e4m3, per-tensor scale) is the
control, the nearest precision below bf16 that a later PR could be tempted by.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Weights = Dict[str, Any]


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by their published names."""
    heads = config["num_attention_heads"]
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim", config["hidden_size"] // heads),
        "ffn": config["intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "layers", "heads", "kv_heads", "head_dim", "ffn"))
def _weights(key, *, vocab, hidden, layers, heads, kv_heads, head_dim, ffn) -> Weights:
    ks = jax.random.split(key, 9)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)

    return {
        "embed": jax.random.normal(ks[0], (vocab, hidden), jnp.float32),
        "layers": {
            "attn_norm": jnp.ones((layers, hidden), jnp.float32),
            "wq": normal(ks[1], (layers, hidden, heads * head_dim), hidden),
            "wk": normal(ks[2], (layers, hidden, kv_heads * head_dim), hidden),
            "wv": normal(ks[3], (layers, hidden, kv_heads * head_dim), hidden),
            "wo": normal(ks[4], (layers, heads * head_dim, hidden), heads * head_dim),
            "mlp_norm": jnp.ones((layers, hidden), jnp.float32),
            "w_gate": normal(ks[5], (layers, hidden, ffn), hidden),
            "w_up": normal(ks[6], (layers, hidden, ffn), hidden),
            "w_down": normal(ks[7], (layers, ffn, hidden), ffn),
        },
        "final_norm": jnp.ones((hidden,), jnp.float32),
        "lm_head": normal(ks[8], (hidden, vocab), hidden),
    }


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device: matrices normal with standard deviation fan_in**-0.5, norms at
    one, the per-layer matrices stacked on a leading axis.  Embedding rows are
    at unit scale, so the residual stream enters the first norm at a root mean
    square of one as in a trained model; at hidden**-0.5 RMSNorm's epsilon
    would be a percent of the mean square it is added to."""
    s = sizes_of(config)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _weights(key, **{k: s[k] for k in ("vocab", "hidden", "layers", "heads", "kv_heads", "head_dim", "ffn")})


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


# A matmul in a lower precision rounds its operands in the backward pass too:
# the cotangent is rounded the same way (per-tensor scale, so nothing
# underflows), as a sensible implementation of that precision would.
_rounded.defvjp(lambda x, precision: (_quantize(x, precision), None),
                lambda precision, _, g: (_quantize(g, precision),))


def _round(x, precision: str):
    return x if precision == "float32" else _rounded(x, precision)


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision))


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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
    """One group: q [S, G, D] shares k, v [S, D].  Causal softmax attention."""
    seq, _, dim = q.shape
    scores = jnp.einsum("sgd,td->gst", _round(q, precision), _round(k, precision)) * dim ** -0.5
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("gst,td->sgd", _round(probs, precision), _round(v, precision))


def _block(x, w, s, precision: str):
    seq = x.shape[0]
    heads, kv, dim = s["heads"], s["kv_heads"], s["head_dim"]
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    q = _rope(_mm(h, w["wq"], precision).reshape(seq, heads, dim), s["rope_theta"])
    k = _rope(_mm(h, w["wk"], precision).reshape(seq, kv, dim), s["rope_theta"])
    v = _mm(h, w["wv"], precision).reshape(seq, kv, dim)
    q = q.reshape(seq, kv, heads // kv, dim)
    attend = jax.checkpoint(functools.partial(_attend, precision=precision))
    out = jnp.concatenate(
        [attend(q[:, g], k[:, g], v[:, g]) for g in range(kv)], axis=1
    ).reshape(seq, heads * dim)
    x = x + _mm(out, w["wo"], precision)
    h = _rms_norm(x, w["mlp_norm"], s["eps"])
    gate = jax.nn.silu(_mm(h, w["w_gate"], precision))
    return x + _mm(gate * _mm(h, w["w_up"], precision), w["w_down"], precision)


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32"):
    """Mean next-token cross-entropy of one sequence; tokens, targets: [S]."""
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        for i in range(s["layers"]):
            w = {name: stacked[i] for name, stacked in weights["layers"].items()}
            x = jax.checkpoint(functools.partial(_block, s=s, precision=precision))(x, w)
        h = _rms_norm(x, weights["final_norm"], s["eps"])
        logits = _mm(h, weights["lm_head"], precision)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss_and_grads(weights: Weights, tokens, targets, config: Dict[str, Any],
                   precision: str = "float32") -> Tuple[jax.Array, Weights]:
    """Loss and its gradient for a batch [B, S], one sequence at a time,
    averaged over the sequences as the mean loss of the batch is."""
    one = one_sequence_fn(config, precision)
    total_loss, total_grads = None, None
    for i in range(tokens.shape[0]):
        l, g = one(weights, tokens[i], targets[i])
        total_loss = l if total_loss is None else total_loss + l
        total_grads = g if total_grads is None else jax.tree.map(jnp.add, total_grads, g)
    n = tokens.shape[0]
    return total_loss / n, jax.tree.map(lambda g: g / n, total_grads)


def one_sequence_fn(config: Dict[str, Any], precision: str = "float32"):
    """The jitted (weights, tokens[S], targets[S]) -> (loss, gradient tree)."""
    return _one_sequence(tuple(sorted(sizes_of(config).items())), precision)


@functools.lru_cache(maxsize=None)
def _one_sequence(frozen_sizes, precision: str):
    s = dict(frozen_sizes)
    return jax.jit(jax.value_and_grad(functools.partial(loss, s=s, precision=precision)))
