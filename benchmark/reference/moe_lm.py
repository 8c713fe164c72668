"""Plain reference of a sparse mixture-of-experts decoder-only language model
(the OLMoE family), and its weights.

Written from the published description (OLMoE-1B-7B's `config.json`,
`model_type: olmoe`, the `olmoe` modelling code's layer equations and the
OLMoE paper, arXiv:2409.02060) in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`.  No kernels, no sort, no grouped
matmul, no capacity, no batching: one sequence at a time, and the experts as
a masked loop — every expert's feed-forward is computed for every position
and weighted by that position's gate for it, which is zero where the router
did not choose it.  It shares no code with `torchft_tpu/`; the only thing the
two have in common is the layout of the weight tree (`make_weights`), which
the benchmark makes from the seed and hands to both.

Per block, x of [S, hidden]:

    h = RMSNorm(x);  q = RMSNorm_q(h Wq), k = RMSNorm_k(h Wk)   (each norm over
        all projected channels, with a weight of its own, before the split
        into heads and before RoPE);  v = h Wv
    RoPE (half-split convention) on q, k; causal softmax attention at scale
        head_dim**-0.5;  x = x + attn Wo
    h = RMSNorm(x);  p = softmax(h Wr) in float32 over the experts
    (g, e) = top-k(p), NOT renormalised (`norm_topk_prob: false`)
    y = sum_j g_j * W_down[e_j] (silu(W_gate[e_j] h) * W_up[e_j] h);  x = x + y

then the final RMSNorm, the untied head and the mean next-token
cross-entropy.  Training adds, per layer and per sequence: the load-balance
loss `experts * sum_e f_e P_e` (f_e: the share of the sequence's positions
that chose expert e among their k; P_e: the mean of p_e over the sequence)
times `router_aux_loss_coef`, and the router z-loss
`mean(logsumexp(h Wr)**2)` times `router_z_loss_coef`.

Departures from the published description, each without effect on the
arithmetic or noted where it has one:

- `jax.checkpoint` around each block, each attention head and each expert of
  the loop: values are recomputed in the backward pass, not computed
  differently.  The experts run as a `lax.scan` over the stacked expert
  weights, which is the loop written once.
- The auxiliary losses are taken per sequence and averaged over the batch,
  as a data-parallel job takes them per device batch (the published training
  code takes them over a device's whole micro-batch); `assumed` in the
  configuration file.
- A near-tie between the k-th and (k+1)-th expert can fall the other way in
  a lower precision: that is a property of top-k routing, not of this file,
  and the configuration's `correct.readings` say how often it happens.

`precision` selects what the matmul operands are rounded to before each
matrix product: "float32" is the reference; "bfloat16" imitates what the
configuration states for the program; "float8" (e4m3, per-tensor scale) is
the control, the nearest precision below bf16 that a later PR could be
tempted by.  The router's product stays in float32 in every precision, as
the published code keeps it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Weights = Dict[str, Any]

_SIZE_KEYS = ("vocab", "hidden", "layers", "heads", "kv_heads", "head_dim", "ffn", "experts")


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by their published names."""
    heads = config["num_attention_heads"]
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or config["hidden_size"] // heads,
        "ffn": config["intermediate_size"],
        "experts": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "aux_coef": float(config["router_aux_loss_coef"]),
        "z_coef": float(config["router_z_loss_coef"]),
    }


@functools.partial(jax.jit, static_argnames=_SIZE_KEYS)
def _weights(key, *, vocab, hidden, layers, heads, kv_heads, head_dim, ffn, experts) -> Weights:
    ks = jax.random.split(key, 10)

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
            "q_norm": jnp.ones((layers, heads * head_dim), jnp.float32),
            "k_norm": jnp.ones((layers, kv_heads * head_dim), jnp.float32),
            "mlp_norm": jnp.ones((layers, hidden), jnp.float32),
            "router": normal(ks[5], (layers, hidden, experts), hidden),
            "w_gate": normal(ks[6], (layers, experts, hidden, ffn), hidden),
            "w_up": normal(ks[7], (layers, experts, hidden, ffn), hidden),
            "w_down": normal(ks[8], (layers, experts, ffn, hidden), ffn),
        },
        "final_norm": jnp.ones((hidden,), jnp.float32),
        "lm_head": normal(ks[9], (hidden, vocab), hidden),
    }


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device: matrices normal with standard deviation fan_in**-0.5, norms at
    one, the per-layer matrices stacked on a leading axis and a layer's
    experts on the next.  Embedding rows are at unit scale, so the residual
    stream enters the first norm at a root mean square of one as in a
    trained model.  The router's logits then have unit variance: its
    softmax is neither flat nor one-hot, and every expert is chosen."""
    s = sizes_of(config)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _weights(key, **{k: s[k] for k in _SIZE_KEYS})


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


def _expert(h, gate_for_it, w_gate, w_up, w_down, precision: str):
    """One expert's feed-forward for every position, weighted by each
    position's gate for this expert (zero where it was not chosen)."""
    inner = jax.nn.silu(_mm(h, w_gate, precision)) * _mm(h, w_up, precision)
    return gate_for_it[:, None] * _mm(inner, w_down, precision)


def _route(h, w, s):
    """The router: float32 in every precision, as the published code keeps it."""
    logits = jnp.matmul(h, w["router"])
    p = jax.nn.softmax(logits, axis=-1)
    gates, chosen = jax.lax.top_k(p, s["top_k"])  # [S, k]
    if s["norm_topk"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return logits, p, gates, chosen


def _experts(h, w, s, precision: str):
    """The mixture, and the two auxiliary losses of this layer."""
    logits, p, gates, chosen = _route(h, w, s)
    one_hot = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32)  # [S, k, experts]
    gate_of = jnp.einsum("sk,ske->es", gates, one_hot)  # [experts, S]: 0 where not chosen

    def one(y, expert):
        gate_for_it, w_gate, w_up, w_down = expert
        return y + jax.checkpoint(functools.partial(_expert, precision=precision))(
            h, gate_for_it, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate_of, w["w_gate"], w["w_up"], w["w_down"]))
    share = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)  # f_e
    balance = s["experts"] * jnp.sum(share * jnp.mean(p, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, s["aux_coef"] * balance + s["z_coef"] * z


def _attention(x, w, s, precision: str):
    seq = x.shape[0]
    heads, kv, dim = s["heads"], s["kv_heads"], s["head_dim"]
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    q = _rms_norm(_mm(h, w["wq"], precision), w["q_norm"], s["eps"])
    k = _rms_norm(_mm(h, w["wk"], precision), w["k_norm"], s["eps"])
    q = _rope(q.reshape(seq, heads, dim), s["rope_theta"])
    k = _rope(k.reshape(seq, kv, dim), s["rope_theta"])
    v = _mm(h, w["wv"], precision).reshape(seq, kv, dim)
    q = q.reshape(seq, kv, heads // kv, dim)
    attend = jax.checkpoint(functools.partial(_attend, precision=precision))
    out = jnp.concatenate(
        [attend(q[:, g], k[:, g], v[:, g]) for g in range(kv)], axis=1
    ).reshape(seq, heads * dim)
    return x + _mm(out, w["wo"], precision)


def _block(x, w, s, precision: str):
    x = _attention(x, w, s, precision)
    y, aux = _experts(_rms_norm(x, w["mlp_norm"], s["eps"]), w, s, precision)
    return x + y, aux


def _layer_weights(weights: Weights, i: int) -> Weights:
    return {name: stacked[i] for name, stacked in weights["layers"].items()}


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32"):
    """Mean next-token cross-entropy of one sequence plus its layers'
    auxiliary losses; tokens, targets: [S]."""
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        aux = 0.0
        for i in range(s["layers"]):
            x, layer_aux = jax.checkpoint(functools.partial(_block, s=s, precision=precision))(
                x, _layer_weights(weights, i))
            aux = aux + layer_aux
        h = _rms_norm(x, weights["final_norm"], s["eps"])
        logits = _mm(h, weights["lm_head"], precision)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked) + aux


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


def routing(weights: Weights, tokens, config: Dict[str, Any], precision: str = "float32"):
    """The experts this reference's router chooses for one sequence, per
    layer: [layers, S, k], each position's k sorted by expert id.  What a
    program's choices are set against, to count the near-ties between the
    k-th and the next expert that fell the other way."""
    s = sizes_of(config)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        for i in range(s["layers"]):
            w = _layer_weights(weights, i)
            x = _attention(x, w, s, precision)
            h = _rms_norm(x, w["mlp_norm"], s["eps"])
            chosen.append(jnp.sort(_route(h, w, s)[3], axis=-1))
            x = x + _experts(h, w, s, precision)[0]
    return jnp.stack(chosen)
