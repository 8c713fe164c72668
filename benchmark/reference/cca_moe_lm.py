"""Plain reference of a decoder-only language model whose attention runs inside
a compressed latent (CCA, compressed convolutional attention), whose router is
an MLP with a state carried from layer to layer, which sends each token to one
expert or to none, merges its residuals through learned vectors and reads its
logits off the embedding itself (the ZAYA1 family; ZAYA1-8B), and its weights.

Written from the published description (ZAYA1-8B's `config.json`, `model_type:
zaya`; CCA: arXiv:2510.04476; the router, its depth averaging and the residual
scaling: arXiv:2511.17127) in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`.  No kernels, no row buffer, no
grouped matmul, no batching: one sequence at a time, every shift an explicit
concatenation of a zero row, the convolution over sequence and channels a
product a head and tap, attention one head and one block of queries at a time
under a dense causal mask, the choice an `argmax`, the experts a masked loop —
every expert HELD HERE is computed for every position and weighted by that
position's gate for it, which is zero where the router did not choose it — and
the head over blocks of rows, so that [S, vocabulary] never exists.  It shares
no code with `torchft_tpu/`; the two have in common the layout of the weight
tree (`make_weights`) and the router's bias (`router_bias`), which the
benchmark makes and hands to both.

All layers alike; x of [S, E]; H query heads and G key/value heads of d columns,
query head j reads KV head g(j) = j // (H / G); a position before the first is
zeros everywhere (`x⁻_t = x_{t-1}`).

Attention sublayer, u = RMSNorm(x):

    q~ = u Wq [S, H, d];  k~ = u Wk [S, G, d]
    v = [u Wv1 ; u⁻ Wv2]: the first half of the KV heads from the position
        itself, the second half from the one before (Wv's columns, a head each)
    z = [q~ ; k~] [S, H + G, d]
    z0_t = a1 * z_t + a0 * z_{t-1} + b0            (a weight a channel and tap: `cca_time0` 2)
    z1_{t,h} = z0_{t,h} A_{h,1} + z0_{t-1,h} A_{h,0} + b1_h   (a [d, d] matrix a head and tap: `cca_time1` 2)
    mu_j = (q~_j + k~_{g(j)}) / 2;  q_j = z1_{q,j} + mu_j;  k_g = z1_{k,g} + mean_{j in g} mu_j
    q^ = sqrt(d) q / |q|_2;  k^ = tau_g sqrt(d) k / |k|_2      (tau a learned scalar a KV head)
    RoPE (half-split pairs) on the first `partial_rotary_factor` of every q^ and k^ head
    o = causal softmax(q^ k^T / sqrt(d)) v;  y = o Wo
    x <- (x + b_r) * a_r + (y + b_o) * a_o           (four learned vectors of E: "attn_merge")

Expert sublayer, u = RMSNorm(x), layer l:

    r_l = u Wd + bd + gamma_l * r_{l-1}              (r_{-1} = 0; r_l, un-normed, is what layer l + 1 receives)
    s = W3 gelu(W2 gelu(W1 RMSNorm(r_l) + b1) + b2);  p = softmax(s) over the experts AND the skip choice
    e* = argmax(p + bias_l);  gate p_{e*}, not renormalised
    e* an expert HELD HERE: y = p_{e*} Wdown_e (silu(u Wgate_e) * u Wup_e);  otherwise (the skip
        choice, or an expert held on another chip) y = 0
    x <- (x + b_r) * a_r + (y + b_o) * a_o           ("mlp_merge")

then the final RMSNorm, logits = h E^T with E the embedding itself, and the mean
next-token cross-entropy over the vocabulary slice.  No balance term.

**One chip's share.**  The configuration's `num_experts` counts the experts
held here (its `expert_parallel` group says which of the router's outputs they
are); the router keeps its published width, and what the experts held
elsewhere would add is left out — here as in the program.  With every expert
held the same code is the uncut layer, which is how the test that the shares
add up reads it.

Departures from the published description, each without effect on the
arithmetic or noted where it has one:

- `jax.checkpoint` around each layer, each attention head, each block of
  queries, each expert of the loop and each block of the head's rows:
  recomputed in the backward pass, not computed differently.
- What the catalog's row does not carry (which KV head is shifted, the
  grouping of the second convolution, the mean's broadcast, the norm's scale,
  the router's depth and activation, what the skip choice returns, the merge's
  form) is listed in the configuration file under `assumed` with the other
  reading beside it.
- The bias is a buffer the published training moves by the experts' load,
  outside the gradient; here it is constant, made from the configuration's
  `router_bias` seed.
- A near-tie between the first and second choice can fall the other way in a
  lower precision: a property of top-1 routing, not of this file.

`precision` selects what the matmul operands are rounded to before each matrix
product: "float32" is the reference; "bfloat16" imitates what the
configuration states for the program; "float8" (e4m3, per-tensor scale) is the
control.  The router stays in float32 in every precision.  `leave_out` names a
piece of the mathematics to compute WITHOUT ("shift", "conv0", "conv1", "mean",
"state"): what `correct` must refuse, never a result.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, Any]

_SIZE_KEYS = ("vocab", "hidden", "layers", "heads", "groups", "dim", "ffn", "held", "experts", "router",
              "init_depth")
QUERY_BLOCK = 2048
HEAD_BLOCK = 1024
LEFT_OUT = ("shift", "conv0", "conv1", "mean", "state")
# The embedding's scale as a share of hidden**-0.5 (`make_weights`).
EMBED_SCALE = 1.0 / 6.0


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by short names.  `held` experts
    `first ... first + held - 1` of the router's `experts` live here; the
    router has one output more, the choice that takes none."""
    n = config["num_hidden_layers"]
    if any(kind != "hybrid" for kind in config["layer_types"][:n]):
        raise ValueError("written for layers of the one kind `hybrid`")
    if config["num_experts_per_tok"] != 1 or config.get("sliding_window") is not None:
        raise ValueError("written for one expert a token and no window")
    if not config["tie_word_embeddings"] or (config["cca_time0"], config["cca_time1"]) != (2, 2):
        raise ValueError("written for a tied head and two convolutions of kernel 2")
    rope = config["rope_parameters"]["hybrid"]
    if rope["rope_type"] != "default":
        raise ValueError(f"no rope_type {rope['rope_type']!r} here")
    share = config.get("expert_parallel") or {}
    bias = config.get("router_bias") or {"seed": 0, "scale": 0.0}
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": n,
        "heads": config["num_attention_heads"],
        "groups": config["num_key_value_heads"],
        "dim": config["head_dim"],
        "ffn": config["moe_intermediate_size"],
        "held": config["num_experts"],
        "experts": share.get("routed_experts", config["num_experts"]),
        "first": share.get("first_expert_held", 0),
        "router": config["router_hidden_size"],
        "init_depth": (config.get("published") or {}).get("num_hidden_layers", n),
        "rotary": int(config["head_dim"] * float(rope["partial_rotary_factor"])),
        "rope_theta": float(rope["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "bias_seed": int(bias["seed"]),
        "bias_scale": float(bias["scale"]),
    }


def router_bias(config: Dict[str, Any]) -> np.ndarray:
    """The router's choice bias, [layers, experts + 1] float32: normal at the
    configuration's `router_bias.scale` from its `seed` (not from the run's: a
    buffer of the deployment, the same in every run)."""
    s = sizes_of(config)
    return _bias(s["bias_seed"], s["bias_scale"], s["layers"], s["experts"] + 1)


def _bias(seed: int, scale: float, layers: int, outputs: int) -> np.ndarray:
    return (np.random.default_rng([seed, 0xB1A5]).standard_normal((layers, outputs)) * scale).astype(np.float32)


@functools.partial(jax.jit, static_argnames=_SIZE_KEYS)
def _weights(key, *, vocab, hidden, layers, heads, groups, dim, ffn, held, experts, router, init_depth) -> Weights:
    k_embed, k_attn, k_conv, k_router, k_experts = jax.random.split(key, 5)
    n, chans = layers, heads + groups
    stream = hidden ** -0.5 * EMBED_SCALE  # the residual stream's root mean square: the embedding's

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)

    def into_the_stream(k, shape, fan_in):
        """A projection that writes into the residual stream (Wo, Wdown)."""
        return normal(k, shape, fan_in) * ((2 * init_depth) ** -0.5 * stream)

    def centred(k, shape, fan_in):
        """Zero mean over the inputs: a layer after a GELU, whose positive mean
        would otherwise give every token the same offset."""
        w = normal(k, shape, fan_in)
        return w - jnp.mean(w, axis=-2, keepdims=True)

    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    k0, k1 = jax.random.split(k_conv)
    kd, k_1, k_2, k_3 = jax.random.split(k_router, 4)
    kg, ku, kw = jax.random.split(k_experts, 3)
    merge = jnp.broadcast_to(jnp.asarray([1.0, 0.0, 1.0, 0.0], jnp.float32)[None, :, None], (n, 4, hidden))
    zeros, ones = (lambda *shape: jnp.zeros(shape, jnp.float32)), (lambda *shape: jnp.ones(shape, jnp.float32))
    stack = {
        "attn_norm": ones(n, hidden),
        "wq": normal(kq, (n, hidden, heads * dim), hidden),
        "wk": normal(kk, (n, hidden, groups * dim), hidden),
        "wv": normal(kv, (n, hidden, groups * dim), hidden),
        "wo": into_the_stream(ko, (n, heads * dim, hidden), heads * dim),
        "cca_conv0": normal(k0, (n, 2, chans * dim), 2),           # [tap, channel]: tap 1 the position itself
        "cca_bias0": zeros(n, chans * dim),
        "cca_conv1": normal(k1, (n, chans, 2, dim, dim), 2 * dim),  # [head, tap, channel in, channel out]
        "cca_bias1": zeros(n, chans, dim),
        "cca_temp": ones(n, groups),
        "attn_merge": merge,
        "mlp_norm": ones(n, hidden),
        "router": {
            "down": normal(kd, (n, hidden, router), hidden), "down_bias": zeros(n, router),
            "carry": ones(n, router), "norm": ones(n, router),
            "w1": normal(k_1, (n, router, router), router), "b1": zeros(n, router),
            "w2": centred(k_2, (n, router, router), router), "b2": zeros(n, router),
            "w3": centred(k_3, (n, router, experts + 1), router),
        },
        "w_gate": normal(kg, (n, held, hidden, ffn), hidden),
        "w_up": normal(ku, (n, held, hidden, ffn), hidden),
        "w_down": into_the_stream(kw, (n, held, ffn, hidden), ffn),
        "mlp_merge": merge,
    }
    return {
        "embed": jax.random.normal(k_embed, (vocab, hidden), jnp.float32) * stream,
        "layers": stack,
        "final_norm": ones(hidden),
    }


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device, the layers stacked under "layers" and a layer's held experts on the
    next axis: matrices normal with standard deviation fan_in**-0.5; norms, the
    temperature, the carried state's weight and the merges' a at one; every
    bias and the merges' b at zero.

    **The embedding is the head**, so its scale sets the logits': with rows at
    hidden**-0.5 a position's own token would read a logit of sqrt(hidden) = 45
    against the others' N(0, 1) and the softmax would be one-hot on the input.
    Rows are at hidden**-0.5 / 6: the own token's logit is 7.5 before the layers
    dilute it (1.3% of the probability), the others' N(0, 1/36), the loss at
    log(vocabulary).  The residual stream then has a root mean square of
    0.0037, still above sqrt(rms_norm_eps), and nothing but the first norm's
    epsilon sees its scale.

    The projections that write into the stream (Wo, every Wdown) are at that
    scale and smaller by sqrt(2 * layers of the PUBLISHED model), the usual
    scaled initialisation of output layers, for the reason
    `reference/mla_moe_lm.py` gives: at fan_in**-0.5 causal attention over
    16,384 random positions is a running mean, the same for every late
    position, it piles up in the stream and the router's load collapses.

    The router's second and third matrices have zero mean over their inputs:
    a GELU's output has a positive mean, which through a plain normal matrix
    gives each expert an offset that every token shares — of the size of the
    scores' own spread, so that one expert took six times its share of a
    layer's tokens and another seven (builder's simulation at the published
    widths, PR 41).  A trained router's bias balancing takes such offsets out;
    centred, the busiest expert has 1.3 to 1.9 times the mean."""
    s = sizes_of(config)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    weights = _weights(key, **{k: s[k] for k in _SIZE_KEYS})
    # the two merges start alike: each its own buffer, whatever the compiler made of the one value
    weights["layers"]["mlp_merge"] = jnp.array(weights["layers"]["attn_merge"], copy=True)
    return weights


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
# the cotangent is rounded the same way (per-tensor scale, so nothing underflows).
_rounded.defvjp(lambda x, precision: (_quantize(x, precision), None),
                lambda precision, _, g: (_quantize(g, precision),))


def _round(x, precision: str):
    return x if precision == "float32" else _rounded(x, precision)


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision))


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _before(a):
    """a [S, ...] one position on: row t is a's row t - 1, row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)


def _merge(x, y, vectors):
    a_r, b_r, a_o, b_o = vectors
    return (x + b_r) * a_r + (y + b_o) * a_o


def _rope(x, theta, rotary: int):
    """x: [S, D]; rotates the pair (x[:, i], x[:, i + rotary / 2]) of position
    p by the angle p * theta**(-2i / rotary); columns from `rotary` on pass."""
    seq, half = x.shape[0], rotary // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:rotary]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[:, rotary:]], axis=-1)


def _attend(q, k, v, precision: str):
    """One head: q, k, v [S, D].  Causal softmax attention under a dense mask,
    a block of queries at a time."""
    seq, dim = q.shape
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    k, v = _round(k, precision), _round(v, precision)

    def queries(args):
        q_block, first = args
        scores = jnp.matmul(_round(q_block, precision), k.T) * dim ** -0.5
        visible = (first + jnp.arange(block))[:, None] >= jnp.arange(seq)[None, :]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.matmul(_round(probs, precision), v)

    out = jax.lax.map(jax.checkpoint(queries), (q.reshape(seq // block, block, dim), jnp.arange(0, seq, block)))
    return out.reshape(seq, dim)


def _attention(x, w, s, precision: str, leave_out: Optional[str]):
    seq, hidden = x.shape
    heads, groups, dim = s["heads"], s["groups"], s["dim"]
    per = heads // groups
    u = _rms_norm(x, w["attn_norm"], s["eps"])
    q0 = _mm(u, w["wq"], precision).reshape(seq, heads, dim)
    k0 = _mm(u, w["wk"], precision).reshape(seq, groups, dim)
    u_before = u if leave_out == "shift" else _before(u)
    wv = w["wv"].reshape(hidden, groups, dim)
    v = [_mm(u if g < groups // 2 else u_before, wv[:, g], precision) for g in range(groups)]

    z = jnp.concatenate([q0, k0], axis=1)  # [S, heads + groups, dim]
    if leave_out == "conv0":
        z0 = z
    else:
        taps = w["cca_conv0"].reshape(2, heads + groups, dim)
        z0 = taps[1] * z + taps[0] * _before(z) + w["cca_bias0"].reshape(heads + groups, dim)
    if leave_out == "conv1":
        z1 = z0
    else:
        z0_before = _before(z0)
        z1 = jnp.stack([
            _mm(z0[:, h], w["cca_conv1"][h, 1], precision) + _mm(z0_before[:, h], w["cca_conv1"][h, 0], precision)
            + w["cca_bias1"][h] for h in range(heads + groups)], axis=1)
    mu = [0.5 * (q0[:, j] + k0[:, j // per]) * (0.0 if leave_out == "mean" else 1.0) for j in range(heads)]
    q = [z1[:, j] + mu[j] for j in range(heads)]
    k = [z1[:, heads + g] + sum(mu[g * per:(g + 1) * per]) / per for g in range(groups)]

    def normed(a, scale):
        a = a * (dim ** 0.5 * scale) / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True))
        return _rope(a, s["rope_theta"], s["rotary"])

    q = [normed(a, 1.0) for a in q]
    k = [normed(a, w["cca_temp"][g]) for g, a in enumerate(k)]
    attend = jax.checkpoint(functools.partial(_attend, precision=precision))
    out = jnp.concatenate([attend(q[j], k[j // per], v[j // per]) for j in range(heads)], axis=-1)
    return _merge(x, _mm(out, w["wo"], precision), w["attn_merge"])


def _route(u, w, state, bias, eps):
    """The router: float32 in every precision.  Returns (this layer's state
    [S, R], probabilities [S, experts + 1], the gate [S] and the choice [S])."""
    r = jnp.matmul(u, w["down"]) + w["down_bias"] + w["carry"] * state
    h = _rms_norm(r, w["norm"], eps)
    h = jax.nn.gelu(jnp.matmul(h, w["w1"]) + w["b1"], approximate=False)
    h = jax.nn.gelu(jnp.matmul(h, w["w2"]) + w["b2"], approximate=False)
    probs = jax.nn.softmax(jnp.matmul(h, w["w3"]), axis=-1)
    chosen = jnp.argmax(probs + bias, axis=-1)
    gate = jnp.take_along_axis(probs, chosen[:, None], axis=-1)[:, 0]
    return r, probs, gate, chosen


def _experts(x, w, state, bias, s, precision: str):
    """The expert sublayer: the held experts' part of the result merged into
    the stream, and this layer's router state."""
    u = _rms_norm(x, w["mlp_norm"], s["eps"])
    state, _, gate, chosen = _route(u, w["router"], state, bias, s["eps"])

    def swiglu(h, w_gate, w_up, w_down):
        return _mm(jax.nn.silu(_mm(h, w_gate, precision)) * _mm(h, w_up, precision), w_down, precision)

    def one(y, expert):
        index, w_gate, w_up, w_down = expert
        gate_for_it = jnp.where(chosen == index, gate, 0.0)  # zero where the position chose another, or none
        return y + gate_for_it[:, None] * jax.checkpoint(swiglu)(u, w_gate, w_up, w_down), None

    held = jnp.arange(s["first"], s["first"] + s["held"])
    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (held, w["w_gate"], w["w_up"], w["w_down"]))
    return _merge(x, y, w["mlp_merge"]), state


def _layer(carry, w, bias, s, precision: str, leave_out: Optional[str]):
    x, state = carry
    x = _attention(x, w, s, precision, leave_out)
    x, new_state = _experts(x, w, jnp.zeros_like(state) if leave_out == "state" else state, bias, s, precision)
    return x, new_state


def _layer_weights(stacked: Weights, i: int) -> Weights:
    return jax.tree.map(lambda leaf: leaf[i], stacked)


def _head_loss(h, embed, targets, precision: str):
    """Mean cross-entropy of logits = h E^T, a block of rows at a time."""
    seq, hidden = h.shape
    block = HEAD_BLOCK if seq % HEAD_BLOCK == 0 else seq

    def rows(args):
        h_block, t_block = args
        logits = _mm(h_block, embed.T, precision)
        picked = jnp.take_along_axis(logits, t_block[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    losses = jax.lax.map(jax.checkpoint(rows), (h.reshape(seq // block, block, hidden),
                                                targets.reshape(seq // block, block)))
    return jnp.mean(losses)


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32",
         leave_out: Optional[str] = None):
    """Mean next-token cross-entropy of one sequence; tokens, targets: [S]."""
    assert leave_out is None or leave_out in LEFT_OUT, leave_out
    bias = _bias(s["bias_seed"], s["bias_scale"], s["layers"], s["experts"] + 1)
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        carry = (x, jnp.zeros((x.shape[0], s["router"]), jnp.float32))
        for i in range(s["layers"]):
            carry = jax.checkpoint(functools.partial(_layer, s=s, precision=precision, leave_out=leave_out))(
                carry, _layer_weights(weights["layers"], i), bias[i])
        h = _rms_norm(carry[0], weights["final_norm"], s["eps"])
        return _head_loss(h, weights["embed"], targets, precision)


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


def one_sequence_fn(config: Dict[str, Any], precision: str = "float32", leave_out: Optional[str] = None):
    """The jitted (weights, tokens[S], targets[S]) -> (loss, gradient tree)."""
    return _one_sequence(tuple(sorted(sizes_of(config).items())), precision, leave_out)


@functools.lru_cache(maxsize=None)
def _one_sequence(frozen_sizes, precision: str, leave_out: Optional[str]):
    s = dict(frozen_sizes)
    return jax.jit(jax.value_and_grad(functools.partial(loss, s=s, precision=precision, leave_out=leave_out)))


def routing(weights: Weights, tokens, config: Dict[str, Any], precision: str = "float32"):
    """The choice this reference's router makes for one sequence, per layer:
    [layers, S, 1] — an expert's index, or `experts` for the choice that takes
    none.  What a program's choices are set against, to count the near-ties
    between the first and the second choice that fell the other way."""
    s = sizes_of(config)
    bias = router_bias(config)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        state = jnp.zeros((x.shape[0], s["router"]), jnp.float32)
        for i in range(s["layers"]):
            w = _layer_weights(weights["layers"], i)
            x = _attention(x, w, s, precision, None)
            u = _rms_norm(x, w["mlp_norm"], s["eps"])
            chosen.append(_route(u, w["router"], state, bias[i], s["eps"])[3][:, None])
            x, state = _experts(x, w, state, bias[i], s, precision)
    return jnp.stack(chosen)
