"""Plain reference of a decoder-only language model that mixes Gated DeltaNet
(a gated delta rule with ONE decay a head, 16 key heads under 32 value heads)
with output-gated softmax attention 3 : 1 over a softmax top-10 router beside a
shared expert under a sigmoid gate, its norms zero-centred (Qwen3-Next-80B-A3B,
`model_type: qwen3_next`), and its weights.

Written from the published description (the model's `config.json`; Gated Delta
Networks, arXiv:2412.06464; gated attention, arXiv:2505.06708; the expert layer
is Qwen2-MoE's / Qwen3-MoE's) in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`.  No kernels, no chunks, no sort, no
grouped matmul, no batching: one sequence at a time, **the recurrence position
by position** (`lax.scan` over t with the state [value heads, 128, 128]; never
the chunk form the program runs, so that the comparison is of two algorithms),
the convolution as explicit shifts, softmax attention as a plain masked product
one head and one block of query rows at a time, and the experts as a masked
loop over the experts HELD HERE.  It shares no code with `torchft_tpu/`; the two
have in common the layout of the weight tree (`make_weights`).

S positions, E = hidden.  Every RMSNorm of a BLOCK (before the mixer, before
the experts), of q and k, and the final one is zero-centred: `x^ * (1 + w)`.
Layer i (from 0) is attention iff (i + 1) % `full_attention_interval` == 0.
Every layer is `x <- x + Mixer(norm(x))`, then `x <- x + Experts(norm(x))`.

Gated DeltaNet (three of four layers), u = norm(x) [S, E]:

    q~ = u Wq, k~ = u Wk  (E -> 16 x 128),  v~ = u Wv, z = u Wz  (E -> 32 x 128),  b = u Wb, a = u Wa  (E -> 32)
    c_t = sum_{i=0..3} w_i * y_{t-3+i}                  (a weight a channel and tap, no bias), then SiLU, on q~, k~, v~
    q_t = L2(.) 128**-0.5,  k_t = L2(.)                 (a key head: x / sqrt(sum x^2 + 1e-6))
    beta_t = sigmoid(b_t),  g_t = -exp(A_log) softplus(a_t + dt_bias)          (ONE number a VALUE head and position)
    S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T           (value head j with key head j // 2; S [128, 128], zero at the start)
    o_t = S_t^T q_t
    y_t = [ RMSNorm_head(o_t; w_n [128], PLAIN weight) * SiLU(z_t) ] Wo        (32 x 128 -> E)

Gated attention (one of four), 16 query heads over 2 KV heads of 256:

    q = u Wq, gate = u Wg  (E -> 16 x 256 each),  k = u Wk, v = u Wv  (E -> 2 x 256)
    q, k <- RMSNorm a head with 1 + w;  RoPE at theta over the FIRST 64 of a head's 256 columns (half-split pairs
    (i, i + 32) inside the 64), the other 192 pass;  causal softmax at 256**-0.5, query head j with KV head j // 8
    y = (attn * sigmoid(gate)) Wo                       (a gate a COLUMN, not a head)

Experts (every layer), u = norm(x): `p = softmax_512(u Wr)` in float32 over ALL
the router's outputs, the 10 largest, gates `p / sum_10 p`; `y = sum_{chosen i
HELD HERE} g_i E_i(u) + sigmoid(u w_s) * Shared(u)`, SwiGLUs of width 512.
Training adds, per layer and sequence, the switch-style balance term `512 *
sum_i f_i P_i` (f_i the share of positions that chose i among their 10, P_i the
mean probability) times `router_aux_loss_coef`.  Then the final norm, the untied
head and the mean next-token cross-entropy over the vocabulary slice.

**One chip's share.**  `num_experts` counts the experts held here (the
`expert_parallel` group says which of the router's outputs they are); the
router keeps its published width, and what the experts held elsewhere would
add is left out — here as in the program.  With every expert held the same
code is the uncut layer, which is how the test that the shares add up reads it.

Departures from the published description, each without effect on the
arithmetic or noted where it has one:

- `jax.checkpoint` around each block, each block of positions of the
  recurrence, each attention head and block of query rows, and each expert of
  the loop: recomputed in the backward pass, not computed differently.
- The fused projections are separate leaves: `in_proj_qkvz` (q | k | v | z
  interleaved a key-head group) is Wq, Wk, Wv, Wz; `in_proj_ba` is Wb, Wa;
  `q_proj` (q | gate interleaved a head) is Wq, Wg; the ONE convolution over
  the 8,192 channels of (q, k, v) is three tap arrays.  A permutation of columns.
- No multi-token-prediction module: the catalog's `config` has no key that
  sizes one (the configuration file's `assumed`).
- What the catalog does not carry (the balance coefficient, L2's epsilon, the
  convolution's activation and missing bias, the decay's initialisation) is the
  configuration file's `assumed`.

`precision` selects what the matmul operands are rounded to before each matrix
product: "float32" is the reference; "bfloat16" imitates what the configuration
states for the program; "float8" (e4m3, per-tensor scale) is the control.  The
recurrence rounds q, k and v as its products' operands and keeps the state in
float32; the router's product and the shared expert's gate stay in float32 in
every precision.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, Any]

QUERY_BLOCK = 1024
POSITION_BLOCK = 128
NORM_SPREAD = 0.1  # the zero-centred norm weights from the seed: normal at this scale around 0 (`make_weights`)
# Pieces of the mathematics that `loss(..., left_out=...)` computes WITHOUT (or with the wrong mechanism in their
# place), for the readings that show each one fails the comparison (`benchmark/tools/routing_ties_gdn.py --left-out 1`):
# the decay (alpha = 1); the beta k k^T term (the state only accumulates beta k v^T under its decay); the
# convolution's three earlier taps; SiLU(z) over the head norm; value head j reading key head j % 16 in place of
# j // 2; attention's column gate; ALL 256 columns of an attention head rotated, not the first 64; the norms' `+ 1`
# (plain weights, which start near 0); the shared expert's sigmoid gate.
LEFT_OUT = ("decay", "delta_term", "convolution", "output_gate", "key_head_map", "attention_gate", "rotate_all",
            "norm_offset", "shared_gate")
_STREAM = ("wo", "w_down", "shared_down")  # projections that write into the residual stream


def layer_plan(config: Dict[str, Any]) -> List[Tuple[str, bool]]:
    """(mixer, sparse) of every layer within the depth, first to last: layer i
    (from 0) is attention iff (i + 1) % full_attention_interval == 0, and every
    layer's feed-forward is the sparse one."""
    every = config["full_attention_interval"]
    return [("attention" if (i + 1) % every == 0 else "gdn", True) for i in range(config["num_hidden_layers"])]


def stack_of(mixer: str, sparse: bool = True) -> str:
    """The subtree of the weights a kind of layer is stacked under."""
    return {"gdn": "gdn_layers", "attention": "attn_layers"}[mixer]


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by short names.  `held` experts
    `first ... first + held - 1` of the router's `experts` outputs live here."""
    if config.get("mlp_only_layers") or config.get("decoder_sparse_step", 1) != 1:
        raise ValueError("written for a sparse feed-forward in every layer")
    if config.get("use_sliding_window") or config.get("rope_scaling") is not None or config["hidden_act"] != "silu":
        raise ValueError("written for full attention, unscaled RoPE and SwiGLU experts")
    if config["tie_word_embeddings"] or not config["norm_topk_prob"]:
        raise ValueError("written for an untied head and renormalised gates")
    if config["linear_num_value_heads"] % config["linear_num_key_heads"] or config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("a key head serves a whole number of value heads, a KV head a whole number of query heads")
    if config["shared_expert_intermediate_size"] != config["moe_intermediate_size"]:
        raise ValueError("written for a shared expert as wide as a routed one")
    share = config.get("expert_parallel") or {}
    return {
        "plan": tuple(layer_plan(config)),
        "vocab": config["vocab_size"], "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"], "head_dim": config["head_dim"],
        "rotary": int(config["head_dim"] * config["partial_rotary_factor"]), "rope_theta": float(config["rope_theta"]),
        "key_heads": config["linear_num_key_heads"], "value_heads": config["linear_num_value_heads"],
        "key_dim": config["linear_key_head_dim"], "value_dim": config["linear_value_head_dim"],
        "conv": config["linear_conv_kernel_dim"],
        "ffn": config["moe_intermediate_size"], "held": config["num_experts"],
        "experts": share.get("router_outputs", config["num_experts"]),
        "first": share.get("first_expert_held", 0),
        "init_depth": (config.get("published") or {}).get("num_hidden_layers", config["num_hidden_layers"]),
        "top_k": config["num_experts_per_tok"],
        "eps": float(config["rms_norm_eps"]),
        "aux_coef": float(config["router_aux_loss_coef"]),
    }


# -- the weights ---------------------------------------------------------------


def _stack_weights(key, n: int, mixer: str, s: Dict[str, Any]) -> Weights:
    """One stack of `n` layers of a kind."""
    hidden = s["hidden"]
    names = iter(jax.random.split(key, 32))

    def normal(shape, fan_in, name=""):
        scale = fan_in ** -0.5 * ((2 * s["init_depth"]) ** -0.5 if name in _STREAM else 1.0)
        return jax.random.normal(next(names), (n,) + shape, jnp.float32) * scale

    def around_zero(shape):
        return jax.random.normal(next(names), (n,) + shape, jnp.float32) * NORM_SPREAD

    out = {"attn_norm": around_zero((hidden,)), "mlp_norm": around_zero((hidden,))}
    if mixer == "gdn":
        wide_k, wide_v, heads, taps = s["key_heads"] * s["key_dim"], s["value_heads"] * s["value_dim"], s["value_heads"], s["conv"]
        steps = jnp.exp(jax.random.uniform(next(names), (n, heads), jnp.float32, np.log(0.001), np.log(0.1)))
        out.update(
            wq=normal((hidden, wide_k), hidden), wk=normal((hidden, wide_k), hidden), wv=normal((hidden, wide_v), hidden),
            wz=normal((hidden, wide_v), hidden), gdn_b=normal((hidden, heads), hidden), gdn_a=normal((hidden, heads), hidden),
            gdn_conv_q=normal((taps, wide_k), taps), gdn_conv_k=normal((taps, wide_k), taps), gdn_conv_v=normal((taps, wide_v), taps),
            A_log=jnp.log(jax.random.uniform(next(names), (n, heads), jnp.float32, 1e-4, 16.0)),
            dt_bias=steps + jnp.log(-jnp.expm1(-steps)),  # the inverse of softplus
            gdn_norm=jnp.ones((n, s["value_dim"]), jnp.float32),
            wo=normal((wide_v, hidden), wide_v, "wo"),
        )
    else:
        heads, kv_heads, dim = s["heads"], s["kv_heads"], s["head_dim"]
        out.update(
            wq=normal((hidden, heads * dim), hidden), wk=normal((hidden, kv_heads * dim), hidden),
            wv=normal((hidden, kv_heads * dim), hidden), attn_out_gate=normal((hidden, heads * dim), hidden),
            q_norm=around_zero((dim,)), k_norm=around_zero((dim,)),
            wo=normal((heads * dim, hidden), heads * dim, "wo"),
        )
    ffn, held = s["ffn"], s["held"]
    out.update(
        router=normal((hidden, s["experts"]), hidden),
        w_gate=normal((held, hidden, ffn), hidden), w_up=normal((held, hidden, ffn), hidden),
        w_down=normal((held, ffn, hidden), ffn, "w_down"),
        shared_gate=normal((hidden, ffn), hidden), shared_up=normal((hidden, ffn), hidden),
        shared_down=normal((ffn, hidden), ffn, "shared_down"), shared_scale=normal((hidden, 1), hidden),
    )
    return out


@functools.partial(jax.jit, static_argnames=("frozen_sizes",))
def _weights(key, frozen_sizes) -> Weights:
    s = dict(frozen_sizes)
    k_embed, k_head, k_norm, k_stacks = jax.random.split(key, 4)
    counts: Dict[str, Tuple[str, int]] = {}
    for mixer, _ in s["plan"]:
        name = stack_of(mixer)
        counts[name] = (mixer, counts.get(name, (mixer, 0))[1] + 1)
    out = {
        "embed": jax.random.normal(k_embed, (s["vocab"], s["hidden"]), jnp.float32),
        "final_norm": jax.random.normal(k_norm, (s["hidden"],), jnp.float32) * NORM_SPREAD,
        "lm_head": jax.random.normal(k_head, (s["hidden"], s["vocab"]), jnp.float32) * s["hidden"] ** -0.5,
    }
    for i, (name, (mixer, n)) in enumerate(sorted(counts.items())):
        out[name] = _stack_weights(jax.random.fold_in(k_stacks, i), n, mixer, s)
    return out


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device: one stacked subtree a kind of layer (`stack_of`: "gdn_layers",
    "attn_layers"), each kind's layers in their order in the model, a layer's
    held experts on the next axis.  Matrices are normal with standard deviation
    fan_in**-0.5 (the convolution's taps over the kernel size; the router and
    the shared expert's gate too), embedding rows at unit scale; the
    projections that write into the residual stream (Wo and every Wdown)
    smaller by sqrt(2 * layers of the PUBLISHED model), the scaled
    initialisation of output layers (`reference/mla_moe_lm.py` says what goes
    wrong without it).  The ZERO-CENTRED norm weights (a block's two, q's and
    k's, the final one) are normal at `NORM_SPREAD` around 0, not 0: a program
    that leaves out their `+ 1` then norms to a tenth and fails, and one that
    adds it where it does not belong (the head norm's plain weight, at one)
    doubles.  The decay: `A_log` = log U(0, 16) a value head (the published
    layer's) and `dt_bias` the inverse softplus of log-uniform steps in
    [0.001, 0.1] a value head (gated delta networks' own initialisation: the
    configuration file's `assumed` says why not the published ones), float32."""
    s = sizes_of(config)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _weights(key, tuple(sorted(s.items())))


# -- the mathematics -------------------------------------------------------------


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
    """RMSNorm over the last axis under the weight w as given."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _norm(x, w, s):
    """A zero-centred norm: `x^ * (1 + w)` (LEFT_OUT's "norm_offset": `x^ * w`)."""
    return _rms_norm(x, w if s.get("left_out") == "norm_offset" else 1.0 + w, s["eps"])


def _swiglu(h, w_gate, w_up, w_down, precision: str):
    return _mm(jax.nn.silu(_mm(h, w_gate, precision)) * _mm(h, w_up, precision), w_down, precision)


def _short_conv(z, taps):
    """z [S, C], taps [T, C]: c_t = sum_i taps[i] * z_{t - (T - 1) + i}, zeros
    before the first position; each tap an explicit shift."""
    seq, n = z.shape[0], taps.shape[0]
    out = jnp.zeros_like(z)
    for i in range(n):
        back = n - 1 - i
        out = out + taps[i] * jnp.concatenate([jnp.zeros((back, z.shape[1]), z.dtype), z[:seq - back]], axis=0)
    return out


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _recurrence(q, k, v, g, beta, delta_term: bool = True):
    """The gated delta rule position by position: q, k, v [S, H, d] (q and k
    already those of each VALUE head's key head), g and beta [S, H] -> o
    [S, H, d].  The state [H, d keys, d values] is float32."""
    seq, heads, dim = q.shape
    block = POSITION_BLOCK if seq % POSITION_BLOCK == 0 else seq

    def position(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[:, None, None]                            # alpha S: one number a head
        seen = jnp.einsum("hk,hkv->hv", kt, state) if delta_term else 0.0    # k^T S
        state = state + (bt[:, None] * kt)[:, :, None] * (vt - seen)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", qt, state)

    def positions(state, xs):
        return jax.lax.scan(position, state, xs)

    blocks = tuple(a.reshape(seq // block, block, *a.shape[1:]) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(jax.checkpoint(positions), jnp.zeros((heads, dim, v.shape[2]), g.dtype), blocks)
    return o.reshape(seq, heads, v.shape[2])


def decay_of(h, w, precision: str = "float32"):
    """g [S, value heads] <= 0 from the normed input h: -exp(A_log) softplus(h Wa + dt_bias)."""
    return -jnp.exp(w["A_log"]) * jax.nn.softplus(_mm(h, w["gdn_a"], precision) + w["dt_bias"])


def _gdn(x, w, s, precision: str):
    seq = x.shape[0]
    key_heads, heads, dk, dv, without = s["key_heads"], s["value_heads"], s["key_dim"], s["value_dim"], s.get("left_out")
    h = _norm(x, w["attn_norm"], s)
    conv = (lambda z, taps: taps[-1] * z) if without == "convolution" else _short_conv
    q, k, v = (jax.nn.silu(conv(_mm(h, w[name], precision), w[taps]))
               for name, taps in (("wq", "gdn_conv_q"), ("wk", "gdn_conv_k"), ("wv", "gdn_conv_v")))
    q, k = _l2(q.reshape(seq, key_heads, dk)) * dk ** -0.5, _l2(k.reshape(seq, key_heads, dk))
    # value head j reads key head j // (value heads / key heads)
    of = jnp.arange(heads) % key_heads if without == "key_head_map" else jnp.arange(heads) // (heads // key_heads)
    q, k = q[:, of], k[:, of]
    g = decay_of(h, w, precision)
    if without == "decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_mm(h, w["gdn_b"], precision))
    o = _recurrence(_round(q, precision), _round(k, precision), _round(v.reshape(seq, heads, dv), precision), g, beta,
                    delta_term=without != "delta_term")
    o = _rms_norm(o, w["gdn_norm"], s["eps"]).reshape(seq, heads * dv)  # a plain weight: no `+ 1` here
    gate = 1.0 if without == "output_gate" else jax.nn.silu(_mm(h, w["wz"], precision))
    return x + _mm(gate * o, w["wo"], precision)


def _rope(x, rotary: int, theta: float):
    """x [S, H, D]: the first `rotary` columns of every head turned in
    half-split pairs (i, i + rotary / 2) by position * theta**(-2i / rotary);
    the other columns pass."""
    seq, half = x.shape[0], rotary // 2
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., rotary:]], axis=-1)


def _attend(q, k, v, precision: str):
    """One head: q, k, v [S, D].  Causal softmax attention, a block of query
    rows at a time: a plain masked product."""
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
    return out.reshape(seq, v.shape[1])


def _attention(x, w, s, precision: str):
    seq = x.shape[0]
    heads, kv_heads, dim, without = s["heads"], s["kv_heads"], s["head_dim"], s.get("left_out")
    h = _norm(x, w["attn_norm"], s)
    q = _norm(_mm(h, w["wq"], precision).reshape(seq, heads, dim), w["q_norm"], s)
    k = _norm(_mm(h, w["wk"], precision).reshape(seq, kv_heads, dim), w["k_norm"], s)
    v = _mm(h, w["wv"], precision).reshape(seq, kv_heads, dim)
    rotary = dim if without == "rotate_all" else s["rotary"]
    q, k = _rope(q, rotary, s["rope_theta"]), _rope(k, rotary, s["rope_theta"])
    attend = jax.checkpoint(functools.partial(_attend, precision=precision))
    group = heads // kv_heads
    out = jnp.concatenate([attend(q[:, i], k[:, i // group], v[:, i // group]) for i in range(heads)], axis=-1)
    if without != "attention_gate":
        out = out * jax.nn.sigmoid(_mm(h, w["attn_out_gate"], precision))
    return x + _mm(out, w["wo"], precision)


def _route(h, w, s):
    """The router: float32 in every precision.  Returns (probabilities [S,
    experts], gates [S, k] renormalised, chosen [S, k])."""
    probs = jax.nn.softmax(jnp.matmul(h, w["router"]), axis=-1)
    gates, chosen = jax.lax.top_k(probs, s["top_k"])
    return probs, gates / jnp.sum(gates, axis=-1, keepdims=True), chosen


def _experts(h, w, s, precision: str):
    """The held experts' part of the mixture plus the shared expert under its
    gate, and the balance term of this layer."""
    probs, gates, chosen = _route(h, w, s)
    one_hot = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32)  # [S, k, experts]
    gate_of = jnp.einsum("sk,ske->es", gates, one_hot)  # [experts, S]: 0 where not chosen
    gate_of = gate_of[s["first"]: s["first"] + s["held"]]

    @jax.checkpoint
    def gated(gate_for_it, w_gate, w_up, w_down):
        return gate_for_it[:, None] * _swiglu(h, w_gate, w_up, w_down, precision)

    def one(y, expert):
        return y + gated(*expert), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate_of, w["w_gate"], w["w_up"], w["w_down"]))
    shared = _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], precision)
    if s.get("left_out") != "shared_gate":
        shared = jax.nn.sigmoid(jnp.matmul(h, w["shared_scale"])) * shared  # [S, 1]: float32 in every precision
    share = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)  # f_i
    return y + shared, s["aux_coef"] * s["experts"] * jnp.sum(share * jnp.mean(probs, axis=0))


def _block(x, w, mixer: str, s, precision: str):
    """One layer: (the stream after it, its balance term)."""
    x = (_gdn if mixer == "gdn" else _attention)(x, w, s, precision)
    y, aux = _experts(_norm(x, w["mlp_norm"], s), w, s, precision)
    return x + y, aux


def _layers(weights: Weights, s):
    """Every layer's (mixer, its weights), first to last."""
    at: Dict[str, int] = {}
    for mixer, _ in s["plan"]:
        name = stack_of(mixer)
        i = at.get(name, 0)
        at[name] = i + 1
        yield mixer, {leaf: value[i] for leaf, value in weights[name].items()}


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32"):
    """Mean next-token cross-entropy of one sequence plus its layers' balance
    terms; tokens, targets: [S]."""
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        aux = 0.0
        for mixer, w in _layers(weights, s):
            x, layer_aux = jax.checkpoint(functools.partial(_block, mixer=mixer, s=s, precision=precision))(x, w)
            aux = aux + layer_aux
        h = _norm(x, weights["final_norm"], s)
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


def one_sequence_fn(config: Dict[str, Any], precision: str = "float32", left_out: str = ""):
    """The jitted (weights, tokens[S], targets[S]) -> (loss, gradient tree);
    `left_out`: one of `LEFT_OUT`, for the readings that show the comparison
    catches a model without that piece."""
    assert not left_out or left_out in LEFT_OUT, left_out
    return _one_sequence(tuple(sorted(dict(sizes_of(config), left_out=left_out).items())), precision)


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
        for mixer, w in _layers(weights, s):
            mixed = (_gdn if mixer == "gdn" else _attention)(x, w, s, precision)
            chosen.append(jnp.sort(_route(_norm(mixed, w["mlp_norm"], s), w, s)[2], axis=-1))
            x = _block(x, w, mixer, s, precision)[0]
    return jnp.stack(chosen)


def decay_statistics(weights: Weights, tokens, config: Dict[str, Any]) -> Dict[str, float]:
    """The seeded distribution of alpha = exp(g) over positions, value heads
    and Gated DeltaNet layers of one sequence: its mean and the shares under
    0.5 and 0.01 (the scan runs neither as a plain delta rule, alpha = 1, nor
    with a state that is never read, alpha = 0)."""
    s = sizes_of(config)
    alphas = []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for mixer, w in _layers(weights, s):
            if mixer == "gdn":
                alphas.append(jnp.exp(decay_of(_norm(x, w["attn_norm"], s), w)))
            x = _block(x, w, mixer, s, "float32")[0]
    alpha = jnp.stack(alphas)
    return {"mean": float(jnp.mean(alpha)), "share_under_half": float(jnp.mean(alpha < 0.5)),
            "share_under_a_hundredth": float(jnp.mean(alpha < 0.01))}
