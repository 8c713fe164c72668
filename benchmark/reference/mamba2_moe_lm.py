"""Plain reference of a decoder-only language model whose blocks are ONE norm
and ONE part each — a Mamba-2 state-space mixer, causal grouped-query attention
without a position term, or un-gated ReLU^2 experts beside a shared expert —
in the order a pattern string gives (the causal tower of
Nemotron-Labs-TwoTower-30B-A3B-Base, `model_type: nemotron_h`), and its weights.

Written from the published description (the model's `config.json`; Mamba-2 is
arXiv:2405.21060, the bias-corrected sigmoid router DeepSeek-V3's,
arXiv:2412.19437) in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`.  No kernels, no chunks, no sort, no
grouped matmul, no batching: one sequence at a time, **the recurrence position
by position** (`lax.scan` over t with the state [heads, 64, 128]; never the
chunk form the program runs, so that the comparison is of two algorithms), the
convolution as explicit shifts, attention one head and one block of queries at
a time, and the experts as a masked loop over the experts HELD HERE.  It shares
no code with `torchft_tpu/`; the two have in common the layout of the weight
tree (`make_weights`) and the router's bias (`router_bias`).

S positions, E = hidden.  Every block l is `x <- x + part_l(RMSNorm(x; w_l))`
with part_l read off `hybrid_override_pattern`'s l-th letter; after the last,
`logits = RMSNorm(x; w_f) W_head` (untied) and the mean next-token cross-entropy
over the vocabulary slice.  No bias on any product.

`M`, Mamba-2: H heads of P columns, G groups of H / G heads, a state of N rows a
head, u = RMSNorm(x) [S, E]:

    [z | c | dt] = u W_in                    E -> H P + (H P + 2 G N) + H;  c = [x | B | C]
    c_t = SiLU( sum_{i=0..3} w_i * c_{t-3+i} + b )     a weight a channel and tap, zeros before the first position
    dt_t = softplus(dt_t + dt_bias)           a number a head, float32, not clamped (time_step_limit (0, inf))
    a_t = exp(dt_t A),  A = -exp(A_log)       a scalar decay a head
    S_t = a_t S_{t-1} + dt_t x_t B_t^T        a head h: S [P, N], x_t [P]; B_t, C_t [N] are group h // (H / G)'s
    y_t = S_t C_t + D_h x_t
    o = RMSNorm_groups( y * SiLU(z); w_n )    the gate FIRST, then a norm over each of the G groups of H P / G columns
    part = o W_out

`*`, attention: q = u Wq [S, heads, d], k = u Wk, v = u Wv [S, kv heads, d], a
query head h attends to KV head h // (heads / kv heads), causal softmax at scale
d**-0.5, NOTHING rotated; Wo.

`E`, experts: `s = sigmoid(u Wr)` in float32 over ALL the router's outputs, the
k largest of `s + b` chosen (b: the constant bias, never in a gate), `g_i = scale
* s_i / (sum_chosen s + 1e-20)`, `f_w(u) = max(u W_up, 0)^2 W_down`, `part =
sum_{chosen i HELD HERE} g_i f_i(u) + f_shared(u)`.

**One chip's share.**  `n_routed_experts` counts the experts held here (the
`expert_parallel` group says which of the router's outputs they are); the
router keeps its published width, and what the experts held elsewhere would
add is left out — here as in the program.  With every expert held the same
code is the uncut block, which is how the test that the shares add up reads it.

Departures from the published description, each without effect on the
arithmetic or noted where it has one:

- The published repository describes a second, "denoiser" tower, conditioning
  between the towers and decoding by diffusion over blocks.  Its `config.json`
  has no key and no parameter for any of that: it is NOT written, here or in
  the program.  This is the causal tower under next-token cross-entropy.
- `jax.checkpoint` around each block, inside a Mamba-2 block around what stands
  before and after the recurrence, each block of positions of the recurrence (a
  scan over blocks of a checkpointed scan: 16,384 states of 2 MB are never alive
  together), each attention head and block of queries, each expert of the loop
  and each block of positions of the head's logits: recomputed in the backward
  pass, not computed differently.
- The bias b is a buffer the published training updates from the experts' load,
  outside the gradient; here it is constant, made from the configuration's
  `router_bias` seed.
- What the catalog does not carry (the initialisation, the position term's
  absence, the order of gate and norm) is the configuration file's `assumed`.

`precision` selects what the matmul operands are rounded to before each matrix
product: "float32" is the reference; "bfloat16" imitates what the configuration
states for the program; "float8" (e4m3, per-tensor scale) is the control.  The
recurrence rounds dt * x, B and C as its products' operands and keeps the state
in float32; the router's product stays in float32 in every precision.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, Any]

QUERY_BLOCK = 2048
POSITION_BLOCK = 128
# Pieces of the mathematics that `loss(..., left_out=...)` computes WITHOUT, for the readings that show each one
# fails the comparison (`benchmark/tools/routing_ties_mamba2.py --left-out 1`): the decay (a_t = 1), the D skip,
# the convolution's three earlier taps, the gate SiLU(z), the group norm, the square (ReLU for ReLU^2), the
# router's scale (1 for 2.5), the shared expert.
LEFT_OUT = ("decay", "skip", "convolution", "gate", "group_norm", "square", "route_scale", "shared_expert")
_STREAM = ("ssm_out", "wo", "w_down", "shared_down")  # projections that write into the residual stream
_STACKS = {"M": "mamba", "*": "attn", "E": "moe"}


def layer_plan(config: Dict[str, Any]) -> List[str]:
    """The letter of every block within the depth, first to last: `M` a
    Mamba-2 mixer, `*` attention, `E` experts."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) < config["num_hidden_layers"]:
        raise ValueError(f"the pattern has {len(pattern)} letters for {config['num_hidden_layers']} blocks")
    plan = list(pattern[:config["num_hidden_layers"]])
    for letter in plan:
        if letter not in _STACKS:
            raise ValueError(f"pattern letter {letter!r}: written for M (Mamba-2), * (attention) and E (experts)")
    return plan


def stack_of(letter: str) -> str:
    """The subtree of the weights a kind of block is stacked under."""
    return _STACKS[letter]


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by short names.  `held` experts
    `first ... first + held - 1` of the router's `experts` outputs live here."""
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("written for one routing group")
    if config["tie_word_embeddings"] or config.get("sliding_window") is not None or config["residual_in_fp32"]:
        raise ValueError("written for an untied head, no window and a stream in the compute type")
    if any(config[key] for key in ("use_bias", "mamba_proj_bias", "mlp_bias", "attention_bias")):
        raise ValueError("written for products without a bias")
    if not config["use_conv_bias"] or not config["norm_topk_prob"]:
        raise ValueError("written for a convolution with a bias and renormalised gates")
    if config["mlp_hidden_act"] != "relu2" or config["mamba_hidden_act"] != "silu":
        raise ValueError("written for ReLU^2 feed-forwards and SiLU in the mixer")
    low, high = config["time_step_limit"]
    if low not in (0, 0.0) or high is not None:
        raise ValueError("written for a time step that is not clamped")
    share = config.get("expert_parallel") or {}
    bias = config.get("router_bias") or {"seed": 0, "scale": 0.0}
    return {
        "plan": tuple(layer_plan(config)),
        "vocab": config["vocab_size"], "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"], "kv_heads": config["num_key_value_heads"], "dim": config["head_dim"],
        "ssm_heads": config["mamba_num_heads"], "ssm_dim": config["mamba_head_dim"], "groups": config["n_groups"],
        "state": config["ssm_state_size"], "conv": config["conv_kernel"],
        "ffn": config["moe_intermediate_size"], "shared_ffn": config["moe_shared_expert_intermediate_size"],
        "n_shared": config["n_shared_experts"],
        "held": config["n_routed_experts"],
        "experts": share.get("router_outputs", config["n_routed_experts"]),
        "first": share.get("first_expert_held", 0),
        "init_depth": (config.get("published") or {}).get("num_hidden_layers", config["num_hidden_layers"]),
        "top_k": config["num_experts_per_tok"],
        "route_scale": float(config["routed_scaling_factor"]),
        "eps": float(config["layer_norm_epsilon"]),
        "dt_min": float(config["time_step_min"]), "dt_max": float(config["time_step_max"]),
        "dt_floor": float(config["time_step_floor"]),
        "bias_seed": int(bias["seed"]), "bias_scale": float(bias["scale"]),
    }


def router_bias(config: Dict[str, Any]) -> np.ndarray:
    """The router's choice bias b, [expert blocks, router outputs] float32, the
    expert blocks in their order in the model: normal at the configuration's
    `router_bias.scale` from its `seed` (not from the run's: a buffer of the
    deployment, the same in every run)."""
    s = sizes_of(config)
    return _bias(s["bias_seed"], s["bias_scale"], s["plan"].count("E"), s["experts"])


def _bias(seed: int, scale: float, layers: int, experts: int) -> np.ndarray:
    return (np.random.default_rng([seed, 0xB1A5]).standard_normal((layers, experts)) * scale).astype(np.float32)


# -- the weights ---------------------------------------------------------------


def _stack_weights(key, n: int, letter: str, s: Dict[str, Any]) -> Weights:
    """One stack of `n` blocks of a kind."""
    hidden = s["hidden"]
    names = iter(jax.random.split(key, 16))

    def normal(shape, fan_in, name=""):
        scale = fan_in ** -0.5 * ((2 * s["init_depth"]) ** -0.5 if name in _STREAM else 1.0)
        return jax.random.normal(next(names), (n,) + shape, jnp.float32) * scale

    norm = jnp.ones((n, hidden), jnp.float32)
    if letter == "M":
        heads, taps = s["ssm_heads"], s["conv"]
        inner = heads * s["ssm_dim"]
        channels = inner + 2 * s["groups"] * s["state"]
        steps = jnp.exp(jax.random.uniform(next(names), (n, heads), jnp.float32, np.log(s["dt_min"]), np.log(s["dt_max"])))
        steps = jnp.maximum(steps, s["dt_floor"])
        return dict(
            attn_norm=norm,
            ssm_in=normal((hidden, inner + channels + heads), hidden),
            ssm_conv=normal((channels, taps), taps),                 # [channel, tap]: the last tap the position itself
            ssm_conv_bias=jnp.zeros((n, channels), jnp.float32),
            dt_bias=steps + jnp.log(-jnp.expm1(-steps)),             # the inverse of softplus
            A_log=jnp.log(jax.random.uniform(next(names), (n, heads), jnp.float32, 1.0, 16.0)),
            ssm_D=jnp.ones((n, heads), jnp.float32),
            ssm_norm=jnp.ones((n, inner), jnp.float32),
            ssm_out=normal((inner, hidden), inner, "ssm_out"),
        )
    if letter == "*":
        heads, kv, dim = s["heads"], s["kv_heads"], s["dim"]
        return dict(attn_norm=norm, wq=normal((hidden, heads * dim), hidden), wk=normal((hidden, kv * dim), hidden),
                    wv=normal((hidden, kv * dim), hidden), wo=normal((heads * dim, hidden), heads * dim, "wo"))
    ffn, held, shared = s["ffn"], s["held"], s["shared_ffn"]
    return dict(
        mlp_norm=norm, router=normal((hidden, s["experts"]), hidden),
        w_up=normal((held, hidden, ffn), hidden), w_down=normal((held, ffn, hidden), ffn, "w_down"),
        shared_up=normal((hidden, shared), hidden), shared_down=normal((shared, hidden), shared, "shared_down"),
    )


@functools.partial(jax.jit, static_argnames=("frozen_sizes",))
def _weights(key, frozen_sizes) -> Weights:
    s = dict(frozen_sizes)
    k_embed, k_head, k_stacks = jax.random.split(key, 3)
    out = {
        "embed": jax.random.normal(k_embed, (s["vocab"], s["hidden"]), jnp.float32),
        "final_norm": jnp.ones((s["hidden"],), jnp.float32),
        "lm_head": jax.random.normal(k_head, (s["hidden"], s["vocab"]), jnp.float32) * s["hidden"] ** -0.5,
    }
    for i, letter in enumerate(sorted(set(s["plan"]))):
        out[stack_of(letter)] = _stack_weights(jax.random.fold_in(k_stacks, i), s["plan"].count(letter), letter, s)
    return out


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device: one stacked subtree a kind of block (`stack_of`: "mamba", "attn",
    "moe"), each kind's blocks in their order in the model, a block's held
    experts on the next axis.  Matrices are normal with standard deviation
    fan_in**-0.5 (the convolution's taps over the kernel size), norms at one,
    the convolution's bias zero, D one, embedding rows at unit scale; the
    projections that write into the residual stream (W_out, Wo and every
    W_down) smaller by sqrt(2 * blocks of the PUBLISHED model), the scaled
    initialisation of output layers (`rescale_prenorm_residual`).  The decay is
    the published layer's: `A_log` = log U(1, 16) a head and `dt_bias` the
    inverse softplus of log-uniform steps in [time_step_min, time_step_max]
    floored at time_step_floor, both float32."""
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
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _relu2_ffn(h, w_up, w_down, precision: str, square: bool = True):
    hidden = jax.nn.relu(_mm(h, w_up, precision))
    return _mm(hidden * hidden if square else hidden, w_down, precision)


def _short_conv(z, taps, bias):
    """z [S, C], taps [C, T], bias [C]: c_t = sum_i taps[:, i] * z_{t - (T - 1) + i}
    + bias, zeros before the first position; each tap an explicit shift."""
    seq, n = z.shape[0], taps.shape[1]
    out = jnp.zeros_like(z) + bias
    for i in range(n):
        back = n - 1 - i
        out = out + taps[:, i] * jnp.concatenate([jnp.zeros((back, z.shape[1]), z.dtype), z[:seq - back]], axis=0)
    return out


def _recurrence(xdt, b, c, la, dim: int):
    """The state-space recurrence position by position: xdt [S, G, (H / G) P]
    (dt * x, a group's heads side by side), b, c [S, G, N] (a group's, its
    heads' alike), la [S, G, H / G] the log decay -> y [S, G, (H / G) P].  The
    state [G, H / G, P, N] is float32 (the N = 128 rows last, and the heads' 64
    columns joined outside the loop: a last axis of 64 is stored as 128 on a
    TPU, which doubled every array the backward pass keeps)."""
    seq, groups, wide = xdt.shape
    per_group = wide // dim
    block = POSITION_BLOCK if seq % POSITION_BLOCK == 0 else seq

    def position(state, xs):
        xt, bt, ct, lt = xs
        state = state * jnp.exp(lt)[:, :, None, None] + xt.reshape(groups, per_group, dim, 1) * bt[:, None, None, :]
        return state, jnp.einsum("ghpn,gn->ghp", state, ct).reshape(groups, wide)

    def positions(state, xs):
        return jax.lax.scan(position, state, xs)

    blocks = tuple(a.reshape(seq // block, block, *a.shape[1:]) for a in (xdt, b, c, la))
    _, y = jax.lax.scan(jax.checkpoint(positions), jnp.zeros((groups, per_group, dim, b.shape[2]), la.dtype), blocks)
    return y.reshape(seq, groups, wide)


def _ssm_widths(s):
    inner = s["ssm_heads"] * s["ssm_dim"]
    return inner, inner + 2 * s["groups"] * s["state"]


def decay_of(h, w, s, precision: str = "float32"):
    """(dt [S, H], the log decay dt * A [S, H] <= 0) from the normed input h."""
    inner, channels = _ssm_widths(s)
    dt = jax.nn.softplus(_mm(h, w["ssm_in"][:, inner + channels:], precision) + w["dt_bias"])
    return dt, -jnp.exp(w["A_log"]) * dt


def _before_the_scan(x, w, s, precision: str):
    """(z [S, H P], x [S, H, P], dt * x rounded as an operand, B and C [S, G, N]
    rounded, the log decay [S, H]) from the stream."""
    seq = x.shape[0]
    heads, dim, groups, state, without = s["ssm_heads"], s["ssm_dim"], s["groups"], s["state"], s.get("left_out")
    inner, channels = _ssm_widths(s)
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    z = _mm(h, w["ssm_in"][:, :inner], precision)
    u = _mm(h, w["ssm_in"][:, inner:inner + channels], precision)
    if without == "convolution":
        u = jax.nn.silu(w["ssm_conv"][:, -1] * u + w["ssm_conv_bias"])
    else:
        u = jax.nn.silu(_short_conv(u, w["ssm_conv"], w["ssm_conv_bias"]))
    xs = u[:, :inner].reshape(seq, heads, dim)
    b = u[:, inner:inner + groups * state].reshape(seq, groups, state)
    c = u[:, inner + groups * state:].reshape(seq, groups, state)
    dt, la = decay_of(h, w, s, precision)
    if without == "decay":
        la = jnp.zeros_like(la)
    return z, xs, _round(xs * dt[:, :, None], precision), _round(b, precision), _round(c, precision), la


def _after_the_scan(x, y, xs, z, w, s, precision: str):
    """The block's result from the scan's y [S, H, P]: the skip, the gate, the
    norm over groups, W_out, and the stream added."""
    seq = x.shape[0]
    groups, without = s["groups"], s.get("left_out")
    inner = _ssm_widths(s)[0]
    if without != "skip":
        y = y + w["ssm_D"][:, None] * xs
    y = y.reshape(seq, inner)
    if without != "gate":
        y = y * jax.nn.silu(z)
    if without != "group_norm":
        y = y.reshape(seq, groups, inner // groups)
        y = (y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + s["eps"])).reshape(seq, inner)
    return x + _mm(y * w["ssm_norm"], w["ssm_out"], precision)


def _mamba(x, w, s, precision: str):
    """A Mamba-2 block in three parts, each recomputed in the backward pass on
    its own (what stands before the scan, the scan, what stands after it): a
    dozen float32 arrays of [S, 6,144] are then never alive together."""
    seq = x.shape[0]
    heads, dim, groups = s["ssm_heads"], s["ssm_dim"], s["groups"]
    z, xs, xdt, b, c, la = jax.checkpoint(functools.partial(_before_the_scan, s=s, precision=precision))(x, w)
    per_group = heads // groups
    y = _recurrence(xdt.reshape(seq, groups, per_group * dim), b, c, la.reshape(seq, groups, per_group), dim)
    return jax.checkpoint(functools.partial(_after_the_scan, s=s, precision=precision))(
        x, y.reshape(seq, heads, dim), xs, z, w)


def _attend(q, k, v, precision: str):
    """One head: q, k, v [S, d].  Causal softmax attention, a block of queries
    at a time."""
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
    heads, kv, dim = s["heads"], s["kv_heads"], s["dim"]
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    q = _mm(h, w["wq"], precision).reshape(seq, heads, dim)
    k = _mm(h, w["wk"], precision).reshape(seq, kv, dim)
    v = _mm(h, w["wv"], precision).reshape(seq, kv, dim)
    attend = jax.checkpoint(functools.partial(_attend, precision=precision))
    out = [attend(q[:, i], k[:, i // (heads // kv)], v[:, i // (heads // kv)]) for i in range(heads)]
    return x + _mm(jnp.concatenate(out, axis=-1), w["wo"], precision)


def _route(h, w, bias, s):
    """The router: float32 in every precision.  Returns (gates [S, k], chosen
    [S, k])."""
    scores = jax.nn.sigmoid(jnp.matmul(h, w["router"]))
    _, chosen = jax.lax.top_k(scores + bias, s["top_k"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    scale = 1.0 if s.get("left_out") == "route_scale" else s["route_scale"]
    return gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20) * scale, chosen


def _experts(x, w, bias, s, precision: str):
    """An expert block: the held experts' part of the mixture plus the shared
    expert, added to the stream."""
    h = _rms_norm(x, w["mlp_norm"], s["eps"])
    gates, chosen = _route(h, w, bias, s)
    one_hot = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32)  # [S, k, experts]
    gate_of = jnp.einsum("sk,ske->es", gates, one_hot)  # [experts, S]: 0 where not chosen
    gate_of = gate_of[s["first"]: s["first"] + s["held"]]
    ffn = functools.partial(_relu2_ffn, precision=precision, square=s.get("left_out") != "square")

    @jax.checkpoint  # the gate inside: outside, the loop would keep every expert's [S, E] output for the gates' gradient
    def gated(gate_for_it, w_up, w_down):
        return gate_for_it[:, None] * ffn(h, w_up, w_down)

    def one(y, expert):
        return y + gated(*expert), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate_of, w["w_up"], w["w_down"]))
    if s.get("left_out") != "shared_expert":
        y = y + ffn(h, w["shared_up"], w["shared_down"])
    return x + y


def _block(x, w, bias, letter: str, s, precision: str):
    if letter == "M":
        return _mamba(x, w, s, precision)
    if letter == "*":
        return _attention(x, w, s, precision)
    return _experts(x, w, bias, s, precision)


def _layers(weights: Weights, s):
    """Every block's (letter, its weights, its row of the bias or None), first to last."""
    at: Dict[str, int] = {}
    for letter in s["plan"]:
        name = stack_of(letter)
        i = at.get(name, 0)
        at[name] = i + 1
        yield letter, {leaf: value[i] for leaf, value in weights[name].items()}, (i if letter == "E" else None)


def _head_loss(h, w_head, targets, precision: str):
    """The mean next-token cross-entropy from the normed stream h [S, E], a
    block of positions at a time: [S, vocabulary] float32 logits, their
    exponentials and their cotangent are then never alive whole."""
    seq = h.shape[0]
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    h, w_head = _round(h, precision), _round(w_head, precision)

    def rows(args):
        h_block, wanted = args
        logits = jnp.matmul(h_block, w_head)
        picked = jnp.take_along_axis(logits, wanted[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = jax.lax.map(jax.checkpoint(rows), (h.reshape(seq // block, block, -1), targets.reshape(seq // block, block)))
    return jnp.sum(sums) / seq


def _loss_of_blocks(outside, blocks, tokens, targets, s: Dict[str, Any], precision: str):
    """The loss from the leaves outside the blocks (embedding, final norm, head)
    and the blocks' own weights, one dict a block, first to last."""
    bias = _bias(s["bias_seed"], s["bias_scale"], s["plan"].count("E"), s["experts"])
    with jax.default_matmul_precision("highest"):
        x = _round(outside["embed"], precision)[tokens]
        expert_blocks = 0
        for letter, w in zip(s["plan"], blocks):
            block = jax.checkpoint(functools.partial(_block, letter=letter, s=s, precision=precision))
            x = block(x, w, bias[expert_blocks] if letter == "E" else None)
            expert_blocks += letter == "E"
        return _head_loss(_rms_norm(x, outside["final_norm"], s["eps"]), outside["lm_head"], targets, precision)


def _apart(weights: Weights, s):
    """(the leaves outside the blocks, the blocks' weights as a list first to last)."""
    stacks = {stack_of(letter) for letter in s["plan"]}
    return {k: v for k, v in weights.items() if k not in stacks}, [w for _, w, _ in _layers(weights, s)]


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32"):
    """Mean next-token cross-entropy of one sequence; tokens, targets: [S]."""
    return _loss_of_blocks(*_apart(weights, s), tokens, targets, s, precision)


def _loss_and_gradient(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str):
    """`loss` and its gradient in the weights' own tree.  The blocks' weights
    are taken out of their stacks BEFORE the differentiation and their
    gradients stacked after it: differentiated through the slicing, each
    block's gradient is padded with zeros to its whole stack and the pads added
    up, which at four expert blocks is 6.4 GB of zeros beside the weights."""
    outside, blocks = _apart(weights, s)
    value, (d_outside, d_blocks) = jax.value_and_grad(_loss_of_blocks, argnums=(0, 1))(
        outside, blocks, tokens, targets, s, precision)
    by_stack: Dict[str, List[Weights]] = {}
    for letter, d_block in zip(s["plan"], d_blocks):
        by_stack.setdefault(stack_of(letter), []).append(d_block)
    stacked = {name: jax.tree.map(lambda *leaves: jnp.stack(leaves), *each) for name, each in by_stack.items()}
    return value, dict(d_outside, **stacked)


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
    return jax.jit(functools.partial(_loss_and_gradient, s=s, precision=precision))


def routing(weights: Weights, tokens, config: Dict[str, Any], precision: str = "float32"):
    """The experts this reference's router chooses for one sequence, per expert
    block: [expert blocks, S, k], each position's k sorted by expert id.  What
    a program's choices are set against, to count the near-ties between the
    k-th and the next expert that fell the other way."""
    s = sizes_of(config)
    bias = router_bias(config)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        for letter, w, row in _layers(weights, s):
            if letter == "E":
                chosen.append(jnp.sort(_route(_rms_norm(x, w["mlp_norm"], s["eps"]), w, bias[row], s)[1], axis=-1))
            x = _block(x, w, bias[row] if row is not None else None, letter, s, precision)
    return jnp.stack(chosen)


def decay_statistics(weights: Weights, tokens, config: Dict[str, Any]) -> Dict[str, float]:
    """The seeded distribution of a = exp(dt A) over positions, heads and
    Mamba-2 blocks of one sequence: its mean and the shares under 0.5 and 0.01
    (the scan runs neither as a plain sum, a = 1, nor with a state that is
    never read, a = 0)."""
    s = sizes_of(config)
    bias = router_bias(config)
    decays = []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for letter, w, row in _layers(weights, s):
            if letter == "M":
                decays.append(jnp.exp(decay_of(_rms_norm(x, w["attn_norm"], s["eps"]), w, s)[1]))
            x = _block(x, w, bias[row] if row is not None else None, letter, s, "float32")
    decay = jnp.stack(decays)
    return {"mean": float(jnp.mean(decay)), "share_under_half": float(jnp.mean(decay < 0.5)),
            "share_under_a_hundredth": float(jnp.mean(decay < 0.01))}
