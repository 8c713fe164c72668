"""Plain reference of a decoder-only language model that mixes Kimi Delta
Attention (KDA: a gated delta rule with a decay a channel) with unrotated
latent attention (MLA, NoPE) 3 : 1 over bias-corrected sigmoid routing beside a
shared expert and a leading dense layer (Kimi-Linear-48B-A3B, `model_type:
kimi_linear`), and its weights.

Written from the published description (the model's `config.json` and the
paper, Kimi Linear, arXiv:2510.26692; the expert and latent-attention forms
are DeepSeek-V3's, arXiv:2412.19437) in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`.  No kernels, no chunks, no sort, no
grouped matmul, no batching: one sequence at a time, **the recurrence position
by position** (`lax.scan` over t with the state [heads, 128, 128]; never the
chunk form the program runs, so that the comparison is of two algorithms),
the convolutions as explicit shifts, latent attention one head and one block
of queries at a time, and the experts as a masked loop over the experts HELD
HERE.  It shares no code with `torchft_tpu/`; the two have in common the layout
of the weight tree (`make_weights`) and the router's bias (`router_bias`).

S positions, E = hidden, H heads, d = the linear layers' head width.  Every
layer is `x <- x + Mixer(RMSNorm(x))`, then `x <- x + FeedForward(RMSNorm(x))`.
Positions before the first are zeros; the state before the first position is
zero.  No layer has a positional term.

KDA mixer (`linear_attn_config.kda_layers`), u = RMSNorm(x) [S, E]:

    q~ = u Wq, k~ = u Wk, v~ = u Wv                      (each E -> H d)
    c_t = sum_{i=0..3} w_i * z_{t-3+i}                   (a weight a channel and tap, no bias), then SiLU, on each
    q_t = L2(.) d**-0.5,  k_t = L2(.),  v_t              (the L2 norm a head: x / sqrt(sum x^2 + 1e-6))
    g_t = -exp(A_h) softplus(u Wa_down Wa_up + b_dt)_t   (a number a channel and head; alpha_t = exp(g_t) in (0, 1))
    beta_t = sigmoid(u Wbeta)_t                          (a number a head)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T     (a head's state [d keys, d values], float32)
    o_t = S_t^T q_t
    y_t = [ sigmoid(u Wg_down Wg_up + b_g)_t * RMSNorm_head(o_t) ] Wo          (the norm over a head's d columns, one weight of d)

MLA mixer (`linear_attn_config.full_attn_layers`), `mla_use_nope`: `q = u Wq`
[S, H, 128 + 64]; `[c ; k_r] = u Wkva` (E -> 512 + 64); `[k_n ; v] = RMSNorm(c)
Wkvb`; `k = [k_n ; k_r]` with the ONE 64-column k_r given to every head and
nothing rotated; causal softmax attention at scale 192**-0.5, values 128 wide;
Wo.

Feed-forward: the first `first_k_dense_replace` layers a SwiGLU at
`intermediate_size`; the others `s = sigmoid(u Wr)` in float32 over ALL the
router's outputs, the k largest of `s + b` chosen (b: the constant bias, never
in a gate), `g_i = s_i / sum_chosen s * routed_scaling_factor`, `y = sum_{chosen
i HELD HERE} g_i E_i(u) + Shared(u)`.  Then the final RMSNorm, the untied head
and the mean next-token cross-entropy over the vocabulary slice.  Training
adds, per sparse layer and sequence, DeepSeek-V3's sequence-wise balance term
times `aux_loss_alpha`.

**One chip's share.**  `num_experts` counts the experts held here (the
`expert_parallel` group says which of the router's outputs they are); the
router keeps its published width, and what the experts held elsewhere would
add is left out — here as in the program.  With every expert held the same
code is the uncut layer, which is how the test that the shares add up reads it.

Departures from the published description, each without effect on the
arithmetic or noted where it has one:

- `jax.checkpoint` around each block, each block of positions of the
  recurrence (a scan over blocks of a checkpointed scan: 16,384 states of 2 MB
  are never alive together), each attention head and block of queries, and
  each expert of the loop: recomputed in the backward pass, not computed
  differently.
- The bias b is a buffer the published training updates from the experts'
  load, outside the gradient; here it is constant, made from the
  configuration's `router_bias` seed.
- What the catalog does not carry (the gates' rank and biases, the decay's
  initialisation, SiLU after each convolution, the scale on q) is the
  configuration file's `assumed`.

`precision` selects what the matmul operands are rounded to before each matrix
product: "float32" is the reference; "bfloat16" imitates what the configuration
states for the program; "float8" (e4m3, per-tensor scale) is the control.  The
recurrence rounds q, k and v as its products' operands and keeps the state in
float32; the router's product stays in float32 in every precision.
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
# fails the comparison (`benchmark/tools/routing_ties_kda.py --left-out 1`): the decay (alpha = 1), the beta k k^T
# term (the state only accumulates beta k v^T under its decay), the convolutions' three earlier taps, the L2 norm
# of q and k, the output's sigmoid gate; and "rotation" ADDS what this model leaves out, a rotary turn of the
# latent layers' 64 shared columns.
LEFT_OUT = ("decay", "delta_term", "convolution", "qk_norm", "output_gate", "rotation")
_STREAM = ("wo", "w_down", "shared_down")  # projections that write into the residual stream


def layer_plan(config: Dict[str, Any]) -> List[Tuple[str, bool]]:
    """(mixer, sparse) of every layer within the depth, first to last: the
    mixer from `linear_attn_config`'s two lists (numbered from 1, of which the
    first `num_hidden_layers` count), dense for the first
    `first_k_dense_replace`."""
    linear = config["linear_attn_config"]
    plan = []
    for i in range(1, config["num_hidden_layers"] + 1):
        if (i in linear["kda_layers"]) == (i in linear["full_attn_layers"]):
            raise ValueError(f"layer {i} is in neither or both of kda_layers and full_attn_layers")
        plan.append(("kda" if i in linear["kda_layers"] else "mla", i > config["first_k_dense_replace"]))
    return plan


def stack_of(mixer: str, sparse: bool) -> str:
    """The subtree of the weights a kind of layer is stacked under."""
    return f"{mixer}_{'layers' if sparse else 'dense'}"


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by short names.  `held` experts
    `first ... first + held - 1` of the router's `experts` outputs live here."""
    if config.get("q_lora_rank") is not None or config["num_expert_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("written for q_lora_rank null and one routing group")
    if config["moe_router_activation_func"] != "sigmoid" or not config["moe_renormalize"]:
        raise ValueError("written for the sigmoid, bias-corrected, renormalised router")
    if not config["mla_use_nope"] or config["tie_word_embeddings"] or config["num_nextn_predict_layers"]:
        raise ValueError("written for unrotated latent attention, an untied head and no extra prediction layers")
    share = config.get("expert_parallel") or {}
    bias = config.get("router_bias") or {"seed": 0, "scale": 0.0}
    linear = config["linear_attn_config"]
    return {
        "plan": tuple(layer_plan(config)),
        "vocab": config["vocab_size"], "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"], "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"], "rank": config["kv_lora_rank"],
        "kda_heads": linear["num_heads"], "kda_dim": linear["head_dim"], "conv": linear["short_conv_kernel_size"],
        "dense_ffn": config["intermediate_size"], "ffn": config["moe_intermediate_size"],
        "held": config["num_experts"],
        "experts": share.get("router_outputs", config["num_experts"]),
        "first": share.get("first_expert_held", 0),
        "shared": config["num_shared_experts"],
        "init_depth": (config.get("published") or {}).get("num_hidden_layers", config["num_hidden_layers"]),
        "top_k": config["num_experts_per_token"],
        "route_scale": float(config["routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "aux_coef": float(config["aux_loss_alpha"]),
        "bias_seed": int(bias["seed"]), "bias_scale": float(bias["scale"]),
    }


def router_bias(config: Dict[str, Any]) -> np.ndarray:
    """The router's choice bias b, [sparse layers, router outputs] float32, the
    sparse layers in their order in the model whatever their mixer: normal at
    the configuration's `router_bias.scale` from its `seed` (not from the
    run's: a buffer of the deployment, the same in every run)."""
    s = sizes_of(config)
    return _bias(s["bias_seed"], s["bias_scale"], sum(sparse for _, sparse in s["plan"]), s["experts"])


def _bias(seed: int, scale: float, layers: int, experts: int) -> np.ndarray:
    return (np.random.default_rng([seed, 0xB1A5]).standard_normal((layers, experts)) * scale).astype(np.float32)


# -- the weights ---------------------------------------------------------------


def _stack_weights(key, n: int, mixer: str, sparse: bool, s: Dict[str, Any]) -> Weights:
    """One stack of `n` layers of a kind."""
    hidden = s["hidden"]
    names = iter(jax.random.split(key, 32))

    def normal(shape, fan_in, name=""):
        scale = fan_in ** -0.5 * ((2 * s["init_depth"]) ** -0.5 if name in _STREAM else 1.0)
        return jax.random.normal(next(names), (n,) + shape, jnp.float32) * scale

    out = {"attn_norm": jnp.ones((n, hidden), jnp.float32), "mlp_norm": jnp.ones((n, hidden), jnp.float32)}
    if mixer == "kda":
        heads, dim, taps = s["kda_heads"], s["kda_dim"], s["conv"]
        wide = heads * dim
        steps = jnp.exp(jax.random.uniform(next(names), (n, wide), jnp.float32, np.log(0.001), np.log(0.1)))
        out.update(
            wq=normal((hidden, wide), hidden), wk=normal((hidden, wide), hidden), wv=normal((hidden, wide), hidden),
            kda_conv_q=normal((taps, wide), taps), kda_conv_k=normal((taps, wide), taps),
            kda_conv_v=normal((taps, wide), taps),
            kda_a_down=normal((hidden, dim), hidden), kda_a_up=normal((dim, wide), dim),
            A_log=jnp.log(jax.random.uniform(next(names), (n, heads), jnp.float32, 1.0, 16.0)),
            dt_bias=steps + jnp.log(-jnp.expm1(-steps)),  # the inverse of softplus
            kda_beta=normal((hidden, heads), hidden),
            kda_g_down=normal((hidden, dim), hidden), kda_g_up=normal((dim, wide), dim),
            kda_g_bias=jnp.zeros((n, wide), jnp.float32), kda_norm=jnp.ones((n, dim), jnp.float32),
            wo=normal((wide, hidden), wide, "wo"),
        )
    else:
        heads, nope, rope, v_dim, rank = s["heads"], s["nope"], s["rope"], s["v_dim"], s["rank"]
        out.update(
            wq=normal((hidden, heads * (nope + rope)), hidden), wkv_a=normal((hidden, rank + rope), hidden),
            kv_norm=jnp.ones((n, rank), jnp.float32), wkv_b=normal((rank, heads * (nope + v_dim)), rank),
            wo=normal((heads * v_dim, hidden), heads * v_dim, "wo"),
        )
    if sparse:
        ffn, held, shared = s["ffn"], s["held"], s["shared"] * s["ffn"]
        out.update(
            router=normal((hidden, s["experts"]), hidden),
            w_gate=normal((held, hidden, ffn), hidden), w_up=normal((held, hidden, ffn), hidden),
            w_down=normal((held, ffn, hidden), ffn, "w_down"),
            shared_gate=normal((hidden, shared), hidden), shared_up=normal((hidden, shared), hidden),
            shared_down=normal((shared, hidden), shared, "shared_down"),
        )
    else:
        ffn = s["dense_ffn"]
        out.update(w_gate=normal((hidden, ffn), hidden), w_up=normal((hidden, ffn), hidden),
                   w_down=normal((ffn, hidden), ffn, "w_down"))
    return out


@functools.partial(jax.jit, static_argnames=("frozen_sizes",))
def _weights(key, frozen_sizes) -> Weights:
    s = dict(frozen_sizes)
    k_embed, k_head, k_stacks = jax.random.split(key, 3)
    counts: Dict[str, Tuple[str, bool, int]] = {}
    for mixer, sparse in s["plan"]:
        name = stack_of(mixer, sparse)
        counts[name] = (mixer, sparse, counts.get(name, (mixer, sparse, 0))[2] + 1)
    out = {
        "embed": jax.random.normal(k_embed, (s["vocab"], s["hidden"]), jnp.float32),
        "final_norm": jnp.ones((s["hidden"],), jnp.float32),
        "lm_head": jax.random.normal(k_head, (s["hidden"], s["vocab"]), jnp.float32) * s["hidden"] ** -0.5,
    }
    for i, (name, (mixer, sparse, n)) in enumerate(sorted(counts.items())):
        out[name] = _stack_weights(jax.random.fold_in(k_stacks, i), n, mixer, sparse, s)
    return out


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device: one stacked subtree a kind of layer (`stack_of`: "kda_dense",
    "kda_layers", "mla_layers"), each kind's layers in their order in the
    model, a layer's held experts on the next axis.  Matrices are normal with
    standard deviation fan_in**-0.5 (the convolutions' taps over the kernel
    size), norms at one, the gates' biases zero, embedding rows at unit scale;
    the projections that write into the residual stream (Wo and every Wdown)
    smaller by sqrt(2 * layers of the PUBLISHED model), the scaled
    initialisation of output layers (`reference/mla_moe_lm.py` says what goes
    wrong without it).  The decay is the published layer's: `A_log` = log
    U(1, 16) a head and `dt_bias` the inverse softplus of log-uniform steps in
    [0.001, 0.1] a channel, both float32."""
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
    """The gated delta rule position by position: q, k, v, g [S, H, d], beta
    [S, H] -> o [S, H, d].  The state [H, d keys, d values] is float32."""
    seq, heads, dim = q.shape
    block = POSITION_BLOCK if seq % POSITION_BLOCK == 0 else seq

    def position(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[:, :, None]                               # Diag(alpha) S
        seen = jnp.einsum("hk,hkv->hv", kt, state) if delta_term else 0.0    # k^T S
        state = state + (bt[:, None] * kt)[:, :, None] * (vt - seen)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", qt, state)

    def positions(state, xs):
        return jax.lax.scan(position, state, xs)

    blocks = tuple(a.reshape(seq // block, block, *a.shape[1:]) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(jax.checkpoint(positions), jnp.zeros((heads, dim, v.shape[2]), g.dtype), blocks)
    return o.reshape(seq, heads, v.shape[2])


def decay_of(h, w, s, precision: str = "float32"):
    """g [S, H, d] <= 0 from the normed input h: -exp(A) softplus(low-rank + b_dt)."""
    a = _mm(_mm(h, w["kda_a_down"], precision), w["kda_a_up"], precision) + w["dt_bias"]
    return -jnp.repeat(jnp.exp(w["A_log"]), s["kda_dim"]).reshape(1, -1) * jax.nn.softplus(a)


def _kda(x, w, s, precision: str):
    seq = x.shape[0]
    heads, dim, without = s["kda_heads"], s["kda_dim"], s.get("left_out")
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    conv = (lambda z, taps: taps[-1] * z) if without == "convolution" else _short_conv
    q, k, v = (jax.nn.silu(conv(_mm(h, w[name], precision), w[taps])).reshape(seq, heads, dim)
               for name, taps in (("wq", "kda_conv_q"), ("wk", "kda_conv_k"), ("wv", "kda_conv_v")))
    if without != "qk_norm":
        q, k = _l2(q), _l2(k)
    q = q * dim ** -0.5
    g = decay_of(h, w, s, precision).reshape(seq, heads, dim)
    if without == "decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_mm(h, w["kda_beta"], precision))
    o = _recurrence(_round(q, precision), _round(k, precision), _round(v, precision), g, beta,
                    delta_term=without != "delta_term")
    o = _rms_norm(o, w["kda_norm"], s["eps"]).reshape(seq, heads * dim)
    gate = jax.nn.sigmoid(_mm(_mm(h, w["kda_g_down"], precision), w["kda_g_up"], precision) + w["kda_g_bias"])
    return x + _mm((1.0 if without == "output_gate" else gate) * o, w["wo"], precision)


def _attend(q, k, v, precision: str):
    """One head: q, k [S, Dqk], v [S, Dv].  Causal softmax attention, a block
    of queries at a time."""
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


def _rope(x, theta: float = 10000.0):
    """x [S, D] turned in half-split pairs (i, i + D/2) by position * theta**(-2i/D): what this model does NOT
    do to its latent layers' shared columns (`LEFT_OUT`'s "rotation")."""
    seq, dim = x.shape
    half = dim // 2
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle), a * jnp.sin(angle) + b * jnp.cos(angle)], axis=-1)


def _mla(x, w, s, precision: str):
    seq = x.shape[0]
    heads, nope, v_dim, rank = s["heads"], s["nope"], s["v_dim"], s["rank"]
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    q = _mm(h, w["wq"], precision).reshape(seq, heads, nope + s["rope"])
    latent = _mm(h, w["wkv_a"], precision)
    c_kv, k_shared = latent[:, :rank], latent[:, rank:]  # k_shared [S, 64]: every head's, not rotated
    kv = _mm(_rms_norm(c_kv, w["kv_norm"], s["eps"]), w["wkv_b"], precision).reshape(seq, heads, nope + v_dim)
    if s.get("left_out") == "rotation":
        k_shared = _rope(k_shared)
        q = jnp.concatenate([q[..., :nope], jax.vmap(_rope, in_axes=1, out_axes=1)(q[..., nope:])], axis=-1)
    attend = jax.checkpoint(functools.partial(_attend, precision=precision))
    out = [attend(q[:, i], jnp.concatenate([kv[:, i, :nope], k_shared], axis=-1), kv[:, i, nope:])
           for i in range(heads)]
    return x + _mm(jnp.concatenate(out, axis=-1), w["wo"], precision)


def _route(h, w, bias, s):
    """The router: float32 in every precision.  Returns (scores [S, experts],
    gates [S, k], chosen [S, k])."""
    scores = jax.nn.sigmoid(jnp.matmul(h, w["router"]))
    _, chosen = jax.lax.top_k(scores + bias, s["top_k"])
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * s["route_scale"]
    return scores, gates, chosen


def _experts(h, w, bias, s, precision: str):
    """The held experts' part of the mixture plus the shared expert, and the
    balance loss of this layer."""
    scores, gates, chosen = _route(h, w, bias, s)
    one_hot = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32)  # [S, k, experts]
    gate_of = jnp.einsum("sk,ske->es", gates, one_hot)  # [experts, S]: 0 where not chosen
    gate_of = gate_of[s["first"]: s["first"] + s["held"]]

    def one(y, expert):
        gate_for_it, w_gate, w_up, w_down = expert
        out = jax.checkpoint(functools.partial(_swiglu, precision=precision))(h, w_gate, w_up, w_down)
        return y + gate_for_it[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate_of, w["w_gate"], w["w_up"], w["w_down"]))
    y = y + _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], precision)
    share = jnp.mean(jnp.sum(one_hot, axis=1), axis=0) * s["experts"] / s["top_k"]  # f_i
    mean_score = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)  # P_i
    return y, s["aux_coef"] * jnp.sum(share * mean_score)


def _block(x, w, bias, mixer: str, sparse: bool, s, precision: str):
    """One layer: (the stream after it, its balance loss)."""
    x = (_kda if mixer == "kda" else _mla)(x, w, s, precision)
    h = _rms_norm(x, w["mlp_norm"], s["eps"])
    if not sparse:
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], precision), 0.0
    y, aux = _experts(h, w, bias, s, precision)
    return x + y, aux


def _layers(weights: Weights, s):
    """Every layer's (mixer, sparse, its weights, its row of the bias or None), first to last."""
    at: Dict[str, int] = {}
    sparse_at = 0
    for mixer, sparse in s["plan"]:
        name = stack_of(mixer, sparse)
        i = at.get(name, 0)
        at[name] = i + 1
        yield mixer, sparse, {leaf: value[i] for leaf, value in weights[name].items()}, (sparse_at if sparse else None)
        sparse_at += sparse


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32"):
    """Mean next-token cross-entropy of one sequence plus its sparse layers'
    balance losses; tokens, targets: [S]."""
    bias = _bias(s["bias_seed"], s["bias_scale"], sum(sparse for _, sparse in s["plan"]), s["experts"])
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        aux = 0.0
        for mixer, sparse, w, row in _layers(weights, s):
            block = jax.checkpoint(functools.partial(_block, mixer=mixer, sparse=sparse, s=s, precision=precision))
            x, layer_aux = block(x, w, bias[row] if sparse else None)
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
    sparse layer: [sparse layers, S, k], each position's k sorted by expert
    id.  What a program's choices are set against, to count the near-ties
    between the k-th and the next expert that fell the other way."""
    s = sizes_of(config)
    bias = router_bias(config)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        for mixer, sparse, w, row in _layers(weights, s):
            if sparse:
                mixed = (_kda if mixer == "kda" else _mla)(x, w, s, precision)
                chosen.append(jnp.sort(_route(_rms_norm(mixed, w["mlp_norm"], s["eps"]), w, bias[row], s)[2], axis=-1))
            x = _block(x, w, bias[row] if sparse else None, mixer, sparse, s, precision)[0]
    return jnp.stack(chosen)


def decay_statistics(weights: Weights, tokens, config: Dict[str, Any]) -> Dict[str, float]:
    """The seeded distribution of alpha = exp(g) over positions, heads,
    channels and KDA layers of one sequence: its mean and the share under 0.5
    (the scan runs neither as a plain delta rule, alpha = 1, nor with a state
    that is never read, alpha = 0)."""
    s = sizes_of(config)
    bias = router_bias(config)
    alphas = []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens]
        for mixer, sparse, w, row in _layers(weights, s):
            if mixer == "kda":
                alphas.append(jnp.exp(decay_of(_rms_norm(x, w["attn_norm"], s["eps"]), w, s)))
            x = _block(x, w, bias[row] if sparse else None, mixer, sparse, s, "float32")[0]
    alpha = jnp.stack(alphas)
    return {"mean": float(jnp.mean(alpha)), "share_under_half": float(jnp.mean(alpha < 0.5)),
            "share_under_a_hundredth": float(jnp.mean(alpha < 0.01))}
