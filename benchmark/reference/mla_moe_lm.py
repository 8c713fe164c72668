"""Plain reference of a decoder-only language model with latent attention
(MLA), a leading dense layer and bias-corrected sigmoid routing over sparse
experts beside a shared one (the DeepSeek-V3 family; Moonlight-16B-A3B), and
its weights.

Written from the published description (Moonlight-16B-A3B's `config.json`,
`model_type: deepseek_v3`, and the DeepSeek-V2/V3 papers, arXiv:2405.04434
and arXiv:2412.19437) in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`.  No kernels, no sort, no grouped
matmul, no row buffer, no batching: one sequence at a time, attention one head
and one block of queries at a time (16 heads x 8,192 x 8,192 float32 scores
would be 4.3 GB whole), and the experts as a masked loop — every expert HELD
HERE is computed for every position and weighted by that position's gate for
it, which is zero where the router did not choose it.  It shares no code with
`torchft_tpu/`; the two have in common the layout of the weight tree
(`make_weights`) and the router's bias (`router_bias`), which the benchmark
makes and hands to both.

Per block, x of [S, hidden]; H heads; `q_lora_rank` null, so the query has no
low-rank path:

    h = RMSNorm(x);  q = h Wq, per head [q_nope (128) | q_rope (64)]
    c = h Wkva = [c_kv (512) | k_rope (64)]: ONE rotary key for all heads
    per head [k_nope (128) | v (128)] = RMSNorm(c_kv) Wkvb
    RoPE (half-split convention: the pair (i, i + 32)) on q_rope and k_rope
    k = [k_nope | k_rope];  causal softmax(q k^T / sqrt(192)) v;  x = x + (heads joined) Wo
    h = RMSNorm(x)
    the first `first_k_dense_replace` layers:  x = x + Wdown(silu(Wgate h) * Wup h), width 11,264
    the others:  s = sigmoid(h Wr) in float32 over ALL the router's outputs (64)
        the k = 6 largest of s + b chosen (b: the constant bias, never in a gate)
        g_i = s_i / sum_chosen s * routed_scaling_factor
        y = sum_{chosen i HELD HERE} g_i E_i(h) + Shared(h);  x = x + y

then the final RMSNorm, the untied head and the mean next-token cross-entropy
over the vocabulary slice.  Training adds, per sparse layer and per sequence,
DeepSeek-V3's sequence-wise balance term times `aux_loss_alpha`:
`sum_i f_i P_i` with `f_i = experts / (k S) * #{positions that chose i}` and
`P_i` the mean over the sequence of `s_i / sum_j s_j`.

**One chip's share.**  The configuration's `n_routed_experts` counts the
experts held here (its `expert_parallel` group says which of the router's
outputs they are); the router keeps its published width, and what the experts
held elsewhere would add is left out — here as in the program.  With every
expert held the same code is the uncut layer, which is how the test that the
shares add up reads it.

Departures from the published description, each without effect on the
arithmetic or noted where it has one:

- `jax.checkpoint` around each block, each attention head, each block of
  queries and each expert of the loop: recomputed in the backward pass, not
  computed differently.
- The RoPE pairing is the half-split one (the published code's interleaved
  pairs are these after a fixed permutation of the 64 columns of Wq's and
  Wkva's rotary parts, which seeded random weights cannot tell apart).
- The bias b is a buffer the published training updates from the experts'
  load, outside the gradient; here it is constant, made from the
  configuration's `router_bias` seed.
- A near-tie between the k-th and (k+1)-th expert can fall the other way in a
  lower precision: a property of top-k routing, not of this file.

`precision` selects what the matmul operands are rounded to before each matrix
product: "float32" is the reference; "bfloat16" imitates what the
configuration states for the program; "float8" (e4m3, per-tensor scale) is the
control.  The router's product stays in float32 in every precision.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, Any]

_SIZE_KEYS = ("vocab", "hidden", "layers", "dense", "heads", "nope", "rope", "v_dim", "rank", "dense_ffn", "ffn",
              "held", "experts", "shared", "init_depth")
QUERY_BLOCK = 2048


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by short names.  `held` experts
    `first ... first + held - 1` of the router's `experts` outputs live here."""
    if config.get("q_lora_rank") is not None or config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("written for q_lora_rank null and one routing group")
    if config["scoring_func"] != "sigmoid" or config["topk_method"] != "noaux_tc" or not config["norm_topk_prob"]:
        raise ValueError("written for the sigmoid, bias-corrected, renormalised router")
    share = config.get("expert_parallel") or {}
    bias = config.get("router_bias") or {"seed": 0, "scale": 0.0}
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "dense": config["first_k_dense_replace"],
        "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "rank": config["kv_lora_rank"],
        "dense_ffn": config["intermediate_size"],
        "ffn": config["moe_intermediate_size"],
        "held": config["n_routed_experts"],
        "experts": share.get("router_outputs", config["n_routed_experts"]),
        "first": share.get("first_expert_held", 0),
        "shared": config["n_shared_experts"],
        "init_depth": (config.get("published") or {}).get("num_hidden_layers", config["num_hidden_layers"]),
        "top_k": config["num_experts_per_tok"],
        "route_scale": float(config["routed_scaling_factor"]),
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "aux_coef": float(config["aux_loss_alpha"]),
        "bias_seed": int(bias["seed"]),
        "bias_scale": float(bias["scale"]),
    }


def router_bias(config: Dict[str, Any]) -> np.ndarray:
    """The router's choice bias b, [sparse layers, router outputs] float32:
    normal at the configuration's `router_bias.scale` from its `seed` (not from
    the run's: a buffer of the deployment, the same in every run)."""
    s = sizes_of(config)
    return _bias(s["bias_seed"], s["bias_scale"], s["layers"] - s["dense"], s["experts"])


def _bias(seed: int, scale: float, layers: int, experts: int) -> np.ndarray:
    return (np.random.default_rng([seed, 0xB1A5]).standard_normal((layers, experts)) * scale).astype(np.float32)


@functools.partial(jax.jit, static_argnames=_SIZE_KEYS)
def _weights(key, *, vocab, hidden, layers, dense, heads, nope, rope, v_dim, rank, dense_ffn, ffn, held, experts,
             shared, init_depth) -> Weights:
    k_embed, k_head, k_dense, k_sparse = jax.random.split(key, 4)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)

    def into_the_stream(k, shape, fan_in):
        """A projection that writes into the residual stream (Wo, Wdown)."""
        return normal(k, shape, fan_in) * (2 * init_depth) ** -0.5

    def attention(k, n):
        ks = jax.random.split(k, 4)
        return {
            "attn_norm": jnp.ones((n, hidden), jnp.float32),
            "wq": normal(ks[0], (n, hidden, heads * (nope + rope)), hidden),
            "wkv_a": normal(ks[1], (n, hidden, rank + rope), hidden),
            "kv_norm": jnp.ones((n, rank), jnp.float32),
            "wkv_b": normal(ks[2], (n, rank, heads * (nope + v_dim)), rank),
            "wo": into_the_stream(ks[3], (n, heads * v_dim, hidden), heads * v_dim),
            "mlp_norm": jnp.ones((n, hidden), jnp.float32),
        }

    ka, kg, ku, kd = jax.random.split(k_dense, 4)
    dense_layers = dict(
        attention(ka, dense),
        w_gate=normal(kg, (dense, hidden, dense_ffn), hidden),
        w_up=normal(ku, (dense, hidden, dense_ffn), hidden),
        w_down=into_the_stream(kd, (dense, dense_ffn, hidden), dense_ffn),
    )
    n = layers - dense
    ka, kr, kg, ku, kd, ksg, ksu, ksd = jax.random.split(k_sparse, 8)
    sparse_layers = dict(
        attention(ka, n),
        router=normal(kr, (n, hidden, experts), hidden),
        w_gate=normal(kg, (n, held, hidden, ffn), hidden),
        w_up=normal(ku, (n, held, hidden, ffn), hidden),
        w_down=into_the_stream(kd, (n, held, ffn, hidden), ffn),
        shared_gate=normal(ksg, (n, hidden, shared * ffn), hidden),
        shared_up=normal(ksu, (n, hidden, shared * ffn), hidden),
        shared_down=into_the_stream(ksd, (n, shared * ffn, hidden), shared * ffn),
    )
    return {
        "embed": jax.random.normal(k_embed, (vocab, hidden), jnp.float32),
        "dense_layers": dense_layers,
        "layers": sparse_layers,
        "final_norm": jnp.ones((hidden,), jnp.float32),
        "lm_head": normal(k_head, (hidden, vocab), hidden),
    }


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device: matrices normal with standard deviation fan_in**-0.5, norms at
    one; the leading dense layers stacked under "dense_layers", the sparse
    ones under "layers", a layer's held experts on the next axis.  Embedding
    rows are at unit scale, so the residual stream enters the first norm at a
    root mean square of one; the router's logits then have unit variance, so
    its sigmoid scores spread over (0.1, 0.9) and every expert is chosen.

    The projections that write into the residual stream (Wo and every Wdown)
    are smaller by sqrt(2 * layers of the PUBLISHED model), the usual scaled
    initialisation of output layers (GPT-2; Megatron-LM's
    `scaled_init_method`).  At fan_in**-0.5 the model measures something no
    deployment sees: at random weights causal attention over 8,192 positions
    is close to a running mean of the values, the same vector for every late
    position; layer by layer it piles up in the stream (15% of the router's
    input by the fifth sparse layer at 2,048 positions, more at 8,192), gives
    every expert's logit an offset that no token escapes, and one expert then
    takes most of a layer's tokens (busiest over mean 10 on the chip, the held
    share 0.03 or 0.3 by the luck of the seed).  A trained model's bias
    buffer exists to take exactly such offsets out; with the smaller output
    projections the stream stays the embedding's, the offsets stay at 2.5%
    and the load is even to the sampling noise and the bias's own lean."""
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


def _attention(x, w, s, precision: str):
    seq = x.shape[0]
    heads, nope, rope, v_dim, rank = s["heads"], s["nope"], s["rope"], s["v_dim"], s["rank"]
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    q = _mm(h, w["wq"], precision).reshape(seq, heads, nope + rope)
    latent = _mm(h, w["wkv_a"], precision)
    c_kv, k_rope = latent[:, :rank], latent[:, rank:]
    kv = _mm(_rms_norm(c_kv, w["kv_norm"], s["eps"]), w["wkv_b"], precision).reshape(seq, heads, nope + v_dim)
    q_rope = _rope(q[..., nope:], s["rope_theta"])
    k_rope = _rope(k_rope[:, None, :], s["rope_theta"])[:, 0]  # [S, rope], every head's
    attend = jax.checkpoint(functools.partial(_attend, precision=precision))
    out = [
        attend(jnp.concatenate([q[:, i, :nope], q_rope[:, i]], axis=-1),
               jnp.concatenate([kv[:, i, :nope], k_rope], axis=-1), kv[:, i, nope:])
        for i in range(heads)
    ]
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


def _dense_block(x, w, s, precision: str):
    x = _attention(x, w, s, precision)
    h = _rms_norm(x, w["mlp_norm"], s["eps"])
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], precision)


def _sparse_block(x, w, bias, s, precision: str):
    x = _attention(x, w, s, precision)
    y, aux = _experts(_rms_norm(x, w["mlp_norm"], s["eps"]), w, bias, s, precision)
    return x + y, aux


def _layer_weights(stacked: Weights, i: int) -> Weights:
    return {name: leaf[i] for name, leaf in stacked.items()}


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32"):
    """Mean next-token cross-entropy of one sequence plus its sparse layers'
    balance losses; tokens, targets: [S]."""
    bias = _bias(s["bias_seed"], s["bias_scale"], s["layers"] - s["dense"], s["experts"])
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        aux = 0.0
        for i in range(s["dense"]):
            x = jax.checkpoint(functools.partial(_dense_block, s=s, precision=precision))(
                x, _layer_weights(weights["dense_layers"], i))
        for i in range(s["layers"] - s["dense"]):
            x, layer_aux = jax.checkpoint(functools.partial(_sparse_block, s=s, precision=precision))(
                x, _layer_weights(weights["layers"], i), bias[i])
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
    sparse layer: [sparse layers, S, k], each position's k sorted by expert
    id.  What a program's choices are set against, to count the near-ties
    between the k-th and the next expert that fell the other way."""
    s = sizes_of(config)
    bias = router_bias(config)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        for i in range(s["dense"]):
            x = _dense_block(x, _layer_weights(weights["dense_layers"], i), s, precision)
        for i in range(s["layers"] - s["dense"]):
            w = _layer_weights(weights["layers"], i)
            x = _attention(x, w, s, precision)
            h = _rms_norm(x, w["mlp_norm"], s["eps"])
            chosen.append(jnp.sort(_route(h, w, bias[i], s)[2], axis=-1))
            x = x + _experts(h, w, bias[i], s, precision)[0]
    return jnp.stack(chosen)
