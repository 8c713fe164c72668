"""Plain reference of a decoder-only language model whose layers mix window and
full attention at different head counts, with a per-head output gate, a leading
dense layer and sigmoid-routed sparse experts beside a shared one
(Laguna-XS.2, `model_type: laguna`), and its weights.

Written from the published `config.json` in straightforward `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`.  No kernels, no sort, no
grouped matmul, no row buffer, no batching: one sequence at a time, attention
one head and one block of queries at a time (loops over the KV heads and the
query heads of each) against a dense mask built from the positions (64 heads x 16,384 x 16,384 float32 scores would be 69 GB whole), and
the experts as a masked loop — every expert HELD HERE is computed for every
position and weighted by that position's gate for it, which is zero where the
router did not choose it.  It shares no code with `torchft_tpu/`; the two have
in common the layout of the weight tree (`make_weights`).

Layer l has the kind kappa(l) of `layer_types[l]`: `full_attention` with
H = 48 query heads, or `sliding_attention` with H = 64 and a window of 512;
8 KV heads of 128 either way.  Per block, x of [S, hidden]:

    h = RMSNorm(x);  q = h Wq [S, H, 128];  k = h Wk, v = h Wv [S, 8, 128]
    RoPE_kappa on q and k (half-split pairs):
        full:    the first 64 of a head's 128 columns turn (partial_rotary_factor
                 0.5), pair i of 32 by position * f_i with YaRN's frequencies
                 f_i = (1 - r_i) * theta**(-2i/64) + r_i * theta**(-2i/64) / 64,
                 theta 5e5, r the linear ramp clip((i - low) / (high - low), 0, 1)
                 between the correction dimensions low = floor(c(beta_fast 64)),
                 high = ceil(c(beta_slow 1)), c(t) = 64 ln(4096 / (2 pi t)) / (2 ln theta);
                 cos and sin times attention_factor 1.4158883...; the other 64 pass
        window:  all 128 columns, pair i of 64 by position * 1e4**(-2i/128)
    scores q k^T / sqrt(128) over the pairs 0 <= t - s (full) or 0 <= t - s < 512
        (window); query head j reads KV head floor(j * 8 / H);  softmax;  o = P v
    o_head = o_head * sigmoid(h Wg)_head        (`gating: true`; Wg: hidden -> H)
    x = x + (heads joined) Wo
    h = RMSNorm(x)
    layer 0 (`mlp_layer_types` "dense"):  x = x + Wdown(silu(Wgate h) * Wup h), width 8,192
    the others:  s = sigmoid(h Wr) in float32 over ALL the router's outputs (256)
        the k = 8 largest of s chosen (no choice bias: the config names no buffer)
        g_i = s_i / sum_chosen s * moe_routed_scaling_factor (2.5)
        y = sum_{chosen i HELD HERE} g_i E_i(h) + Shared(h);  x = x + y
        (experts and the shared expert SwiGLU at 512; gates weight the experts'
        OUTPUTS: moe_apply_router_weight_on_input false)

then the final RMSNorm, the untied head and the mean next-token cross-entropy
over the vocabulary slice.  Training adds, per sparse layer and per sequence,
the sequence-wise balance term times `aux_loss_alpha`: `sum_i f_i P_i` with
`f_i = experts / (k S) * #{positions that chose i}` and `P_i` the mean over the
sequence of `s_i / sum_j s_j` (DeepSeek-V3's, arXiv:2412.19437 eq. 17-19).

**One chip's share.**  The configuration's `num_experts` counts the experts
held here (its `expert_parallel` group says which of the router's outputs they
are); the router keeps its published width, and what the experts held
elsewhere would add is left out — here as in the program.  With every expert
held the same code is the uncut layer, which is how the test that the shares
add up reads it.

What the published config does not say, and what was assumed (each in the
configuration file's `assumed` with its reason): the router's score function
(sigmoid, renormalised: 256 / 8 / 2.5 / one shared expert are DeepSeek-V3's
numbers), what `gating` gates (attention's output, a head at a time), no
QK-norm, the balance term, the RoPE pairing (half-split; the interleaved one is
a fixed permutation of columns that seeded random weights cannot tell apart).

Departures without effect on the arithmetic: `jax.checkpoint` around each block,
each KV head, each attention head, each block of queries, each expert of the
loop, each eighth of the dense feed-forward and each block of the head's rows
(recomputed in the backward pass, not computed differently), and a run of
layers of one kind as a `jax.lax.scan` over its stack.  A near-tie between
the k-th and (k+1)-th expert can fall the other way in a lower precision: a
property of top-k routing, not of this file.

`precision` selects what the matmul operands are rounded to before each matrix
product: "float32" is the reference; "bfloat16" imitates what the configuration
states for the program; "float8" (e4m3, per-tensor scale) is the control.  The
router's product stays in float32 in every precision.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, Any]

PERIOD = ("full_attention", "sliding_attention", "sliding_attention", "sliding_attention")
# A stack a kind of layer, as the program's tree has them.
STACK_OF = {("full_attention", "dense"): "dense_layers", ("sliding_attention", "sparse"): "window_layers",
            ("full_attention", "sparse"): "layers"}
QUERY_BLOCK = 1024


def layer_kinds(config: Dict[str, Any]) -> Tuple[Tuple[str, str, int], ...]:
    """Per layer (attention kind, feed-forward kind, query heads), the first
    `num_hidden_layers` entries of the published lists."""
    n = config["num_hidden_layers"]
    kinds = tuple(zip(config["layer_types"][:n], config["mlp_layer_types"][:n],
                      config["num_attention_heads_per_layer"][:n]))
    if tuple(k[0] for k in kinds) != tuple(PERIOD[i % 4] for i in range(n)):
        raise ValueError("written for the period full, window, window, window")
    if any(kind[:2] not in STACK_OF for kind in kinds):
        raise ValueError(f"no stack for a layer of {sorted(set(k[:2] for k in kinds) - set(STACK_OF))}")
    return kinds


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by short names.  `held` experts
    `first ... first + held - 1` of the router's `experts` outputs live here."""
    if config.get("moe_apply_router_weight_on_input"):
        raise ValueError("written for gates on the experts' outputs")
    rope = config["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or window["rope_type"] != "default":
        raise ValueError("written for YaRN on full layers and plain RoPE on window layers")
    share = config.get("expert_parallel") or {}
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "kinds": layer_kinds(config),
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window"],
        "dense_ffn": config["intermediate_size"],
        "ffn": config["moe_intermediate_size"],
        "shared": config["shared_expert_intermediate_size"],
        "held": config["num_experts"],
        "experts": share.get("router_outputs", config["num_experts"]),
        "first": share.get("first_expert_held", 0),
        "init_depth": (config.get("published") or {}).get("num_hidden_layers", config["num_hidden_layers"]),
        "top_k": config["num_experts_per_tok"],
        "route_scale": float(config["moe_routed_scaling_factor"]),
        "gating": bool(config["gating"]),
        "eps": float(config["rms_norm_eps"]),
        "aux_coef": float(config["aux_loss_alpha"]),
        "full_rope": (float(full["rope_theta"]), float(full["partial_rotary_factor"]), float(full["factor"]),
                      int(full["original_max_position_embeddings"]), float(full["beta_fast"]),
                      float(full["beta_slow"]), float(full["attention_factor"])),
        "window_rope": (float(window["rope_theta"]), float(window["partial_rotary_factor"])),
    }


def _stack_counts(kinds) -> Dict[str, Tuple[int, int]]:
    """stack -> (layers in it, its query heads)."""
    out: Dict[str, Tuple[int, int]] = {}
    for attention, ffn, heads in kinds:
        stack = STACK_OF[(attention, ffn)]
        out[stack] = (out.get(stack, (0, heads))[0] + 1, heads)
    return out


@functools.partial(jax.jit, static_argnames=("s",))
def _weights(key, *, s) -> Weights:
    s = dict(s)
    hidden, kv, dim = s["hidden"], s["kv_heads"] * s["head_dim"], s["head_dim"]

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)

    def into_the_stream(k, shape, fan_in):
        """A projection that writes into the residual stream (Wo, Wdown)."""
        return normal(k, shape, fan_in) * (2 * s["init_depth"]) ** -0.5

    def stack(k, n, heads, sparse):
        ks = jax.random.split(k, 13)
        layers = {
            "attn_norm": jnp.ones((n, hidden), jnp.float32),
            "wq": normal(ks[0], (n, hidden, heads * dim), hidden),
            "wk": normal(ks[1], (n, hidden, kv), hidden),
            "wv": normal(ks[2], (n, hidden, kv), hidden),
            "wo": into_the_stream(ks[3], (n, heads * dim, hidden), heads * dim),
            "mlp_norm": jnp.ones((n, hidden), jnp.float32),
        }
        if s["gating"]:
            layers["attn_gate"] = normal(ks[4], (n, hidden, heads), hidden)
        if not sparse:
            return dict(layers, w_gate=normal(ks[5], (n, hidden, s["dense_ffn"]), hidden),
                        w_up=normal(ks[6], (n, hidden, s["dense_ffn"]), hidden),
                        w_down=into_the_stream(ks[7], (n, s["dense_ffn"], hidden), s["dense_ffn"]))
        return dict(
            layers,
            router=normal(ks[5], (n, hidden, s["experts"]), hidden),
            w_gate=normal(ks[6], (n, s["held"], hidden, s["ffn"]), hidden),
            w_up=normal(ks[7], (n, s["held"], hidden, s["ffn"]), hidden),
            w_down=into_the_stream(ks[8], (n, s["held"], s["ffn"], hidden), s["ffn"]),
            shared_gate=normal(ks[9], (n, hidden, s["shared"]), hidden),
            shared_up=normal(ks[10], (n, hidden, s["shared"]), hidden),
            shared_down=into_the_stream(ks[11], (n, s["shared"], hidden), s["shared"]),
        )

    k_embed, k_head, k_stacks = jax.random.split(key, 3)
    tree = {
        "embed": jax.random.normal(k_embed, (s["vocab"], hidden), jnp.float32),
        "final_norm": jnp.ones((hidden,), jnp.float32),
        "lm_head": normal(k_head, (hidden, s["vocab"]), hidden),
    }
    for i, (name, (n, heads)) in enumerate(sorted(_stack_counts(s["kinds"]).items())):
        tree[name] = stack(jax.random.fold_in(k_stacks, i), n, heads, sparse=name != "dense_layers")
    return tree


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device, in the program's tree: the full-attention dense layer under
    "dense_layers", the window sparse layers under "window_layers", the
    full-attention sparse layers under "layers", each stacked in the model's
    order, a layer's held experts on the next axis.  Matrices are normal with
    standard deviation fan_in**-0.5 (router and head gate too, so their logits
    have unit variance), norms at one, embedding rows at unit scale, and the
    projections that write into the residual stream (Wo and every Wdown)
    smaller by sqrt(2 * layers of the PUBLISHED model), the scaled
    initialisation of output layers: what `reference/mla_moe_lm.py`'s
    `make_weights` says of causal attention over thousands of random positions
    and the router's load holds here too."""
    s = sizes_of(config)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _weights(key, s=tuple(sorted(s.items())))


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


DENSE_PARTS = 8


def _swiglu_in_parts(h, w_gate, w_up, w_down, precision: str):
    """`_swiglu` an eighth of the feed-forward's width at a time, the parts'
    results summed: the same products, and no [S, 8,192] float32 array whole."""
    hidden, width = w_gate.shape
    parts = DENSE_PARTS if width % DENSE_PARTS == 0 else 1
    one = jax.checkpoint(functools.partial(_swiglu, precision=precision))
    columns = lambda m: m.reshape(hidden, parts, width // parts).transpose(1, 0, 2)  # noqa: E731
    y, _ = jax.lax.scan(lambda y, part: (y + one(h, *part), None), jnp.zeros_like(h),
                        (columns(w_gate), columns(w_up), w_down.reshape(parts, width // parts, hidden)))
    return y


def yarn_inv_freq(theta: float, rot: int, factor: float, original: int, beta_fast: float, beta_slow: float):
    """YaRN's frequency of each of the rot / 2 rotary pairs (float64)."""

    def correction_dim(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(correction_dim(beta_fast)), 0), min(math.ceil(correction_dim(beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(rot // 2):
        plain = theta ** (-2.0 * i / rot)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append((1.0 - ramp) * plain + ramp * plain / factor)
    return np.asarray(out, np.float64)


def _rope(x, inv_freq, factor: float):
    """x: [S, H, D]; the first 2 * len(inv_freq) columns turn — the pair
    (x[..., i], x[..., i + half]) of position p by the angle p * inv_freq[i],
    cos and sin times `factor` — and the others pass."""
    seq, half = x.shape[0], len(inv_freq)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = (jnp.cos(angle) * factor)[:, None, :], (jnp.sin(angle) * factor)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest], axis=-1)


def _rope_of(attention: str, s):
    """(frequencies, factor on cos and sin) of a kind of layer."""
    if attention == "full_attention":
        theta, share, factor, original, beta_fast, beta_slow, attention_factor = s["full_rope"]
        return yarn_inv_freq(theta, int(s["head_dim"] * share), factor, original, beta_fast, beta_slow), attention_factor
    theta, share = s["window_rope"]
    rot = int(s["head_dim"] * share)
    return theta ** (-2.0 * np.arange(rot // 2, dtype=np.float64) / rot), 1.0


def _attend(q, k, v, window, precision: str):
    """One head: q, k, v [S, D].  Softmax attention over the pairs the dense
    mask `0 <= t - s < window` keeps (window None: every earlier position), a
    block of queries at a time."""
    seq, dim = q.shape
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    k, v = _round(k, precision), _round(v, precision)

    def queries(args):
        q_block, first = args
        scores = jnp.matmul(_round(q_block, precision), k.T) * dim ** -0.5
        distance = (first + jnp.arange(block))[:, None] - jnp.arange(seq)[None, :]
        visible = distance >= 0 if window is None else (distance >= 0) & (distance < window)
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return jnp.matmul(_round(probs, precision), v)

    out = jax.lax.map(jax.checkpoint(queries), (q.reshape(seq // block, block, dim), jnp.arange(0, seq, block)))
    return out.reshape(seq, dim)


def _attention(x, w, attention: str, heads: int, s, precision: str):
    """One KV head at a time — its `heads / kv_heads` query heads projected,
    turned, attended, gated and sent through their rows of Wo, the KV heads'
    contributions summed — so that no [S, heads, head_dim] float32 array is
    ever whole (0.5 GB each at the cell's size, a dozen of them in a block's
    backward pass): the same products, partitioned by head."""
    seq, hidden = x.shape
    kv_heads, dim = s["kv_heads"], s["head_dim"]
    group = heads // kv_heads  # query head j reads KV head floor(j * kv_heads / heads) = j // group
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    inv_freq, factor = _rope_of(attention, s)
    window = s["window"] if attention == "sliding_attention" else None
    attend = jax.checkpoint(functools.partial(_attend, window=window, precision=precision))

    def columns(m, width):
        """[hidden, kv_heads * width] -> [kv_heads, hidden, width]: each KV head's columns."""
        return m.reshape(hidden, kv_heads, width).transpose(1, 0, 2)

    @jax.checkpoint
    def of_kv_head(h, wq, wk, wv, wg, wo):
        q = _rope(_mm(h, wq, precision).reshape(seq, group, dim), inv_freq, factor)
        k = _rope(_mm(h, wk, precision)[:, None, :], inv_freq, factor)[:, 0]
        v = _mm(h, wv, precision)
        out = jax.lax.map(lambda q_head: attend(q_head, k, v), q.transpose(1, 0, 2)).transpose(1, 0, 2)  # [S, group, D]
        if s["gating"]:
            out = out * jax.nn.sigmoid(_mm(h, wg, precision))[:, :, None]
        return _mm(out.reshape(seq, group * dim), wo, precision)

    gate = columns(w["attn_gate"], group) if s["gating"] else jnp.zeros((kv_heads, hidden, group), jnp.float32)
    y, _ = jax.lax.scan(
        lambda y, of_head: (y + of_kv_head(h, *of_head), None), jnp.zeros_like(x),
        (columns(w["wq"], group * dim), columns(w["wk"], dim), columns(w["wv"], dim), gate,
         w["wo"].reshape(kv_heads, group * dim, hidden)))
    return x + y


def _route(h, w, s):
    """The router: float32 in every precision.  Returns (scores [S, experts],
    gates [S, k], chosen [S, k]).  `w["chosen"]` [S, k], where a caller put
    one, takes the place of the k largest (`one_sequence_fn`'s `forced`)."""
    scores = jax.nn.sigmoid(jnp.matmul(h, w["router"]))
    if "chosen" in w:
        gates, chosen = jnp.take_along_axis(scores, w["chosen"], axis=-1), w["chosen"]
    else:
        gates, chosen = jax.lax.top_k(scores, s["top_k"])
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * s["route_scale"]
    return scores, gates, chosen


def _experts(h, w, s, precision: str):
    """The held experts' part of the mixture plus the shared expert, and the
    balance loss of this layer."""
    scores, gates, chosen = _route(h, w, s)
    one_hot = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32)  # [S, k, experts]
    gate_of = jnp.einsum("sk,ske->es", gates, one_hot)  # [experts, S]: 0 where not chosen
    gate_of = gate_of[s["first"]: s["first"] + s["held"]]

    @jax.checkpoint  # the gate inside: the loop keeps no [S, hidden] array an expert for the backward pass
    def weighted(h, gate_for_it, w_gate, w_up, w_down):
        return gate_for_it[:, None] * _swiglu(h, w_gate, w_up, w_down, precision)

    def one(y, expert):
        return y + weighted(h, *expert), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate_of, w["w_gate"], w["w_up"], w["w_down"]))
    y = y + _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], precision)
    share = jnp.mean(jnp.sum(one_hot, axis=1), axis=0) * s["experts"] / s["top_k"]  # f_i
    mean_score = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)  # P_i
    return y, s["aux_coef"] * jnp.sum(share * mean_score)


def _block(x, w, kind, s, precision: str):
    """One layer of `kind` = (attention, feed-forward, heads): (x, balance loss)."""
    attention, ffn, heads = kind
    x = _attention(x, w, attention, heads, s, precision)
    h = _rms_norm(x, w["mlp_norm"], s["eps"])
    if ffn == "dense":
        return x + _swiglu_in_parts(h, w["w_gate"], w["w_up"], w["w_down"], precision), jnp.zeros((), jnp.float32)
    y, aux = _experts(h, w, s, precision)
    return x + y, aux


def _runs(s):
    """The layers as runs of one kind: (kind, its stack, the run's first layer in the stack, its length)."""
    at: Dict[str, int] = {}
    runs = []
    for kind in s["kinds"]:
        stack = STACK_OF[kind[:2]]
        i = at.get(stack, 0)
        at[stack] = i + 1
        if runs and runs[-1][0] == kind:
            runs[-1][3] += 1
        else:
            runs.append([kind, stack, i, 1])
    return [tuple(run) for run in runs]


def _layers(weights: Weights, s):
    """(kind, the layer's weights) first to last, each out of its kind's stack."""
    for kind, stack, first, count in _runs(s):
        for i in range(first, first + count):
            yield kind, {name: leaf[i] for name, leaf in weights[stack].items()}


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32", forced=None):
    """Mean next-token cross-entropy of one sequence plus its sparse layers'
    balance losses; tokens, targets: [S].  `forced` [sparse layers, S, k]:
    the experts each position is given in place of its router's k largest."""
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        aux, sparse_seen = 0.0, 0
        for kind, stack, first, count in _runs(s):
            # a run of one kind is a loop over its slice of the stack (the whole stack, in the cut this was
            # written for), so that the layers' gradients are written into the stacked gradient and never
            # held beside it
            stacked = weights[stack]
            if (first, count) != (0, stacked["attn_norm"].shape[0]):
                stacked = {name: leaf[first:first + count] for name, leaf in stacked.items()}
            if kind[1] == "sparse":
                if forced is not None:
                    stacked = dict(stacked, chosen=forced[sparse_seen:sparse_seen + count])
                sparse_seen += count
            block = jax.checkpoint(functools.partial(_block, kind=kind, s=s, precision=precision))
            x, layer_aux = jax.lax.scan(block, x, stacked)
            aux = aux + jnp.sum(layer_aux)
        h = _rms_norm(x, weights["final_norm"], s["eps"])
        head = _round(weights["lm_head"], precision)

        @jax.checkpoint
        def rows(args):  # a block of positions at a time: no [S, vocabulary] float32 logits whole
            h_block, targets_block = args
            logits = jnp.matmul(_round(h_block, precision), head)
            picked = jnp.take_along_axis(logits, targets_block[:, None], axis=-1)[:, 0]
            return jax.nn.logsumexp(logits, axis=-1) - picked

        seq = h.shape[0]
        block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
        losses = jax.lax.map(rows, (h.reshape(seq // block, block, -1), targets.reshape(seq // block, block)))
        return jnp.mean(losses) + aux


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
    """The jitted (weights, tokens[S], targets[S]) -> (loss, gradient tree).
    A fourth argument, `forced` [sparse layers, S, k] int32, gives every
    position its experts (`routing`'s result, or a program's choices): two
    computations under the same `forced` differ by their arithmetic alone and
    by no near-tie (`tools/tie_free_swa.py`)."""
    return _one_sequence(tuple(sorted(sizes_of(config).items())), precision)


@functools.lru_cache(maxsize=None)
def _one_sequence(frozen_sizes, precision: str):
    s = dict(frozen_sizes)
    return jax.jit(jax.value_and_grad(
        lambda weights, tokens, targets, forced=None: loss(weights, tokens, targets, s, precision, forced)))


def routing(weights: Weights, tokens, config: Dict[str, Any], precision: str = "float32"):
    """The experts this reference's router chooses for one sequence, per
    sparse layer: [sparse layers, S, k], each position's k sorted by expert
    id.  What a program's choices are set against, to count the near-ties
    between the k-th and the next expert that fell the other way."""
    s = sizes_of(config)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        for kind, w in _layers(weights, s):
            if kind[1] == "sparse":
                after = _attention(x, w, kind[0], kind[2], s, precision)
                chosen.append(jnp.sort(_route(_rms_norm(after, w["mlp_norm"], s["eps"]), w, s)[2], axis=-1))
            x = _block(x, w, kind, s, precision)[0]
    return jnp.stack(chosen)
