"""Plain reference of a decoder-only language model whose router reads the layer's
input before attention, whose experts are ReGLU, and whose layers mix window
attention under RoPE with full attention that has no position term at all
(SmallThinker-21BA3B-Instruct, `model_name: smallthinker_21b_instruct`), and
its weights.

Written from the published `config.json` in straightforward `jax.numpy`,
float32, `jax.default_matmul_precision("highest")`.  No kernels, no sort, no
grouped matmul, no row buffer, no batching: one sequence at a time, attention
one head and one block of queries at a time (loops over the KV heads and the
seven query heads of each) against a dense mask built from the positions
(28 heads x 16,384 x 16,384 float32 scores would be 30 GB whole), and the
experts as a masked loop — every expert HELD HERE is computed for every
position and weighted by that position's gate for it, which is zero where the
router did not choose it.  It shares no code with `torchft_tpu/`; the two have
in common the layout of the weight tree (`make_weights`).

Layer l, input x of [S, hidden], epsilon 1e-6, no bias anywhere, no QK-norm, no
shared expert, no dense layer:

    z    = x Wr                        the router reads the LAYER'S INPUT: the residual
                                       stream before `input_layernorm`, before attention
    idx  = the 6 largest of z;  p = softmax(z[idx]) over those 6, float32
           (`moe_primary_router_apply_softmax` true, `norm_topk_prob` true)
    h    = RMSNorm(x);  q = h Wq [S, 28, 128];  k = h Wk, v = h Wv [S, 4, 128]
    rope_layout[l] == 1:            q, k = RoPE(q), RoPE(k): all 128 columns, half-split
                                    pairs, pair i of 64 by position * 1.5e6**(-2i/128)
    sliding_window_layout[l] == 1:  a query at t sees the keys s with 0 <= t - s < 4096,
                                    else every s <= t
    scores q k^T / sqrt(128), query head j reads KV head j // 7;  softmax;  o = P v
    x1   = x + (heads joined) Wo
    h2   = RMSNorm(x1)
    y    = sum_{j in idx, HELD HERE} p_j (relu(h2 Wg_j) * (h2 Wu_j)) Wd_j     experts of width 768
    x2   = x1 + y

then the final RMSNorm, the untied head and the mean next-token cross-entropy
over the vocabulary slice.  Both published layouts are `[0, 1, 1, 1] x 13`:
layer 0 of a period is full causal attention with no position term, layers 1-3
are window 4,096 under RoPE.

**One chip's share.**  The configuration's `moe_num_primary_experts` counts the
experts held here (its `expert_parallel` group says which of the router's
outputs they are); the router keeps its published width, and what the experts
held elsewhere would add is left out — here as in the program.  With every
expert held the same code is the uncut layer, which is how the test that the
shares add up reads it.

What the published config does not say, and what was assumed (each in the
configuration file's `assumed` with its reason): which tensor the early router
reads (the raw layer input x, not the normed h), no auxiliary balance loss, the
RoPE pairing (half-split; the interleaved one is a fixed permutation of columns
that seeded random weights cannot tell apart).  The catalog's `described_as`
names "secondary experts"; the config has no key for them and the published
parameter count leaves them no room, so there are none here.

Departures without effect on the arithmetic: `jax.checkpoint` around each block,
each KV head, each attention head, each block of queries, each expert of the
loop and each block of the head's rows (recomputed in the backward pass, not
computed differently), and a run of layers of one kind as a `jax.lax.scan` over
its stack.  A near-tie between the 6th and 7th expert can fall the other way in
a lower precision, and a gate pre-activation within rounding of zero can land on
the other side of ReLU's mask: properties of top-k routing and of ReLU, not of
this file.

`precision` selects what the matmul operands are rounded to before each matrix
product: "float32" is the reference; "bfloat16" imitates what the configuration
states for the program; "float8" (e4m3, per-tensor scale) is the control.  The
router's product stays in float32 in every precision.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, Any]

PERIOD = (0, 1, 1, 1)  # both layouts: 0 a full layer without RoPE, 1 a window layer under RoPE
# A stack a kind of layer, as the program's tree has them.
STACK_OF = {"full_attention": "layers", "sliding_attention": "window_layers"}
QUERY_BLOCK = 1024
# Pieces of the mathematics that `loss(..., left_out=...)` computes WRONGLY, for the readings that show the comparison
# catches each: the router fed the experts' input h2, SiLU where ReLU stands, RoPE on the full layers too, none on the
# window layers, the window layers over every earlier key, the gates a softmax over all the outputs left as it is.
LEFT_OUT = ("early_router", "relu", "nope_on_full", "rope_on_window", "window", "softmax_over_kept")


def layer_kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    """Per layer its kind of attention, from the first `num_hidden_layers`
    entries of the two published layouts."""
    n = config["num_hidden_layers"]
    rope, window = config["rope_layout"][:n], config["sliding_window_layout"][:n]
    if len(rope) != n or list(rope) != list(window) or tuple(rope) != tuple(PERIOD[i % 4] for i in range(n)):
        raise ValueError("written for rope_layout == sliding_window_layout == [0, 1, 1, 1] repeated")
    return tuple("sliding_attention" if flag else "full_attention" for flag in window)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by short names.  `held` experts
    `first ... first + held - 1` of the router's `experts` outputs live here."""
    if config.get("rope_scaling") is not None or config.get("tie_word_embeddings"):
        raise ValueError("written for plain RoPE and an untied head")
    if not (config["moe_primary_router_apply_softmax"] and config["norm_topk_prob"]):
        raise ValueError("written for a softmax over the kept experts")
    share = config.get("expert_parallel") or {}
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "kinds": layer_kinds(config),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window_size"],
        "theta": float(config["rope_theta"]),
        "ffn": config["moe_ffn_hidden_size"],
        "held": config["moe_num_primary_experts"],
        "experts": share.get("router_outputs", config["moe_num_primary_experts"]),
        "first": share.get("first_expert_held", 0),
        "init_depth": (config.get("published") or {}).get("num_hidden_layers", config["num_hidden_layers"]),
        "top_k": config["moe_num_active_primary_experts"],
        "eps": float(config["rms_norm_eps"]),
    }


def _stack_counts(kinds) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for kind in kinds:
        out[STACK_OF[kind]] = out.get(STACK_OF[kind], 0) + 1
    return out


@functools.partial(jax.jit, static_argnames=("s",))
def _weights(key, *, s) -> Weights:
    s = dict(s)
    hidden, q, kv = s["hidden"], s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)

    def into_the_stream(k, shape, fan_in):
        """A projection that writes into the residual stream (Wo, Wdown)."""
        return normal(k, shape, fan_in) * (2 * s["init_depth"]) ** -0.5

    def stack(k, n):
        ks = jax.random.split(k, 8)
        return {
            "attn_norm": jnp.ones((n, hidden), jnp.float32),
            "wq": normal(ks[0], (n, hidden, q), hidden),
            "wk": normal(ks[1], (n, hidden, kv), hidden),
            "wv": normal(ks[2], (n, hidden, kv), hidden),
            "wo": into_the_stream(ks[3], (n, q, hidden), q),
            "mlp_norm": jnp.ones((n, hidden), jnp.float32),
            "router": normal(ks[4], (n, hidden, s["experts"]), hidden),
            "w_gate": normal(ks[5], (n, s["held"], hidden, s["ffn"]), hidden),
            "w_up": normal(ks[6], (n, s["held"], hidden, s["ffn"]), hidden),
            "w_down": into_the_stream(ks[7], (n, s["held"], s["ffn"], hidden), s["ffn"]),
        }

    k_embed, k_head, k_stacks = jax.random.split(key, 3)
    tree = {
        "embed": jax.random.normal(k_embed, (s["vocab"], hidden), jnp.float32),
        "final_norm": jnp.ones((hidden,), jnp.float32),
        "lm_head": normal(k_head, (hidden, s["vocab"]), hidden),
    }
    for i, (name, n) in enumerate(sorted(_stack_counts(s["kinds"]).items())):
        tree[name] = stack(jax.random.fold_in(k_stacks, i), n)
    return tree


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device, in the program's tree: the full layers under "layers", the window
    layers under "window_layers", each stacked in the model's order, a layer's
    held experts on the next axis.  Matrices are normal with standard deviation
    fan_in**-0.5 (the router too, so its logits have the input's scale), norms
    at one, embedding rows at unit scale, and the projections that write into
    the residual stream (Wo and every Wdown) smaller by sqrt(2 * layers of the
    PUBLISHED model), the scaled initialisation of output layers: what
    `reference/mla_moe_lm.py`'s `make_weights` says of causal attention over
    thousands of random positions and the router's load holds here too."""
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


def _rope(x, theta: float):
    """x: [S, H, D]; every column turns — the pair (x[..., i], x[..., i + D/2])
    of position p by the angle p * theta**(-2i/D)."""
    seq, half = x.shape[0], x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


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


def _attention(x, w, kind: str, s, precision: str):
    """One KV head at a time — its seven query heads projected, turned where
    the kind turns them, attended and sent through their rows of Wo, the KV
    heads' contributions summed — so that no [S, heads, head_dim] float32 array
    is ever whole: the same products, partitioned by head."""
    seq, hidden = x.shape
    heads, kv_heads, dim, left_out = s["heads"], s["kv_heads"], s["head_dim"], s.get("left_out")
    group = heads // kv_heads  # query head j reads KV head j // group
    h = _rms_norm(x, w["attn_norm"], s["eps"])
    windowed = kind == "sliding_attention"
    turned = (windowed and left_out != "rope_on_window") or (not windowed and left_out == "nope_on_full")
    window = s["window"] if windowed and left_out != "window" else None
    attend = jax.checkpoint(functools.partial(_attend, window=window, precision=precision))
    turn = (lambda a: _rope(a, s["theta"])) if turned else (lambda a: a)

    def columns(m, width):
        """[hidden, kv_heads * width] -> [kv_heads, hidden, width]: each KV head's columns."""
        return m.reshape(hidden, kv_heads, width).transpose(1, 0, 2)

    @jax.checkpoint
    def of_kv_head(h, wq, wk, wv, wo):
        q = turn(_mm(h, wq, precision).reshape(seq, group, dim))
        k = turn(_mm(h, wk, precision)[:, None, :])[:, 0]
        v = _mm(h, wv, precision)
        out = jax.lax.map(lambda q_head: attend(q_head, k, v), q.transpose(1, 0, 2)).transpose(1, 0, 2)  # [S, group, D]
        return _mm(out.reshape(seq, group * dim), wo, precision)

    y, _ = jax.lax.scan(
        lambda y, of_head: (y + of_kv_head(h, *of_head), None), jnp.zeros_like(x),
        (columns(w["wq"], group * dim), columns(w["wk"], dim), columns(w["wv"], dim),
         w["wo"].reshape(kv_heads, group * dim, hidden)))
    return x + y


def _route(x, w, s):
    """The router: float32 in every precision, on WHAT IT IS GIVEN (the layer's
    input).  Returns (gates [S, k], chosen [S, k]): the k largest logits and
    the softmax over those k."""
    logits = jnp.matmul(x, w["router"])
    kept, chosen = jax.lax.top_k(logits, s["top_k"])
    if s.get("left_out") == "softmax_over_kept":
        return jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), chosen, axis=-1), chosen
    return jax.nn.softmax(kept, axis=-1), chosen


def _reglu(h, w_gate, w_up, w_down, s, precision: str):
    act = jax.nn.silu if s.get("left_out") == "relu" else jax.nn.relu
    return _mm(act(_mm(h, w_gate, precision)) * _mm(h, w_up, precision), w_down, precision)


def _experts(h, gates, chosen, w, s, precision: str):
    """The held experts' part of the mixture for the experts' input h under
    the router's gates and choice."""
    one_hot = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32)  # [S, k, experts]
    gate_of = jnp.einsum("sk,ske->es", gates, one_hot)  # [experts, S]: 0 where not chosen
    gate_of = gate_of[s["first"]: s["first"] + s["held"]]

    @jax.checkpoint  # the gate inside: the loop keeps no [S, hidden] array an expert for the backward pass
    def weighted(h, gate_for_it, w_gate, w_up, w_down):
        return gate_for_it[:, None] * _reglu(h, w_gate, w_up, w_down, s, precision)

    y, _ = jax.lax.scan(lambda y, expert: (y + weighted(h, *expert), None), jnp.zeros_like(h),
                        (gate_of, w["w_gate"], w["w_up"], w["w_down"]))
    return y


def _block(x, w, kind: str, s, precision: str):
    """One layer of `kind`: the stream after it."""
    after = _attention(x, w, kind, s, precision)
    h2 = _rms_norm(after, w["mlp_norm"], s["eps"])
    gates, chosen = _route(h2 if s.get("left_out") == "early_router" else x, w, s)
    return after + _experts(h2, gates, chosen, w, s, precision)


def _runs(s):
    """The layers as runs of one kind: (kind, its stack, the run's first layer in the stack, its length)."""
    at: Dict[str, int] = {}
    runs = []
    for kind in s["kinds"]:
        stack = STACK_OF[kind]
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


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32"):
    """Mean next-token cross-entropy of one sequence; tokens, targets: [S]."""
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        for kind, stack, first, count in _runs(s):
            # a run of one kind is a loop over its slice of the stack, so that the layers' gradients are written
            # into the stacked gradient and never held beside it
            stacked = {name: leaf[first:first + count] for name, leaf in weights[stack].items()}
            block = jax.checkpoint(functools.partial(_block, kind=kind, s=s, precision=precision))
            x, _ = jax.lax.scan(lambda x, w: (block(x, w), None), x, stacked)
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
        return jnp.mean(losses)


def loss_and_grads(weights: Weights, tokens, targets, config: Dict[str, Any],
                   precision: str = "float32", left_out: str = "") -> Tuple[jax.Array, Weights]:
    """Loss and its gradient for a batch [B, S], one sequence at a time,
    averaged over the sequences as the mean loss of the batch is."""
    one = one_sequence_fn(config, precision, left_out)
    total_loss, total_grads = None, None
    for i in range(tokens.shape[0]):
        l, g = one(weights, tokens[i], targets[i])
        total_loss = l if total_loss is None else total_loss + l
        total_grads = g if total_grads is None else jax.tree.map(jnp.add, total_grads, g)
    n = tokens.shape[0]
    return total_loss / n, jax.tree.map(lambda g: g / n, total_grads)


def one_sequence_fn(config: Dict[str, Any], precision: str = "float32", left_out: str = ""):
    """The jitted (weights, tokens[S], targets[S]) -> (loss, gradient tree).
    `left_out`: one of `LEFT_OUT`, for the readings that show the comparison
    catches a model without that piece."""
    assert not left_out or left_out in LEFT_OUT, left_out
    return _one_sequence(tuple(sorted(dict(sizes_of(config), left_out=left_out).items())), precision)


@functools.lru_cache(maxsize=None)
def _one_sequence(frozen_sizes, precision: str):
    s = dict(frozen_sizes)
    return jax.jit(jax.value_and_grad(lambda weights, tokens, targets: loss(weights, tokens, targets, s, precision)))


def routing(weights: Weights, tokens, config: Dict[str, Any], precision: str = "float32", units: bool = False):
    """The experts this reference's router chooses for one sequence, per
    layer: [layers, S, k], each position's k sorted by expert id.  What a
    program's choices are set against, to count the near-ties between the k-th
    and the next expert that fell the other way.  With `units` also ReLU's
    mask: (chosen, lit [layers, held, S, F] bool — whether the held expert's
    hidden unit is above zero at the position, whoever the position chose)."""
    s = sizes_of(config)
    chosen, lit = [], []
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        for kind, w in _layers(weights, s):
            chosen.append(jnp.sort(_route(x, w, s)[1], axis=-1))
            if units:
                h2 = _rms_norm(_attention(x, w, kind, s, precision), w["mlp_norm"], s["eps"])
                lit.append(jax.lax.map(lambda w_gate: _mm(h2, w_gate, precision) > 0, w["w_gate"]))
            x = _block(x, w, kind, s, precision)
    return (jnp.stack(chosen), jnp.stack(lit)) if units else jnp.stack(chosen)
