"""Plain reference of a decoder-only language model with learned sparse
attention (a lightning indexer that picks each query's keys, over grouped-query
attention with a per-head QK-norm) and softmax top-k routing over sparse
experts (Keye-VL-2.0-30B-A3B's language model), and its weights.

Written from the published description (the model's `config.json`,
`model_type: KeyeVL2`, whose decoder is Qwen3-MoE's, and DeepSeek-V3.2's
sparse attention, which the `sa_config` names: indexer heads, one index key
head, `topk`) in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`.  No kernels, no thresholds, no
bisection, no sorted rows, no grouped matmul: dense index scores, a
`jax.lax.top_k` per query, a masked softmax; one sequence at a time, attention
one block of queries and one head at a time (32 heads x 32,768 x 32,768 float32
scores would be 137 GB whole), and the experts as a masked loop over the
experts HELD HERE.  It shares no code with `torchft_tpu/`; the two have in
common the layout of the weight tree (`make_weights`).

Per block, x of [S, hidden]; 32 query heads h, KV head g(h) = h // 8:

    u = RMSNorm(x)
    q[t, h] = RoPE(RMSNorm_128(Wq u[t])_h);  k[t, g] = RoPE(RMSNorm_128(Wk u[t])_g);  v[t, g] = (Wv u[t])_g
    the indexer, on u~ = stop_gradient(u):
        a[t, j] = RoPE((Wiq u~[t])_j)  (16 heads of 64);  b[s] = RoPE(LayerNorm(Wik u~[s]))  (one head of 64)
        w[t] = Wiw u~[t]  (16)
        I[t, s] = 16**-0.5 * 64**-0.5 * sum_j w[t, j] * relu(a[t, j] . b[s])       for s <= t
    S_t = the positions of the min(t + 1, topk) largest I[t, s], s <= t, ties to the lower position
    o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, g(h)] / sqrt(128)) v[s, g(h)]
    x = x + Wo [o[t, 0..31]]
    u' = RMSNorm(x);  p = softmax(Wr u') over ALL the router's outputs (128), in float32
    the 8 largest chosen, their gates renormalised to sum 1
    x = x + sum_{chosen i HELD HERE} gate_i * Wdown_i(silu(Wgate_i u') * Wup_i u')

then the final RMSNorm, the untied head and the mean next-token cross-entropy
over the vocabulary slice.  Training adds

    0.001 * sum over layers of  experts * sum_i f_i P_i      (Switch's balance term as Qwen3-MoE applies
        it to top-k: f_i the share of the sequence's positions that chose i, P_i the mean probability of i)
    + sum over layers of  mean_t KL( pbar[t, :] || softmax_{s in S_t} I[t, s] )
        pbar[t, s] = stop_gradient( (1 / 32) sum_h head h's probability of s )

so the indexer's matrices and LayerNorm learn from the KL term alone and every
other weight from the first two terms alone.

**One chip's share.**  `num_experts` counts the experts held here (the
configuration's `expert_parallel` group says which of the router's outputs
they are); the router keeps its published width, and what the experts held
elsewhere would add is left out — here as in the program.  With every expert
held the same code is the uncut layer.

Departures from the published description, each without effect on the
arithmetic or noted where it has one:

- `jax.checkpoint` around each block, each block of queries, each head and each
  expert of the loop: recomputed in the backward pass, not computed differently.
- RoPE pairs column i of a head with column i + half (text-only position ids
  make `mrope_section` [16, 24, 24] plain RoPE over the 64 pairs).
- The vision tower is not here: token ids in, the language model alone.
- Near-ties at the topk-th index score, or between the 8th and 9th expert, can
  fall the other way in a lower precision: a property of top-k, not of this file.

`precision` selects what the matmul operands are rounded to before each matrix
product (the indexer's too): "float32" is the reference; "bfloat16" imitates
what the configuration states for the program; "float8" (e4m3, per-tensor
scale) is the control.  The router's product stays in float32 in every
precision.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Weights = Dict[str, Any]

_SIZE_KEYS = ("vocab", "hidden", "layers", "heads", "kv_heads", "head_dim", "index_heads", "index_dim", "ffn",
              "held", "experts", "init_depth")
QUERY_BLOCK = 1024


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by short names.  `held` experts
    `first ... first + held - 1` of the router's `experts` outputs live here."""
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or not config["norm_topk_prob"]:
        raise ValueError("written for one index key head and renormalised gates")
    if config.get("mlp_only_layers") or config.get("decoder_sparse_step", 1) != 1 or config.get("use_sliding_window"):
        raise ValueError("written for a sparse feed-forward in every layer and no sliding window")
    share = config.get("expert_parallel") or {}
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"],
        "topk": sa["topk"],
        "ffn": config["moe_intermediate_size"],
        "held": config["num_experts"],
        "experts": share.get("router_outputs", config["num_experts"]),
        "first": share.get("first_expert_held", 0),
        "init_depth": (config.get("published") or {}).get("num_hidden_layers", config["num_hidden_layers"]),
        "top_k": config["num_experts_per_tok"],
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "aux_coef": float(config["router_aux_loss_coef"]),
        "index_coef": float(config.get("indexer_loss_coef", 1.0)),
    }


@functools.partial(jax.jit, static_argnames=_SIZE_KEYS)
def _weights(key, *, vocab, hidden, layers, heads, kv_heads, head_dim, index_heads, index_dim, ffn, held, experts,
             init_depth) -> Weights:
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)

    def into_the_stream(k, shape, fan_in):
        """A projection that writes into the residual stream (Wo, Wdown)."""
        return normal(k, shape, fan_in) * (2 * init_depth) ** -0.5

    n = layers
    ks = jax.random.split(k_layers, 11)
    stacked = {
        "attn_norm": jnp.ones((n, hidden), jnp.float32),
        "wq": normal(ks[0], (n, hidden, heads * head_dim), hidden),
        "wk": normal(ks[1], (n, hidden, kv_heads * head_dim), hidden),
        "wv": normal(ks[2], (n, hidden, kv_heads * head_dim), hidden),
        "wo": into_the_stream(ks[3], (n, heads * head_dim, hidden), heads * head_dim),
        "q_norm": jnp.ones((n, head_dim), jnp.float32),
        "k_norm": jnp.ones((n, head_dim), jnp.float32),
        "wi_q": normal(ks[4], (n, hidden, index_heads * index_dim), hidden),
        "wi_k": normal(ks[5], (n, hidden, index_dim), hidden),
        "wi_k_norm": jnp.ones((n, index_dim), jnp.float32),
        "wi_k_bias": jnp.zeros((n, index_dim), jnp.float32),
        "wi_w": normal(ks[6], (n, hidden, index_heads), hidden),
        "mlp_norm": jnp.ones((n, hidden), jnp.float32),
        "router": normal(ks[7], (n, hidden, experts), hidden),
        "w_gate": normal(ks[8], (n, held, hidden, ffn), hidden),
        "w_up": normal(ks[9], (n, held, hidden, ffn), hidden),
        "w_down": into_the_stream(ks[10], (n, held, ffn, hidden), ffn),
    }
    return {
        "embed": jax.random.normal(k_embed, (vocab, hidden), jnp.float32),
        "layers": stacked,
        "final_norm": jnp.ones((hidden,), jnp.float32),
        "lm_head": normal(k_head, (hidden, vocab), hidden),
    }


def make_weights(seed: int, config: Dict[str, Any]) -> Weights:
    """Float32 weights from the seed, in one jitted call on the default
    device: matrices normal with standard deviation fan_in**-0.5 (the router
    and the indexer's three too, so the router's logits and the index scores
    have order-one spread), norm weights one, the LayerNorm's bias zero, the
    layers stacked under "layers" with a layer's held experts on the next axis.
    Embedding rows are at unit scale, so the residual stream enters the first
    norm at a root mean square of one.  The projections that write into the
    residual stream (Wo and every Wdown) are smaller by sqrt(2 * layers of the
    PUBLISHED model), the usual scaled initialisation of output layers (GPT-2;
    Megatron-LM's `scaled_init_method`): at fan_in**-0.5 attention over
    thousands of random positions is close to a running mean of the values,
    the same vector for every late position, which piles up in the stream
    layer by layer, gives every expert's logit an offset no token escapes and
    collapses the router's load onto a few experts (found on the chip for the
    latent-attention configuration, `reference/mla_moe_lm.py`)."""
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


def _layer_norm(x, w, b, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * w + b


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


def _indexer(u, w, s, precision: str):
    """Index queries a [S, J, Di], the one index key head b [S, Di] and the
    head weights [S, J] with both scale factors in them, from the DETACHED
    normed input."""
    u = jax.lax.stop_gradient(u)
    seq = u.shape[0]
    heads, dim = s["index_heads"], s["index_dim"]
    a = _rope(_mm(u, w["wi_q"], precision).reshape(seq, heads, dim), s["rope_theta"])
    b = _layer_norm(_mm(u, w["wi_k"], precision), w["wi_k_norm"], w["wi_k_bias"], s["eps"])
    b = _rope(b[:, None, :], s["rope_theta"])[:, 0]
    weights = _mm(u, w["wi_w"], precision) * (heads ** -0.5 * dim ** -0.5)
    return a, b, weights


def _index_scores(a_block, b, weights_block, precision: str):
    """I[t, s] of a block of queries against every position: [block, S]."""
    bt = _round(b, precision).T

    @jax.checkpoint
    def term(a_j, w_j):  # [block, Di], [block]: one index head's part of the score
        return w_j[:, None] * jax.nn.relu(jnp.matmul(_round(a_j, precision), bt))

    def head(total, args):
        return total + term(*args), None

    zero = jnp.zeros((a_block.shape[0], b.shape[0]), a_block.dtype)
    total, _ = jax.lax.scan(head, zero, (a_block.transpose(1, 0, 2), weights_block.T))
    return total


def _selected(scores, first, topk: int):
    """bool [block, S]: for the query at position first + r the
    min(position + 1, topk) largest scores among s <= position, ties to the
    lower position (`jax.lax.top_k`'s rule).  Constant under the gradient."""
    block, seq = scores.shape
    visible = (first + jnp.arange(block))[:, None] >= jnp.arange(seq)[None, :]
    _, idx = jax.lax.top_k(jnp.where(visible, jax.lax.stop_gradient(scores), -jnp.inf), min(topk, seq))
    hit = jnp.zeros((block, seq), bool).at[jnp.arange(block)[:, None], idx].set(True)
    return hit & visible


def _attend_block(args, k, v, b, s, precision: str):
    """One block of queries: q [block, H, D], a [block, J, Di], weights
    [block, J], first position.  Returns (o [block, H * D], the block's sum of
    KL terms)."""
    q, a, weights, first = args
    heads, group = s["heads"], s["heads"] // s["kv_heads"]
    scores = _index_scores(a, b, weights, precision)
    keep = _selected(scores, first, s["topk"])
    k_r, v_r = _round(k, precision), _round(v, precision)

    def probs(h):
        logits = jnp.matmul(_round(q[:, h], precision), k_r[:, h // group].T) * s["head_dim"] ** -0.5
        return jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)

    def out_of(h):
        return jnp.matmul(_round(probs(h), precision), v_r[:, h // group])

    o = jax.lax.map(jax.checkpoint(out_of), jnp.arange(heads))                 # [H, block, D]
    pbar = jax.lax.stop_gradient(
        jax.lax.fori_loop(0, heads, lambda h, total: total + jax.lax.stop_gradient(probs(h)),
                          jnp.zeros_like(scores))) / heads
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    positive = pbar > 0.0
    kl = jnp.where(positive, pbar * (jnp.log(jnp.where(positive, pbar, 1.0)) - jnp.where(keep, log_q, 0.0)), 0.0)
    return o.transpose(1, 0, 2).reshape(q.shape[0], -1), jnp.sum(kl)


def _attention(x, w, s, precision: str):
    """x + the layer's sparse attention, and the layer's index loss (the mean
    over positions of the KL term)."""
    seq = x.shape[0]
    heads, kv_heads, dim = s["heads"], s["kv_heads"], s["head_dim"]
    u = _rms_norm(x, w["attn_norm"], s["eps"])
    q = _rms_norm(_mm(u, w["wq"], precision).reshape(seq, heads, dim), w["q_norm"], s["eps"])
    k = _rms_norm(_mm(u, w["wk"], precision).reshape(seq, kv_heads, dim), w["k_norm"], s["eps"])
    v = _mm(u, w["wv"], precision).reshape(seq, kv_heads, dim)
    q, k = _rope(q, s["rope_theta"]), _rope(k, s["rope_theta"])
    a, b, weights = _indexer(u, w, s, precision)
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    n = seq // block
    one = jax.checkpoint(functools.partial(_attend_block, s=s, precision=precision))
    o, kl = jax.lax.map(
        lambda args: one(args, k, v, b),
        (q.reshape(n, block, heads, dim), a.reshape(n, block, *a.shape[1:]), weights.reshape(n, block, -1),
         jnp.arange(0, seq, block)))
    return x + _mm(o.reshape(seq, heads * dim), w["wo"], precision), jnp.sum(kl) / seq


def _route(h, w, s):
    """The router: float32 in every precision.  Returns (probabilities [S,
    experts], gates [S, k] renormalised, chosen [S, k])."""
    probs = jax.nn.softmax(jnp.matmul(h, w["router"]), axis=-1)
    gates, chosen = jax.lax.top_k(probs, s["top_k"])
    return probs, gates / jnp.sum(gates, axis=-1, keepdims=True), chosen


def _experts(h, w, s, precision: str):
    """The held experts' part of the mixture, and the layer's balance term:
    experts * sum_i f_i P_i with f_i the share of the sequence's positions
    that chose expert i among their k (so the f_i sum to k) and P_i the mean
    router probability of i (Switch Transformer's term as Qwen3-MoE's
    `load_balancing_loss_func` applies it to top-k)."""
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
    share = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)  # f_i
    return y, s["experts"] * jnp.sum(share * jnp.mean(probs, axis=0))


def _block(x, w, s, precision: str):
    x, index_loss = _attention(x, w, s, precision)
    y, balance = _experts(_rms_norm(x, w["mlp_norm"], s["eps"]), w, s, precision)
    return x + y, s["aux_coef"] * balance + s["index_coef"] * index_loss


def _layer_weights(stacked: Weights, i: int) -> Weights:
    return {name: leaf[i] for name, leaf in stacked.items()}


def _head_loss(x, final_norm, lm_head, targets, s, precision: str):
    """The final norm, the head and the mean next-token cross-entropy, a block
    of positions' logits at a time (32,768 x 18,992 float32 are 2.5 GB whole)."""
    h = _round(_rms_norm(x, final_norm, s["eps"]), precision)
    head = _round(lm_head, precision)

    def rows(args):
        h_block, targets_block = args
        logits = jnp.matmul(h_block, head)
        picked = jnp.take_along_axis(logits, targets_block[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    seq = x.shape[0]
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    sums = jax.lax.map(jax.checkpoint(rows), (h.reshape(seq // block, block, -1), targets.reshape(seq // block, block)))
    return jnp.sum(sums) / seq


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32"):
    """Mean next-token cross-entropy of one sequence plus its layers' balance
    and index losses; tokens, targets: [S]."""
    with jax.default_matmul_precision("highest"):
        x = _round(weights["embed"], precision)[tokens]
        extra = 0.0
        for i in range(s["layers"]):
            x, layer_extra = jax.checkpoint(functools.partial(_block, s=s, precision=precision))(
                x, _layer_weights(weights["layers"], i))
            extra = extra + layer_extra
        return _head_loss(x, weights["final_norm"], weights["lm_head"], targets, s, precision) + extra


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
    """(weights, tokens[S], targets[S]) -> (loss, gradient tree): `loss` and
    its gradient, computed ONE BLOCK AT A TIME (`_one_sequence`)."""
    return _one_sequence(tuple(sorted(sizes_of(config).items())), precision)


@functools.lru_cache(maxsize=None)
def _one_sequence(frozen_sizes, precision: str):
    """`jax.value_and_grad(loss)` written out as the chain rule over the
    blocks, each step a jitted program of its own: the embedding, every block
    forward (its input kept), the head with its gradient, then every block's
    `jax.vjp` from the last to the first, and the embedding's.  The same
    numbers as differentiating `loss` whole (the tests hold the two together);
    whole, the compiler keeps several blocks' intermediates of 32,768 positions
    alive at once (3.4 GB a block; four blocks read 16.8 GB on a 16 GB chip),
    so the comparison at the cell's size runs this form."""
    s = dict(frozen_sizes)

    def embed(table, tokens):
        return _round(table, precision)[tokens]

    def block(x, w):
        return _block(x, w, s, precision)

    def head(x, final_norm, lm_head, targets):
        return _head_loss(x, final_norm, lm_head, targets, s, precision)

    @jax.jit
    def block_backward(x, w, dx_out):
        _, back = jax.vjp(block, x, w)
        return back((dx_out, jnp.ones((), jnp.float32)))  # the block's extra terms enter the loss with weight one

    @jax.jit
    def embed_backward(table, tokens, dx):
        return jax.vjp(lambda t: embed(t, tokens), table)[1](dx)[0]

    embed_forward, block_forward = jax.jit(embed), jax.jit(block)
    head_and_grads = jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2)))

    def run(weights: Weights, tokens, targets):
        with jax.default_matmul_precision("highest"):
            layers = [_layer_weights(weights["layers"], i) for i in range(s["layers"])]
            inputs, x, extra = [], embed_forward(weights["embed"], tokens), 0.0
            for w in layers:
                inputs.append(x)
                x, layer_extra = block_forward(x, w)
                extra = extra + layer_extra
            ce, (dx, d_norm, d_head) = head_and_grads(x, weights["final_norm"], weights["lm_head"], targets)
            d_layers = [None] * len(layers)
            for i in reversed(range(len(layers))):
                dx, d_layers[i] = block_backward(inputs.pop(), layers[i], dx)
            grads = {
                "embed": embed_backward(weights["embed"], tokens, dx),
                "layers": {name: jnp.stack([d[name] for d in d_layers]) for name in weights["layers"]},
                "final_norm": d_norm,
                "lm_head": d_head,
            }
            return ce + extra, grads

    return run


def selection(weights: Weights, tokens, config: Dict[str, Any], precision: str = "float32"):
    """The keys this reference's indexer selects for one sequence: yields, layer
    by layer, a bool [S, S] whose row t is the set S_t.  What a program's
    selection is set against, to count the near-ties at the topk-th score that
    fell the other way."""
    s = sizes_of(config)

    @jax.jit
    def selected(x, w):
        with jax.default_matmul_precision("highest"):
            a, b, wts = _indexer(_rms_norm(x, w["attn_norm"], s["eps"]), w, s, precision)
            seq = x.shape[0]
            block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
            keep = jax.lax.map(
                lambda args: _selected(_index_scores(args[0], b, args[1], precision), args[2], s["topk"]),
                (a.reshape(seq // block, block, *a.shape[1:]), wts.reshape(seq // block, block, -1),
                 jnp.arange(0, seq, block)))
            return keep.reshape(seq, seq)

    @jax.jit
    def advance(x, w):
        with jax.default_matmul_precision("highest"):
            return _block(x, w, s, precision)[0]

    # The precision is set inside the jitted bodies, not around the loop: a
    # generator suspended inside the context would leave it set for its caller.
    x = _round(weights["embed"], precision)[tokens]
    for i in range(s["layers"]):
        w = _layer_weights(weights["layers"], i)
        yield selected(x, w)
        x = advance(x, w)
