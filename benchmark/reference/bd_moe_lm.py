"""Plain reference of a sparse-expert language model trained by block
diffusion (SDAR-30B-A3B-Chat, `model_type: sdar_moe`: Qwen3-MoE's decoder —
grouped-query attention with a per-head QK-norm, softmax top-k routing over
sparse experts — under BD3-LM's objective), and its weights.

Written from the published description (the model's `config.json`; SDAR,
arXiv:2510.06303, which adapts an auto-regressive model into a block-diffusion
one; BD3-LM, arXiv:2503.09573, whose training pass over a doubled stream this
is) in straightforward `jax.numpy`, float32,
`jax.default_matmul_precision("highest")`.  No kernels, no tile walk, no
sorted rows, no grouped matmul: one sequence at a time, attention one block of
queries and one head at a time against a dense masked score matrix (the noised
queries against both halves' keys, the clean queries against the clean keys),
and the experts as a masked loop over the experts HELD HERE.  It shares no
code with `torchft_tpu/`; the two have in common the layout of the weight tree
(`make_weights`) and the definition of the noise (`noise`, below).

One sequence x of L tokens, blocks of b consecutive tokens, B(i) = i // b:

    key  = fold_in(PRNGKey(noise_seed), sum_i x_i * (i * 2654435761 + 1) mod 2**32);  k_u, k_v = split(key)
    t_k  = eps + (1 - eps) u_k,  u ~ U[0, 1) a block (k_u),  eps = 1e-3   (on float32's uniform grid: `noise`)
    m_i  = [v_i < t_B(i)],       v ~ U[0, 1) a token (k_v)
    x~_i = MASK if m_i else x_i                      (MASK: the vocabulary slice's last id)
    z    = [x~ ; x], 2L positions, position ids [0 .. L-1 ; 0 .. L-1]

48 pre-norm blocks over all 2L positions, h of [2L, hidden]; 32 query heads,
KV head g(h) = h // 8:

    u = RMSNorm(h)
    q[p, h] = RoPE(RMSNorm_128(Wq u[p])_h);  k[p, g] = RoPE(RMSNorm_128(Wk u[p])_g);  v[p, g] = (Wv u[p])_g
    a noised query i sees the noised keys j with B(j) = B(i) and the clean keys j with B(j) < B(i);
    a clean query i sees the clean keys j with B(j) <= B(i) and no noised key   (inside a block: both ways)
    o[p, h] = softmax over the visible keys of q[p, h] . k[., g(h)] / sqrt(128), times v
    h = h + Wo [o[p, 0..31]]
    u' = RMSNorm(h);  p = softmax(Wr u') over ALL the router's outputs (128), in float32
    the 8 largest chosen, their gates renormalised to sum 1
    h = h + sum_{chosen i HELD HERE} gate_i * Wdown_i(silu(Wgate_i u') * Wup_i u')

then the final RMSNorm and the untied head over the NOISED half, position i
predicting x_i (no shift):

    loss = (1 / L) sum_i m_i / t_B(i) * CE(logits_i, x_i)
         + 0.001 * sum over layers of  experts * sum_e f_e P_e      (over the 2L positions; `reference/dsa_moe_lm.py`)

The clean half's rows of the last block are computed and thrown away.

**One chip's share.**  `num_experts` counts the experts held here (the
configuration's `expert_parallel` group says which of the router's outputs
they are); the router keeps its published width, and what the experts held
elsewhere would add is left out — here as in the program.  With every expert
held the same code is the uncut layer.

Departures from the published description, each without effect on the
arithmetic or noted where it has one: `jax.checkpoint` around each block, each
block of queries, each head and each expert of the loop (recomputed in the
backward pass, not computed differently); RoPE pairs column i of a head with
column i + half.  What the catalog does not carry (the block length, the
schedule, the shift, the mask token's row in a sliced vocabulary) is the
configuration file's `assumed`.

`left_out` (`LEFT_OUT`): the same model with one mechanism WRONG, for the
readings that show the comparison sees each — "causal" (a causal mask over the
2L positions), "clean_half" (no clean copy: the noised tokens see the noised
tokens of their own block and of the blocks before), "weight" (no 1 / t: the
masked tokens' plain mean), "shift" (the auto-regressive head kept: position
i - 1 predicts token i), "rope" (position ids 0 .. 2L - 1).

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

Weights = Dict[str, Any]

_SIZE_KEYS = ("vocab", "hidden", "layers", "heads", "kv_heads", "head_dim", "ffn", "held", "experts")
QK_NORM = 3 ** 0.5  # the QK-norms' weights from the seed: a random query's scores are N(0, QK_NORM**4) (`make_weights`)
QUERY_BLOCK = 1024
EPS = 1e-3
LEFT_OUT = ("causal", "clean_half", "weight", "shift", "rope")


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the mathematics needs, by short names.  `held` experts
    `first ... first + held - 1` of the router's `experts` outputs live here."""
    if not config["norm_topk_prob"]:
        raise ValueError("written for renormalised gates")
    if config.get("mlp_only_layers") or config.get("decoder_sparse_step", 1) != 1 or config.get("use_sliding_window"):
        raise ValueError("written for a sparse feed-forward in every layer and no sliding window")
    share = config.get("expert_parallel") or {}
    diffusion = config["block_diffusion"]
    return {
        "vocab": config["vocab_size"],
        "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "ffn": config["moe_intermediate_size"],
        "held": config["num_experts"],
        "experts": share.get("router_outputs", config["num_experts"]),
        "first": share.get("first_expert_held", 0),
        "top_k": config["num_experts_per_tok"],
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "aux_coef": float(config["router_aux_loss_coef"]),
        "block_length": int(diffusion["block_length"]),
        "noise_seed": int(diffusion["noise_seed"]),
    }


@functools.partial(jax.jit, static_argnames=_SIZE_KEYS)
def _weights(key, *, vocab, hidden, layers, heads, kv_heads, head_dim, ffn, held, experts) -> Weights:
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)

    def into_the_stream(k, shape, fan_in):
        """A projection that writes into the residual stream (Wo, Wdown)."""
        return normal(k, shape, fan_in) * (2 * layers) ** -0.5

    n = layers
    ks = jax.random.split(k_layers, 8)
    stacked = {
        "attn_norm": jnp.ones((n, hidden), jnp.float32),
        "wq": normal(ks[0], (n, hidden, heads * head_dim), hidden),
        "wk": normal(ks[1], (n, hidden, kv_heads * head_dim), hidden),
        "wv": normal(ks[2], (n, hidden, kv_heads * head_dim), hidden),
        "wo": into_the_stream(ks[3], (n, heads * head_dim, hidden), heads * head_dim),
        "q_norm": jnp.full((n, head_dim), QK_NORM, jnp.float32),
        "k_norm": jnp.full((n, head_dim), QK_NORM, jnp.float32),
        "mlp_norm": jnp.ones((n, hidden), jnp.float32),
        "router": normal(ks[4], (n, hidden, experts), hidden),
        "w_gate": normal(ks[5], (n, held, hidden, ffn), hidden),
        "w_up": normal(ks[6], (n, held, hidden, ffn), hidden),
        "w_down": into_the_stream(ks[7], (n, held, ffn, hidden), ffn),
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
    too), embedding rows at unit scale, the layers stacked under "layers" with
    a layer's held experts on the next axis — as `reference/dsa_moe_lm.py`
    makes them, but for two settings that this objective forces (PERF.md
    section 6, PR 66: a quarter of the rows carry the ONE [MASK] embedding, and
    at that file's settings attention adds a thousandth of its norm to it, so
    they reach every router as one vector and a near-tie of that vector is
    settled for thousands of rows at once, differently in bfloat16 and float32):

    - the QK-norms' weights are `QK_NORM` = sqrt(3), not one: a random query's
      scores over random keys are N(0, 9), so its softmax holds a handful of
      keys, as a trained model's does, where N(0, 1) is the near-uniform mean
      over thousands — what attention adds to a row is then the row's own;
    - the projections that write into the residual stream (Wo, every Wdown) are
      smaller by sqrt(2 * layers RUN), not by the published model's 48: the
      scaled initialisation of the stack that runs, so that what a layer adds
      is a few percent of the row and not a thousandth.

    The other norm weights are one."""
    s = sizes_of(config)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return _weights(key, **{k: s[k] for k in _SIZE_KEYS})


# -- the noise -----------------------------------------------------------------


def noise(tokens, block_length: int, noise_seed: int):
    """(m [L] bool, t [L] float32) of one sequence's ids [L]: the mask and
    every token's block's level in [EPS, 1), from the sequence's ids and the
    seed alone.  u and v are 23 random bits over 2**23 — float32's uniform
    grid — and the level is taken DOWN to that grid in integer arithmetic,
    t = (LO + floor(u' (2**23 - LO) / 2**23)) / 2**23 with u' = u 2**23 and LO
    = EPS on the grid: a float32 product and sum round once or twice as a
    compiler fuses them, and the mask has to be the program's bit for bit."""
    seq = tokens.shape[0]
    place = jnp.arange(seq, dtype=jnp.uint32) * jnp.uint32(2654435761) + jnp.uint32(1)
    key = jax.random.fold_in(jax.random.PRNGKey(noise_seed), jnp.sum(tokens.astype(jnp.uint32) * place))
    k_u, k_v = jax.random.split(key)
    grid = 2 ** 23
    low = round(EPS * grid)
    span = grid - low
    u = jax.random.bits(k_u, (seq // block_length,), jnp.uint32) >> 9
    v = jax.random.bits(k_v, (seq,), jnp.uint32) >> 9
    # floor(u span / grid) without leaving 32 bits: long multiplication in base 4096, u = a 4096 + c, span = p 4096 + q
    a, c, p, q = u // 4096, u % 4096, span // 4096, span % 4096
    t = jnp.repeat(low + 2 * a * p + (a * q + c * p + c * q // 4096) // 2048, block_length)
    return v < t, t.astype(jnp.float32) / grid


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


def _rope(x, positions, theta):
    """x: [P, H, D]; rotates the pair (x[..., i], x[..., i + D/2]) of the
    row at position id p by the angle p * theta**(-2i/D)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _sees(rows, cols, half: int, s):
    """bool [rows, cols]: whether the query at stream position `rows[i]` sees
    the key at `cols[j]`, of a stream whose first `half` positions are the
    noised copy."""
    without = s.get("left_out")
    if without == "causal":
        return rows[:, None] >= cols[None, :]
    b = s["block_length"]
    row_noised, col_noised = (rows < half)[:, None], (cols < half)[None, :]
    row_block, col_block = ((rows % half) // b)[:, None], ((cols % half) // b)[None, :]
    if without == "clean_half":  # the stream is the noised copy alone: a block sees itself and the blocks before
        return col_block <= row_block
    return jnp.where(row_noised, jnp.where(col_noised, col_block == row_block, col_block < row_block),
                     ~col_noised & (col_block <= row_block))


def _attend_block(args, k, v, cols, half, s, precision: str):
    """One block of queries q [block, H, D] at the stream positions `rows`
    against the keys k, v [K, KV, D] at the stream positions `cols`."""
    q, rows = args
    heads, group = s["heads"], s["heads"] // s["kv_heads"]
    keep = _sees(rows, cols, half, s)
    k_r, v_r = _round(k, precision), _round(v, precision)

    def out_of(h):
        logits = jnp.matmul(_round(q[:, h], precision), k_r[:, h // group].T) * s["head_dim"] ** -0.5
        probs = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        return jnp.matmul(_round(probs, precision), v_r[:, h // group])

    o = jax.lax.map(jax.checkpoint(out_of), jnp.arange(heads))                 # [H, block, D]
    return o.transpose(1, 0, 2).reshape(q.shape[0], -1)


def _attention(x, w, half, s, precision: str):
    """x [P, hidden] + the layer's attention under the three-part mask: the
    noised queries (the first `half` positions) against every key, the clean
    queries against the clean keys (they see no noised one)."""
    seq = x.shape[0]
    heads, kv_heads, dim = s["heads"], s["kv_heads"], s["head_dim"]
    place = jnp.arange(seq)
    positions = place if s.get("left_out") == "rope" else place % half
    u = _rms_norm(x, w["attn_norm"], s["eps"])
    q = _rms_norm(_mm(u, w["wq"], precision).reshape(seq, heads, dim), w["q_norm"], s["eps"])
    k = _rms_norm(_mm(u, w["wk"], precision).reshape(seq, kv_heads, dim), w["k_norm"], s["eps"])
    v = _mm(u, w["wv"], precision).reshape(seq, kv_heads, dim)
    q, k = _rope(q, positions, s["rope_theta"]), _rope(k, positions, s["rope_theta"])
    one = jax.checkpoint(functools.partial(_attend_block, half=half, s=s, precision=precision))

    def part(first: int, count: int, key_first: int):
        """Queries first .. first + count - 1 against the keys from key_first on."""
        block = QUERY_BLOCK if count % QUERY_BLOCK == 0 else count
        n = count // block
        return jax.lax.map(
            lambda args: one(args, k[key_first:], v[key_first:], place[key_first:]),
            (q[first:first + count].reshape(n, block, heads, dim), place[first:first + count].reshape(n, block)),
        ).reshape(count, heads * dim)

    if half == seq or s.get("left_out") == "causal":  # one half alone, or a rule that crosses the halves anyhow
        o = part(0, seq, 0)
    else:
        o = jnp.concatenate([part(0, half, 0), part(half, seq - half, half)])
    return x + _mm(o, w["wo"], precision)


def _route(h, w, s):
    """The router: float32 in every precision.  Returns (probabilities [P,
    experts], gates [P, k] renormalised, chosen [P, k])."""
    probs = jax.nn.softmax(jnp.matmul(h, w["router"]), axis=-1)
    gates, chosen = jax.lax.top_k(probs, s["top_k"])
    return probs, gates / jnp.sum(gates, axis=-1, keepdims=True), chosen


def _experts(h, w, s, precision: str):
    """The held experts' part of the mixture, and the layer's balance term
    over the positions of h (`reference/dsa_moe_lm.py` `_experts`)."""
    probs, gates, chosen = _route(h, w, s)
    one_hot = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32)  # [P, k, experts]
    gate_of = jnp.einsum("sk,ske->es", gates, one_hot)  # [experts, P]: 0 where not chosen
    gate_of = gate_of[s["first"]: s["first"] + s["held"]]

    @jax.checkpoint
    def gated(gate_for_it, w_gate, w_up, w_down):
        return gate_for_it[:, None] * _swiglu(h, w_gate, w_up, w_down, precision)

    def one(y, expert):
        return y + gated(*expert), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate_of, w["w_gate"], w["w_up"], w["w_down"]))
    share = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)  # f_e
    return y, s["experts"] * jnp.sum(share * jnp.mean(probs, axis=0))


def _block(x, w, half, s, precision: str):
    x = _attention(x, w, half, s, precision)
    y, balance = _experts(_rms_norm(x, w["mlp_norm"], s["eps"]), w, s, precision)
    return x + y, s["aux_coef"] * balance


def _layer_weights(stacked: Weights, i: int) -> Weights:
    return {name: leaf[i] for name, leaf in stacked.items()}


def _head_loss(x, final_norm, lm_head, tokens, weight, s, precision: str):
    """The final norm and the head over the noised half's rows x [L, hidden],
    and the weighted cross-entropy of row i against token i, a block of
    rows' logits at a time."""
    h = _round(_rms_norm(x, final_norm, s["eps"]), precision)
    head = _round(lm_head, precision)

    def rows(args):
        h_block, tokens_block, weight_block = args
        logits = jnp.matmul(h_block, head)
        picked = jnp.take_along_axis(logits, tokens_block[:, None], axis=-1)[:, 0]
        return jnp.sum(weight_block * (jax.nn.logsumexp(logits, axis=-1) - picked))

    seq = x.shape[0]
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    n = seq // block
    sums = jax.lax.map(jax.checkpoint(rows), (h.reshape(n, block, -1), tokens.reshape(n, block), weight.reshape(n, block)))
    return jnp.sum(sums) / seq


def _stream(tokens, s):
    """(the stream's ids, the loss's target and its weight a row of the noised
    copy)."""
    m, t = noise(tokens, s["block_length"], s["noise_seed"])
    without = s.get("left_out")
    weight = jnp.where(m, 1.0 if without == "weight" else 1.0 / t, 0.0)
    targets = tokens
    if without == "shift":  # row i - 1 predicts token i: row i carries token i + 1's target and weight, the last row none
        targets = jnp.roll(tokens, -1)
        weight = jnp.roll(weight, -1).at[-1].set(0.0)
    noised = jnp.where(m, s["vocab"] - 1, tokens)
    ids = noised if without == "clean_half" else jnp.concatenate([noised, tokens])
    return ids, targets, weight


def loss(weights: Weights, tokens, targets, s: Dict[str, Any], precision: str = "float32"):
    """The block-diffusion loss of one sequence plus its layers' balance
    terms; tokens: [L].  `targets` (the job's roll by one) is not read."""
    del targets
    with jax.default_matmul_precision("highest"):
        half = tokens.shape[0]
        ids, wanted, weight = _stream(tokens, s)
        x = _round(weights["embed"], precision)[ids]
        extra = 0.0
        for i in range(s["layers"]):
            x, layer_extra = jax.checkpoint(functools.partial(_block, half=half, s=s, precision=precision))(
                x, _layer_weights(weights["layers"], i))
            extra = extra + layer_extra
        return _head_loss(x[:half], weights["final_norm"], weights["lm_head"], wanted, weight, s, precision) + extra


def loss_and_grads(weights: Weights, tokens, targets, config: Dict[str, Any],
                   precision: str = "float32") -> Tuple[jax.Array, Weights]:
    """Loss and its gradient for a batch [B, L], one sequence at a time,
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
    """(weights, tokens[L], targets[L]) -> (loss, gradient tree): `loss` and
    its gradient, computed ONE BLOCK AT A TIME (`_one_sequence`).  `left_out`:
    one of `LEFT_OUT`."""
    assert not left_out or left_out in LEFT_OUT, left_out
    return _one_sequence(tuple(sorted(dict(sizes_of(config), left_out=left_out).items())), precision)


@functools.lru_cache(maxsize=None)
def _one_sequence(frozen_sizes, precision: str):
    """`jax.value_and_grad(loss)` written out as the chain rule over the
    blocks, each step a jitted program of its own (`reference/dsa_moe_lm.py`
    says why: whole, the compiler keeps several blocks' intermediates of
    32,768 positions alive at once)."""
    s = dict(frozen_sizes)

    def embed(table, ids):
        return _round(table, precision)[ids]

    def head(x, final_norm, lm_head, targets, weight, half):
        return _head_loss(x[:half], final_norm, lm_head, targets, weight, s, precision)

    @functools.partial(jax.jit, static_argnames="half")
    def block_forward(x, w, half):
        return _block(x, w, half, s, precision)

    @functools.partial(jax.jit, static_argnames="half")
    def block_backward(x, w, dx_out, half):
        _, back = jax.vjp(lambda x, w: _block(x, w, half, s, precision), x, w)
        return back((dx_out, jnp.ones((), jnp.float32)))  # the block's extra term enters the loss with weight one

    @jax.jit
    def embed_backward(table, ids, dx):
        return jax.vjp(lambda t: embed(t, ids), table)[1](dx)[0]

    embed_forward, stream = jax.jit(embed), jax.jit(lambda tokens: _stream(tokens, s))
    head_and_grads = jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2)), static_argnames="half")

    def run(weights: Weights, tokens, targets=None):
        with jax.default_matmul_precision("highest"):
            half = tokens.shape[0]
            ids, wanted, weight = stream(tokens)
            layers = [_layer_weights(weights["layers"], i) for i in range(s["layers"])]
            inputs, x, extra = [], embed_forward(weights["embed"], ids), 0.0
            for w in layers:
                inputs.append(x)
                x, layer_extra = block_forward(x, w, half=half)
                extra = extra + layer_extra
            ce, (dx, d_norm, d_head) = head_and_grads(x, weights["final_norm"], weights["lm_head"], wanted, weight, half=half)
            d_layers = [None] * len(layers)
            for i in reversed(range(len(layers))):
                dx, d_layers[i] = block_backward(inputs.pop(), layers[i], dx, half=half)
            grads = {
                "embed": embed_backward(weights["embed"], ids, dx),
                "layers": {name: jnp.stack([d[name] for d in d_layers]) for name in weights["layers"]},
                "final_norm": d_norm,
                "lm_head": d_head,
            }
            return ce + extra, grads

    return run


def chosen(weights: Weights, tokens, config: Dict[str, Any], precision: str = "float32"):
    """The experts this reference's router chooses for one sequence's 2L
    positions: int32 [layers, 2L, k], each position's k sorted.  What a
    program's choices are set against, to count the near-ties that fell the
    other way."""
    s = sizes_of(config)
    half = tokens.shape[0]

    @jax.jit
    def advance(x, w):
        with jax.default_matmul_precision("highest"):
            x = _attention(x, w, half, s, precision)
            h = _rms_norm(x, w["mlp_norm"], s["eps"])
            return jnp.sort(_route(h, w, s)[2], axis=-1), x + _experts(h, w, s, precision)[0]

    x = _round(weights["embed"], precision)[_stream(tokens, s)[0]]
    picked = []
    for i in range(s["layers"]):
        choice, x = advance(x, _layer_weights(weights["layers"], i))
        picked.append(choice)
    return jnp.stack(picked)
