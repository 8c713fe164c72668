"""Learned sparse attention (DeepSeek-V3.2's lightning indexer over GQA): an
indexer scores every visible (query, key) pair, each query keeps its `topk`
best keys, and attention runs over that selection alone.

For one sequence, with index queries ``a`` [J, S, Di], ONE index key head
``b`` [S, Di] and per-query head weights ``w`` [S, J] (the two scale factors
J**-0.5 and Di**-0.5 already in ``w``):

    I[t, s] = sum_j w[t, j] * relu(a[j, t] . b[s])              for s <= t
    S_t     = the min(t + 1, topk) largest I[t, :t + 1], ties to the lower s
    out[t, h] = softmax_{s in S_t}(q[t, h] . k[s, g(h)] * scale) v[s, g(h)]
    loss    = mean_t KL( pbar[t, :] || softmax_{s in S_t} I[t, s] )
    pbar[t, s] = (1 / H) sum_h  (head h's attention probability of s)

``out`` is differentiated with respect to q, k, v with the selection a
constant; ``loss`` with respect to a, b, w with ``pbar`` a constant
(DeepSeek-V3.2's sparse-stage objective).  Nothing else flows: the caller
hands the indexer a detached input.

On one TPU device five pallas kernels do it, none of which holds an [S, S]
float32 array in HBM:

  - ``tpuft_dsa_select`` (per block of 256 queries): the block's index scores
    against every visible key stay in VMEM as order-preserving int32 keys
    ([256, S]: 32 MiB at 32,768); the EXACT topk-th largest of each row is
    found by bisection on the key's 32 bits (each pass counts the entries at
    or above a candidate), ties at the threshold are cut at a position found
    the same way, and the row's log-sum-exp over the selection is taken while
    the scores are there.  Out: a threshold, a position cut and a
    log-sum-exp per query — 12 bytes a query to keep for the backward pass;
  - ``tpuft_dsa_mask``: the selection as an int8 [S, S] mask (1 GiB at
    32,768, alive for one layer's attention), rebuilt from the thresholds by
    scoring the tiles once more — never by selecting again.  The forward and
    the backward pass both read the mask this kernel makes from the same
    operands, so the two cannot disagree;
  - ``tpuft_dsa_attn_fwd`` / ``tpuft_dsa_attn_bwd_dkdv_dq``: the flash
    kernels of ops/attention.py with the mask tile in place of the causal
    triangle.  They visit every causal tile and no other (a triangular
    grid walk, `ops.attention._Walk`); at weights drawn from a seed the
    selection leaves no tile past the dense prefix empty, so there is
    nothing more for a walk to skip (PERF.md section 7);
  - ``tpuft_dsa_index_loss``: per tile the 32 heads' probabilities again
    (one QK^T a head, from the saved log-sum-exps) summed into pbar, the KL
    row sums, and IN THE SAME PASS the loss's gradient with respect to a, b
    and w — d loss / d I = (softmax(I) - pbar) / (B S) on the selection —
    which the backward pass only scales by the loss's cotangent.

``tpuft_dsa_mask`` and ``tpuft_dsa_index_loss`` walk their 256 x 512 tiles
the same way: one grid step for each tile that holds a visible pair, row by
row, none for a tile above the diagonal.

What the two selection-side kernels' loops carry is sized by the vector
register file (64 registers of 8 x 128; one store slot a bundle, so a carry
that does not fit is spilled and reloaded whole every iteration, and the
loop runs at the store slot's pace — PERF.md section 6, PR 55; read the
schedule with `tools/dsa_probe.py`):

  - ``tpuft_dsa_select`` folds a block's keys 64 rows at a time.  A count
    (an integer sum: exact in any order) and the maximum add a key tile's
    four lane blocks into a [64, 128] carry, 8 registers, beside the
    threshold they compare with (a value a row is a register for every
    eight rows once it is spread over the lanes) — so ``tau`` and ``cut``
    are what any order of the additions gives.  The log-sum-exp's float sum
    keeps a tile's every column apart ([32, 512] carries) and the order in
    which the tiles are added, so ``z`` keeps its bits;
  - ``tpuft_dsa_index_loss`` carries the heads' sum [256, 512] float32 — 128
    registers, twice the file: it cannot be narrowed (products of fewer
    columns or rows leave the MXU waiting on their latency), so its loop runs
    several heads a body (`_heads_a_body`: four where the count divides), the
    next head's product standing under this one's exponential and its
    load-add-store of the carry; the heads are added in ascending order as in
    a loop of one.

Off-TPU, under a multi-device mesh and for shapes the kernels do not tile,
the same mathematics runs in plain XLA with dense [S, S] scores and
``jax.lax.top_k`` (``_dsa_xla``: also the oracle the kernels are tested
against).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops import _pallas_util
from torchft_tpu.ops import attention as _fa

_INT_MIN = -(2 ** 31)
BLOCK_Q = 256   # rows of a select / mask / index-loss tile
BLOCK_K = 512   # key columns of a tile
# Rows that a fold of `tpuft_dsa_select` carries through the key tiles at a
# time (the compiler's schedule at the Keye cell's shapes, bundles a key tile
# of 256 x 512: a count 128 at 64 rows, 176 at 32, 130 with spills at 128;
# the float sum 464 at 32 rows, 632 at 64 — `tools/dsa_probe.py`).
COUNT_ROWS = 64  # a count, the maximum: a [64, 128] int32 carry, 8 registers
SUM_ROWS = 32    # the log-sum-exp's float sum: a [32, 512] f32 carry, 16 registers
_VMEM_LIMIT = 100 * 2 ** 20

# What a rematerialised layer keeps so that its backward pass neither scores,
# selects nor attends again (see ops.attention.SAVED_NAMES): the attention
# output and row statistics, the selection's thresholds, and the index
# loss with its ready gradient.
SAVED_NAMES = tuple("tpuft_dsa_" + kept for kept in (
    "out", "lse", "tau", "cut", "loss", "da", "db", "dw", "selected"))


def _kept(x, name: str):
    from jax.ad_checkpoint import checkpoint_name

    assert "tpuft_dsa_" + name in SAVED_NAMES
    return checkpoint_name(x, "tpuft_dsa_" + name)


def kernels_apply(seq: int, d_head: int, d_index: int, mesh=None) -> bool:
    """Whether the five kernels run: the sequence tiles in blocks of 512, a
    head is a lane multiple wide, and the program being traced runs on one
    TPU device (`_pallas_util.kernels_apply`); else the XLA formulation."""
    return (
        seq % BLOCK_K == 0
        and d_head % _pallas_util.LANE == 0
        and d_index % 8 == 0
        and _pallas_util.kernels_apply(mesh)
    )


# -- in-tile pieces shared by the kernels --------------------------------------


def _sortable(x):
    """f32 -> int32 with the same order (-0.0 first made +0.0)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _unsortable(key):
    return jax.lax.bitcast_convert_type(key ^ ((key >> 31) & 0x7FFFFFFF), jnp.float32)


def _index_tile(a_ref, bt, w, heads: int):
    """I of one tile: a_ref (1, J, bq, Di) bf16, bt [Di, bk] bf16 (the key
    head transposed), w [bq, J] f32 -> [bq, bk] f32.  The one place the
    kernels score a tile, so that each sees bit for bit the same score."""
    acc = None
    for j in range(heads):
        r = jax.lax.dot(a_ref[0, j], bt, preferred_element_type=jnp.float32)
        term = w[:, j:j + 1] * jnp.maximum(r, 0.0)
        acc = term if acc is None else acc + term
    return acc


def _tile_positions(qi, ki, bq: int, bk: int):
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows, cols


# -- tpuft_dsa_select ------------------------------------------------------------


def _select_kernel(a_ref, bt_ref, w_ref, tau_ref, cut_ref, z_ref, keys_scr,
                   *, heads: int, topk: int, bq: int, bk: int, seq: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    tiles = (qi * bq + bq - 1) // bk + 1  # key tiles that hold a visible column
    w = w_ref[0]
    lane = _pallas_util.LANE

    def fill(kt, _):
        cols0 = pl.multiple_of(kt * bk, bk)
        score = _index_tile(a_ref, bt_ref[0, :, pl.ds(cols0, bk)], w, heads)
        rows, cols = _tile_positions(qi, kt, bq, bk)
        keys_scr[:, pl.ds(cols0, bk)] = jnp.where(rows >= cols, _sortable(score), _INT_MIN)
        return 0

    jax.lax.fori_loop(0, tiles, fill, 0)

    # What a fold carries through the key tiles fits the vector registers
    # (the module docstring): some rows at a time, and where the order of the
    # additions does not matter a tile's lane blocks folded into one.
    def fold(step, rows: int, width: int, init, *per_row):
        """acc = step(acc, keys, cols, *per_row) over the visible tiles of
        the block's keys, `rows` rows at a time from [rows, width] of `init`
        -> [bq, width]; `per_row` are [bq, 1] values the step reads.  Rows
        do not meet in a step."""
        rows = rows if bq % rows == 0 else bq
        done = []
        for r0 in range(0, bq, rows):
            here = [v[r0:r0 + rows] for v in per_row]

            def body(kt, acc):
                cols0 = pl.multiple_of(kt * bk, bk)
                cols = cols0 + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
                return step(acc, keys_scr[r0:r0 + rows, pl.ds(cols0, bk)], cols, *here)  # noqa: B023

            done.append(jax.lax.fori_loop(0, tiles, body, jnp.full((rows, width), init)))
        return jnp.concatenate(done, axis=0)

    width = lane if bk % lane == 0 else bk

    def lanes(x, op):
        """[rows, bk] -> [rows, width]: the tile's lane blocks folded by op."""
        return functools.reduce(op, [x[:, i:i + width] for i in range(0, bk, width)])

    def count(pred, *per_row):
        """Per row, how many entries of the visible tiles satisfy pred(keys, cols, *per_row)."""
        part = fold(lambda acc, *x: acc + lanes(pred(*x).astype(jnp.int32), jnp.add), COUNT_ROWS, width, jnp.int32(0), *per_row)
        return jnp.sum(part, axis=1, keepdims=True)

    row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    want = jnp.minimum(row + 1, topk)  # keys this query keeps

    # The want-th largest key: the largest T with count(key >= T) >= want,
    # built from the sign down (for a negative T setting a lower bit raises it).
    tau = jnp.where(count(lambda k, _: k >= 0) >= want, 0, _INT_MIN).astype(jnp.int32)

    def bit_step(i, tau):
        cand = tau | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(lambda k, _, at: k >= at, cand) >= want, cand, tau)

    tau = jax.lax.fori_loop(0, 31, bit_step, tau)
    # Ties at the threshold go to the lower position: of the keys equal to
    # tau the first `need`, which end at the largest position P with fewer
    # than `need` equal keys before it.
    need = want - count(lambda k, _, at: k > at, tau)

    def cut_step(i, cut):
        cand = cut | jnp.left_shift(jnp.int32(1), (seq - 1).bit_length() - 1 - i)
        return jnp.where(count(lambda k, c, at, before: (k == at) & (c < before), tau, cand) < need, cand, cut)

    # Only a block in which some query has more keys tied at its threshold
    # than it may keep pays for these passes; elsewhere every key equal to
    # tau is kept, whatever its position.
    tied = count(lambda k, _, at: k == at, tau)
    cut = jax.lax.cond(
        jnp.max(tied - need) > 0,
        lambda: jax.lax.fori_loop(0, (seq - 1).bit_length(), cut_step, jnp.zeros((bq, 1), jnp.int32)),
        lambda: jnp.full((bq, 1), seq, jnp.int32),
    )

    # The selection's log-sum-exp of I, while the scores are here.  (An
    # invisible entry holds INT_MIN, below every tau: it is never selected.)
    # The float sum keeps a tile's every column apart and the order of its
    # additions, so z keeps its bits.
    top = _unsortable(jnp.max(
        fold(lambda acc, k, _: jnp.maximum(acc, lanes(k, jnp.maximum)), COUNT_ROWS, width, jnp.int32(_INT_MIN)),
        axis=1, keepdims=True))
    total = jnp.sum(fold(
        lambda acc, k, c, tau, cut, top: acc + jnp.where(
            (k > tau) | ((k == tau) & (c <= cut)), jnp.exp(_unsortable(k) - top), 0.0),
        SUM_ROWS, bk, jnp.float32(0.0), tau, cut, top), axis=1, keepdims=True)
    tau_ref[0] = jnp.broadcast_to(tau, tau_ref.shape[1:])
    cut_ref[0] = jnp.broadcast_to(cut, cut_ref.shape[1:])
    z_ref[0] = jnp.broadcast_to(top + jnp.log(total), z_ref.shape[1:])


def _select_pallas(a, bt, w, topk: int, interpret: bool = False):
    """a [B, J, S, Di], bt [B, Di, S], w [B, S, J] f32 -> (tau int32, cut
    int32, z f32), each [B, S, 1]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq, di = a.shape
    bq, bk = min(BLOCK_Q, seq), min(BLOCK_K, seq)
    lane = _pallas_util.LANE
    row_spec = pl.BlockSpec((1, bq, lane), lambda b, i: (b, i, 0))
    tau, cut, z = pl.pallas_call(
        functools.partial(_select_kernel, heads=heads, topk=topk, bq=bq, bk=bk, seq=seq),
        out_shape=(
            jax.ShapeDtypeStruct((batch, seq, lane), jnp.int32),
            jax.ShapeDtypeStruct((batch, seq, lane), jnp.int32),
            jax.ShapeDtypeStruct((batch, seq, lane), jnp.float32),
        ),
        grid=(batch, seq // bq),
        in_specs=[
            pl.BlockSpec((1, heads, bq, di), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, di, seq), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bq, heads), lambda b, i: (b, i, 0)),
        ],
        out_specs=(row_spec, row_spec, row_spec),
        scratch_shapes=[pltpu.VMEM((bq, seq), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_dsa_select",
    )(a, bt, w)
    return tau[:, :, :1], cut[:, :, :1], z[:, :, :1]


# -- tpuft_dsa_mask ---------------------------------------------------------------


def _walk(seq: int) -> "_fa._Walk":
    """This file's (bq, bk) tiles, causal over one sequence: a step for each
    tile with a visible pair, row by row (`ops.attention._Walk`)."""
    return _fa._Walk(True, seq, seq, min(BLOCK_Q, seq), min(BLOCK_K, seq))


def _mask_kernel(*refs, walk, heads: int):
    # A tile above the diagonal has no place in the packed triangle, and no
    # step in the walk.
    qi, ki, (a_ref, bt_ref, w_ref, tau_ref, cut_ref, mask_ref) = walk.tile(refs)
    keys = _sortable(_index_tile(a_ref, bt_ref[0], w_ref[0], heads))
    rows, cols = _tile_positions(qi, ki, walk.block_q, walk.block_k)
    tau = tau_ref[0]
    keep = ((keys > tau) | ((keys == tau) & (cols <= cut_ref[0]))) & (rows >= cols)
    mask_ref[0, 0] = jnp.where(keep, 1, 0).astype(jnp.int8)


def _mask_spec(walk):
    """How this file's (bq, bk) tiles read the packed mask, whose tiles are
    the flash kernels' (tile, bk): a half of a tile where bq is."""
    sub = _fa._block_sizes(walk.seq_q, walk.seq_k)[0] // walk.block_q
    return walk.spec((1, 1, walk.block_q, walk.block_k), lambda b, i, j: (b, _fa._tri(i // sub, j), i % sub, 0))


def _mask_pallas(a, bt, w, tau, cut, interpret: bool = False):
    """The selection as int8 [B, tiles, tile, bk]: the lower triangle's
    (tile, bk) tiles row by row (`ops.attention._tri`), 1 where query t
    keeps key s."""
    from jax.experimental import pallas as pl

    batch, heads, seq, di = a.shape
    walk = _walk(seq)
    spec, bq, bk = walk.spec, walk.block_q, walk.block_k
    tile = _fa._block_sizes(seq, seq)[0]
    n = seq // tile
    row_spec = spec((1, bq, 1), lambda b, i, j: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_mask_kernel, walk=walk, heads=heads),
        out_shape=jax.ShapeDtypeStruct((batch, n * (n + 1) // 2, tile, bk), jnp.int8),
        grid_spec=walk.grid_spec(
            batch,
            in_specs=[
                spec((1, heads, bq, di), lambda b, i, j: (b, 0, i, 0)),
                spec((1, di, bk), lambda b, i, j: (b, 0, j)),
                spec((1, bq, heads), lambda b, i, j: (b, i, 0)),
                row_spec, row_spec,
            ],
            out_specs=_mask_spec(walk),
        ),
        interpret=interpret,
        name="tpuft_dsa_mask",
    )(*walk.tables, a, bt, w, tau, cut)


# -- tpuft_dsa_index_loss -----------------------------------------------------------


def _index_loss_kernel(*refs, walk, q_heads: int, kv_heads: int, heads: int, heads_a_body: int, scale: float,
                       inv_rows: float):
    from jax.experimental import pallas as pl

    qi, ki, (q_ref, k_ref, lse_ref, a_ref, bt_ref, w_ref, z_ref, mask_ref,
             kl_ref, da_ref, dbt_ref, dw_ref, kl_scr) = walk.tile(refs)
    bq, bk = walk.block_q, walk.block_k

    @pl.when((qi == 0) & (ki == 0))
    def _init_keys():
        dbt_ref[...] = jnp.zeros_like(dbt_ref)

    @pl.when(ki == 0)
    def _init_rows():
        kl_scr[...] = jnp.zeros_like(kl_scr)
        da_ref[...] = jnp.zeros_like(da_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    # (every step of the walk is a tile with a visible pair)
    group = q_heads // kv_heads
    d = q_ref.shape[2] // q_heads  # q's and k's blocks are [1, rows, heads * d]: head h the lane-aligned column block at h * d
    head = lambda ref, h: ref[0, :, pl.ds(pl.multiple_of(h * d, d), d)]  # noqa: E731

    def some_heads(i, total):
        # `heads_a_body` heads a loop body, added in ascending order: head
        # h + 1's product stands under head h's exponential and its
        # load-add-store of the carry ([bq, bk] f32, twice the register file)
        for u in range(heads_a_body):
            h = i * heads_a_body + u
            s = jax.lax.dot_general(
                head(q_ref, h), head(k_ref, jax.lax.div(h, group)), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            lse = jnp.transpose(lse_ref[0, h, 0:1, pl.ds(pl.multiple_of(qi * bq, bq), bq)], (1, 0))  # [bq, 1]
            total = total + jnp.exp(s - lse)
        return total

    keep = mask_ref[0, 0].astype(jnp.int32) != 0
    total = jax.lax.fori_loop(0, q_heads // heads_a_body, some_heads, jnp.zeros((bq, bk), jnp.float32))
    pbar = jnp.where(keep, total * (1.0 / q_heads), 0.0)
    bt, w = bt_ref[0], w_ref[0]
    log_q = _index_tile(a_ref, bt, w, heads) - z_ref[0]
    kl = jnp.where(pbar > 0.0, pbar * (jnp.log(jnp.where(pbar > 0.0, pbar, 1.0)) - log_q), 0.0)
    kl_scr[:, 0:1] += jnp.sum(kl, axis=1, keepdims=True)
    # d loss / d I on the selection; the index heads' products once more
    # for what each passes back
    g = jnp.where(keep, jnp.exp(jnp.where(keep, log_q, 0.0)) - pbar, 0.0) * inv_rows
    cols0 = pl.multiple_of(ki * bk, bk)
    for j in range(heads):
        a_j = a_ref[0, j]
        r = jax.lax.dot(a_j, bt, preferred_element_type=jnp.float32)
        dw_ref[0, :, j:j + 1] += jnp.sum(g * jnp.maximum(r, 0.0), axis=1, keepdims=True)
        e = jnp.where(r > 0.0, g * w[:, j:j + 1], 0.0).astype(a_j.dtype)
        da_ref[0, j] += jax.lax.dot_general(
            e, bt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)      # e @ b: [bq, Di]
        dbt_ref[0, :, pl.ds(cols0, bk)] += jax.lax.dot_general(
            a_j, e, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)     # a^T @ e: [Di, bk]

    @pl.when(ki == walk.last_k(qi))
    def _emit():
        kl_ref[0] = jnp.broadcast_to(kl_scr[:, 0:1], kl_ref.shape[1:])


def _heads_a_body(q_heads: int) -> int:
    """Query heads a body of the index loss's loop over them: four where the
    count divides, else two, else one.  (At the Keye cell's shapes a head is
    532 bundles of the compiler's schedule in a body of one, 387 of two, 362
    of four, 357 of eight, and a call 118.4 / 91.2 / 84.3 / 80.9 ms on a
    v5e: eight doubles the body for the last 4% — PERF.md section 6, PR 55.)"""
    return next(u for u in (4, 2, 1) if q_heads % u == 0)


def _index_loss_pallas(q, k, lse, a, bt, w, z, mask, scale: float, interpret: bool = False,
                       heads_a_body: int | None = None):
    """q [B, S, H, D], k [B, S, KV, D], lse [B, H, S] -> (kl rows [B, S],
    d loss/d a [B, J, S, Di] f32, d loss/d bt [B, Di, S] f32, d loss/d w
    [B, S, J] f32) of loss = sum(kl rows) / (B * S).  `heads_a_body` is for
    tests and tools/dsa_probe.py (1 is the loop of one head a body); the
    program reads it from the shapes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, q_heads, d = q.shape
    kv_heads, heads, di = k.shape[2], a.shape[1], a.shape[3]
    heads_a_body = heads_a_body or _heads_a_body(q_heads)
    assert q_heads % heads_a_body == 0, (q_heads, heads_a_body)
    walk = _walk(seq)
    spec, bq, bk = walk.spec, walk.block_q, walk.block_k
    lane = _pallas_util.LANE
    kl, da, dbt, dw = pl.pallas_call(
        functools.partial(
            _index_loss_kernel, walk=walk, q_heads=q_heads, kv_heads=kv_heads, heads=heads,
            heads_a_body=heads_a_body, scale=scale, inv_rows=1.0 / (batch * seq)),
        out_shape=(
            jax.ShapeDtypeStruct((batch, seq, lane), jnp.float32),
            jax.ShapeDtypeStruct(a.shape, jnp.float32),
            jax.ShapeDtypeStruct(bt.shape, jnp.float32),
            jax.ShapeDtypeStruct(w.shape, jnp.float32),
        ),
        grid_spec=walk.grid_spec(
            batch,
            in_specs=[
                spec((1, bq, q_heads * d), lambda b, i, j: (b, i, 0)),
                spec((1, bk, kv_heads * d), lambda b, i, j: (b, j, 0)),
                spec((1, q_heads, 1, seq), lambda b, i, j: (b, 0, 0, 0)),   # whole rows, as the flash kernels'
                spec((1, heads, bq, di), lambda b, i, j: (b, 0, i, 0)),
                spec((1, di, bk), lambda b, i, j: (b, 0, j)),
                spec((1, bq, heads), lambda b, i, j: (b, i, 0)),
                spec((1, bq, 1), lambda b, i, j: (b, i, 0)),
                _mask_spec(walk),
            ],
            out_specs=(
                spec((1, bq, lane), lambda b, i, j: (b, i, 0)),
                spec((1, heads, bq, di), lambda b, i, j: (b, 0, i, 0)),
                spec((1, di, seq), lambda b, i, j: (b, 0, 0)),   # a sequence's whole row, resident
                spec((1, bq, heads), lambda b, i, j: (b, i, 0)),
            ),
            scratch_shapes=[pltpu.VMEM((bq, lane), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=walk.semantics("arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_dsa_index_loss",
    )(*walk.tables, q.reshape(batch, seq, -1), k.reshape(batch, seq, -1), lse[:, :, None, :], a, bt, w, z, mask)
    return kl[:, :, 0], da, dbt, dw


# -- the plain XLA formulation ---------------------------------------------------------


def index_scores(a, bt, w):
    """Dense I [B, S, S] f32 (every pair, the causal rule not applied)."""
    r = jnp.einsum("bjtd,bds->bjts", a, bt, preferred_element_type=jnp.float32)
    return jnp.einsum("btj,bjts->bts", w.astype(jnp.float32), jnp.maximum(r, 0.0))


def selection_mask(scores, topk: int):
    """bool [B, S, S]: the min(t + 1, topk) largest visible scores of each
    query, ties to the lower position (`jax.lax.top_k`'s rule)."""
    seq = scores.shape[-1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, seq))
    return _scatter_rows(idx, seq) & causal


def _scatter_rows(idx, seq: int):
    batch, rows, _ = idx.shape
    flat = jnp.zeros((batch * rows, seq), bool)
    flat = flat.at[jnp.arange(batch * rows)[:, None], idx.reshape(batch * rows, -1)].set(True)
    return flat.reshape(batch, rows, seq)


def _dsa_xla(q, k, v, a, bt, w, topk: int, scale: float):
    """The module docstring's mathematics with dense scores; differentiated
    by autodiff.  q [B, S, H, D], k/v [B, S, KV, D], turned to head-major in
    here, and the output back."""
    batch, seq, q_heads, _ = q.shape
    group = q_heads // k.shape[2]
    with jax.named_scope("attn"):
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    with jax.named_scope("dsa_index"):
        scores = index_scores(a, bt, w)
    with jax.named_scope("dsa_select"):
        keep = selection_mask(jax.lax.stop_gradient(scores), topk)[:, None]   # [B, 1, S, S]
        selected = jnp.sum(keep, dtype=jnp.int32)
    with jax.named_scope("attn"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bhkd->bqhd", p.astype(v.dtype), v).astype(q.dtype)
    with jax.named_scope("dsa_index"):
        pbar = jax.lax.stop_gradient(jnp.mean(p, axis=1))                     # [B, S, S]
        log_q = jax.nn.log_softmax(jnp.where(keep[:, 0], scores, -jnp.inf), axis=-1)
        kl = jnp.where(pbar > 0.0, pbar * (jnp.log(jnp.where(pbar > 0.0, pbar, 1.0)) - jnp.where(keep[:, 0], log_q, 0.0)), 0.0)
        loss = jnp.sum(kl) / (batch * seq)
    return out, loss, selected


# -- the kernels' path, one custom_vjp --------------------------------------------------


def _masked_flash_fwd(q, k, v, mask, scale, interpret: bool = False):
    """q [B, S, H, D], k/v [B, S, KV, D] -> (out [B, S, H, D], lse [B, H, S])."""
    batch, seq, q_heads, _ = q.shape
    flat = lambda t: t.reshape(batch, seq, -1)  # noqa: E731 — a head a column block, the kernels' form
    o, lse = _fa._fa_pallas_call(flat(q), flat(k), flat(v), scale, True, interpret=interpret, mask=mask,
                                 q_heads=q_heads, kv_group=q_heads // k.shape[2])
    return o.reshape(q.shape), lse.reshape(batch, q_heads, seq)


def _masked_flash_bwd(q, k, v, o, lse, g, mask, scale, interpret: bool = False):
    (batch, seq, q_heads, _), kv_heads = q.shape, k.shape[2]
    group = q_heads // kv_heads
    flat = lambda t: t.reshape(batch, seq, -1)  # noqa: E731
    dq, dk, dv = _fa._fa_bwd_pallas(
        flat(q), flat(k), flat(v), flat(o), lse.reshape(batch * q_heads, seq), flat(g), scale, True,
        interpret=interpret, mask=mask, q_heads=q_heads, kv_group=group)
    return (dq.reshape(q.shape), _fa.group_sum(dk, kv_heads, group).reshape(k.shape),
            _fa.group_sum(dv, kv_heads, group).reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _dsa_kernels(q, k, v, a, bt, w, topk: int, scale: float):
    return _dsa_kernels_fwd(q, k, v, a, bt, w, topk, scale)[0]


def _dsa_kernels_fwd(q, k, v, a, bt, w, topk, scale):
    # The scopes are parts of obs/spans.PARTS: they name the work for a profile.
    with jax.named_scope("dsa_select"):
        tau, cut, z = _select_pallas(a, bt, w, topk)
        # kept as [B, S]: a trailing axis of one would be stored a lane tile wide
        tau, cut = _kept(tau[..., 0], "tau"), _kept(cut[..., 0], "cut")
        mask = _mask_pallas(a, bt, w, tau[..., None], cut[..., None])
    with jax.named_scope("attn"):
        out, lse = _masked_flash_fwd(q, k, v, mask, scale)
        out, lse = _kept(out, "out"), _kept(lse, "lse")
    with jax.named_scope("dsa_index"):
        kl, da, dbt, dw = _index_loss_pallas(q, k, lse, a, bt, w, z, mask, scale)
        loss = _kept(jnp.sum(kl) / (kl.shape[0] * kl.shape[1]), "loss")
        da = _kept(da.astype(a.dtype), "da")
        dbt, dw = _kept(dbt.astype(bt.dtype), "db"), _kept(dw, "dw")
    with jax.named_scope("dsa_select"):
        selected = _kept(jnp.sum(mask, dtype=jnp.int32), "selected")
    return (out, loss, selected), (q, k, v, a, bt, w, tau, cut, out, lse, da, dbt, dw)


def _dsa_kernels_bwd(topk, scale, res, cotangents):
    q, k, v, a, bt, w, tau, cut, out, lse, da, dbt, dw = res
    g_out, g_loss, _ = cotangents
    # from the kept thresholds: scored once more, never selected again
    with jax.named_scope("dsa_select"):
        mask = _mask_pallas(a, bt, w, tau[..., None], cut[..., None])
    with jax.named_scope("attn"):
        dq, dk, dv = _masked_flash_bwd(q, k, v, out, lse, g_out, mask, scale)
    with jax.named_scope("dsa_index"):
        g_loss = g_loss.astype(jnp.float32)
        return (dq, dk, dv, (g_loss * da.astype(jnp.float32)).astype(a.dtype),
                (g_loss * dbt.astype(jnp.float32)).astype(bt.dtype), (g_loss * dw).astype(w.dtype))


_dsa_kernels.defvjp(_dsa_kernels_fwd, _dsa_kernels_bwd)


def selection(index_q, index_k, index_w, *, topk: int, mesh=None):
    """The selection alone, as the packed int8 mask the attention kernels
    read ([B, tiles, tile, tile]: `ops.attention._tri`) where the kernels run,
    else as a dense bool [B, S, S].  For tools that count what was selected."""
    bt, w = index_k.transpose(0, 2, 1), index_w.astype(jnp.float32)
    if kernels_apply(index_q.shape[2], _pallas_util.LANE, index_q.shape[-1], mesh):
        tau, cut, _ = _select_pallas(index_q, bt, w, topk)
        return _mask_pallas(index_q, bt, w, tau, cut)
    return selection_mask(index_scores(index_q, bt, w), topk)


def packed_lower_triangle(dense):
    """A dense [B, S, S] mask in the kernels' packed layout."""
    batch, seq, _ = dense.shape
    tile = _fa._block_sizes(seq, seq)[0]
    n = seq // tile
    blocks = dense.reshape(batch, n, tile, n, tile).transpose(0, 1, 3, 2, 4)
    rows, cols = zip(*[(i, j) for i in range(n) for j in range(i + 1)])
    return blocks[:, jnp.asarray(rows), jnp.asarray(cols)]


def sparse_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, index_q: jax.Array, index_k: jax.Array, index_w: jax.Array,
    *, topk: int, scale: float | None = None, mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q [B, S, H, D], k/v [B, S, KV, D] as `flash_attention`'s; index_q
    [B, J, S, Di] and index_k [B, S, Di] (both after RoPE), index_w [B, S, J] f32 with the
    indexer's scale factors in it -> (out [B, S, H, D], the index loss (a scalar: the
    mean over batch and positions of the KL term), the number of selected
    pairs (int32)).  See the module docstring for what is differentiated
    with respect to what."""
    batch, seq, q_heads, d = q.shape
    assert q_heads % k.shape[2] == 0, "query heads must be a multiple of kv heads"
    scale = scale if scale is not None else d ** -0.5
    with jax.named_scope("dsa_index"):
        bt = index_k.transpose(0, 2, 1)  # [B, Di, S]: a key tile is a lane-aligned slice
        w = index_w.astype(jnp.float32)
    if kernels_apply(seq, d, index_q.shape[-1], mesh):
        return _dsa_kernels(q, k, v, index_q, bt, w, topk, scale)
    return _dsa_xla(q, k, v, index_q, bt, w, topk, scale)
