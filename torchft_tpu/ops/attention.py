"""Flash attention: pallas TPU forward + backward kernels, position-major.

The kernels read q, k, v and the output's cotangent and write the output, dq,
dk and dv WHERE THE PROJECTIONS LEAVE THEM: [B, S, heads * d], head h the
lane-aligned column block [:, h * d:(h + 1) * d] — whole vector registers, and
in HBM's tiled layout the whole tiles that `heads` blocks of (rows, d) are.
Nothing turns to head-major and back around a call (117 MB a turn at 28 x
16,384 x 128); only the row statistics (lse, delta: 4 bytes a position and
head) and the VMEM scratch lead with the heads, and the XLA formulation turns
inside itself.

Design notes (MXU/HBM-minded):
  - forward streams K/V blocks through VMEM with the classic online-softmax
    accumulator, so HBM traffic is O(S*D) instead of materializing the
    O(S^2) score matrix;
  - the log-sum-exp per query row is saved, and the backward pass recomputes
    scores blockwise from (q, k, lse) — the flash recompute trade: extra
    FLOPs on the MXU instead of an O(S^2) residual in HBM.  On TPU the
    backward is ONE pallas kernel, `tpuft_fa_bwd_dkdv_dq` (q axis
    innermost): dk/dv accumulate in (block_k, d) f32 VMEM scratch, and dq
    in a third scratch that holds one head's WHOLE (seq_q, d_qk) f32 row
    while the kv blocks go by — the s/p/dp/ds tile work is computed once a
    tile (5 matrix products where two passes make 7), and the row is cast to
    the output's dtype inside the kernel, so dq never exists in f32 in HBM
    (2 MiB at seq 4,096 x 128, 16 MiB at 32,768 x 128 of a v5e's 128 MiB of
    VMEM; `vmem_limit_bytes` is sized from the shapes).  Only a row over
    `_DQ_ROW_VMEM_BUDGET` takes two passes (`tpuft_fa_bwd_dkdv`, then
    `tpuft_fa_bwd_dq` with kv innermost and the tile work done again): the
    choice reads the operands' shapes and nothing else.  The one-pass
    kernel's name contains `tpuft_fa_bwd_dkdv` on purpose: the benchmark
    books device time to attention by that substring, and a
    `tpuft_fa_bwd_dq` in a trace says the one-pass form did not engage;
  - a grid step carries H heads' tile, not one (`HEADS_PER_STEP`): the
    grid's outer axis is batch*heads / H — a batch entry's heads / H column
    blocks of H * d lanes one after the other (`_entry_and_block`) — a head
    is a static slice of its block (`_head`), every scratch leads with the
    heads, and the tile's arithmetic is a function of values, a head at a
    time: the backward's whole (`_bwd_tile`, `_bwd_step`), the forward's in
    two halves a head, walked with a skew of one so that a head's p v stands
    beside the next head's softmax tile (`_fwd_scores`, `_fwd_accumulate`,
    `_fwd_step`); H is read from the shapes.  A head's arithmetic is what
    one head a step computes, bit for bit;
  - grouped queries read their KV heads in place: k and v reach the kernels
    with their own heads, [B, S, kv_heads * d], in both directions, and
    no array of q_heads k or v heads exists in HBM.  A step's k / v
    block follows its H heads and the group along the columns (`_kv_spec`):
    the one KV head they share, the KV heads of the whole groups they are,
    or — H neither a divisor nor a multiple of the group — the adjacent KV
    heads they straddle, in one block placed by element, each head taking
    its own by a scalar index times the width (`_kv_head`).  H does not
    depend on the group.  dk and dv leave the kernel a query head each, and
    `group_sum` adds a group's column blocks in float32;
  - grid layout: the reduction axis innermost — TPU executes the innermost
    grid dimension sequentially, which is what makes the VMEM scratch
    accumulator legal.  A call that is causal over one sequence (``causal``
    or a mask, ``seq_q == seq_k``) walks the tiles that hold a visible pair
    and no others: its grid is (batch*heads / H, T), T = n (n + 1) / 2 for n
    square tiles a side, and two scalar-prefetched int32 tables give step
    t's (q tile, kv tile) to the kernel and to every index map (`_Walk`).
    The forward goes row by row (kv tiles 0 .. qi under q tile qi: start at
    the first, emit at the diagonal), the backward column by column (q
    tiles ki .. n - 1 over kv tile ki: dk/dv start at the diagonal and emit
    at the last row; the dq rows of q tile qi are complete, and cast into
    the output, at THEIR diagonal step).  A tile above the diagonal has no
    step, so nothing is issued or fetched for it.  Every other call keeps
    the rectangle (batch*heads / H, outer_blocks, inner_blocks): there is
    nothing to skip.  The choice reads ``causal``, the mask and the
    operands' shapes, and nothing else;
  - a **window** (``flash_attention(..., window=w)``: a query at t sees the
    keys s with ``0 <= t - s < w``) narrows the same walk to the band: a
    tile has a step when it holds a pair inside the window, so a row of the
    walk runs from `first_k` to `last_k` and a column from `first_q` to
    `last_q` — 2n - 1 tiles at w = the tile's side, where the triangle has
    n (n + 1) / 2.  Inside a tile ONE comparison keeps the pairs of the
    band, diagonal and lower edge alike (``t - s`` read as an unsigned
    number is under w exactly there).  The kernels are the same bodies
    under names of their own (`tpuft_swa_fwd`, `tpuft_swa_bwd_dkdv_dq`), so
    a trace tells the two kinds of layer apart.  A window that covers the
    sequence is no window: the causal call, bit for bit.

Query and key share one head width (``d_qk``), value and output another
(``d_v``): equal for plain multi-head attention, 192 / 128 for latent
attention (MLA).  Both have to be lane multiples; ``flash_attention`` pads a
``d_qk`` that is not (192 -> 256) with zero columns, which add nothing to a
score, and folds such heads into the batch — B * heads entries of ONE head, a
step adjacent entries (`_step_share`), the kernels' form of before PR 65: they
are assembled as [B, S, heads, d] arrays, which XLA lays S innermost, so a
head's [S, d] is a view and a row of heads 8 copies a layer (`flash_attention`).

The reference XLA attention runs off-TPU (CPU test mesh), under a multi-device mesh (a pallas call
has no partitioning rule), and for shapes the kernel does not tile (seq not divisible by the block size).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops import _pallas_util

_NEG_INF = -1e30
_LANE = 128  # TPU lane width: scratch row-stats are kept (block_q, 128)


def _use_pallas(seq_q: int, seq_k: int, d_v: int, mesh=None) -> bool:
    """Whether the kernels run (`flash_attention` pads the query's and key's
    width to a lane multiple, so only the value's has to be one)."""
    bq, bk = _block_sizes(seq_q, seq_k)
    return (
        seq_q % bq == 0
        and seq_k % bk == 0
        and d_v % _LANE == 0
        and _pallas_util.kernels_apply(mesh)
    )


def _block_sizes(seq_q: int, seq_k: int) -> Tuple[int, int]:
    # 512x512.  Fatter q blocks were measured slower at the flagship's 32
    # heads x 1,024 positions (PR 2), where causal masking can only skip
    # whole blocks: a 1024-row block straddling the diagonal computes 33%
    # more masked elements than two 512-row blocks.  As compiled at 28 x
    # 16,384 x 128 (`tools/fa_bwd_probe.py --bundles`; PERF.md section 6), a
    # forward tile is 1,139 bundles a head for 1,024 cycles of MXU work and the
    # backward tile ~2,500 for its five products' 2,560.  The band walk has the same tiles: under a window of 512, 256
    # x 256 tiles visit three kv tiles a row, two thirds of them inside the
    # band, at three times the grid steps — and are slower (64 x 16,384 x 128
    # on a v5e: 10.0 ms forward and 12.6 backward at 512, 15.7 and 18.7 at
    # 256; PERF.md section 6, PR 37).
    return min(512, seq_q), min(512, seq_k)


def _bd_visible(rows, cols, half: int, block: int):
    """What a query at ``rows`` sees of the keys at ``cols`` (int32, shapes that
    broadcast) in a block-diffusion stream of ``2 * half`` positions — the
    noised copy of ``half`` data tokens, then the clean copy — with blocks of
    ``block`` tokens, B(p) = (p mod half) // block: a noised query sees the
    noised keys of its own block and the clean keys of the blocks before it, a
    clean query the clean keys of its own block and before, and no noised key.
    Inside a block attention runs both ways.  As two comparisons: a clean key
    counts as its block, a noised one as ``half // block`` more; a query sees
    the keys up to ``reach`` (its block, less one where it is noised: clean
    keys alone lie there) and the one number ``own`` (its own noised block)."""
    shift = block.bit_length() - 1 if block & (block - 1) == 0 else None

    def block_of(p):
        return jax.lax.shift_right_logical(p, shift) if shift is not None else jax.lax.div(p, block)

    blocks = half // block
    row_noised, col_noised = rows < half, cols < half
    row_block = block_of(jnp.where(row_noised, rows, rows - half))
    key = block_of(jnp.where(col_noised, cols, cols - half)) + jnp.where(col_noised, blocks, 0)
    reach = row_block - row_noised.astype(jnp.int32)
    own = jnp.where(row_noised, row_block + blocks, -1)
    return (key <= reach) | (key == own)


def bd_pairs_walked(seq: int, block_length: int) -> int:
    """The visible pairs under the steps of the walk `flash_attention(..., block_length=block_length)` takes over a
    stream of ``seq`` positions (the tiles the kernels use; one tile where they do not apply, as the XLA form)."""
    bq, bk = _block_sizes(seq, seq)
    if seq % bq or seq % bk:
        bq = bk = seq
    return _Walk(True, seq, seq, bq, bk, block_length=block_length).pairs_seen()


def _bd_tiles(half: int, block: int, r0, rows: int, c0, cols: int):
    """bool, r0's shape: whether the tile of ``rows`` queries from r0 and
    ``cols`` keys from c0 (numpy int arrays) holds a pair `_bd_visible` keeps.
    A tile may straddle the halves: its noised and its clean rows and columns
    are taken apart, and a part's blocks are a range."""
    import numpy as np

    r1, c1 = r0 + rows - 1, c0 + cols - 1
    rows_noised, rows_clean, cols_noised, cols_clean = r0 < half, r1 >= half, c0 < half, c1 >= half
    noised_rows = (r0 // block, np.minimum(r1, half - 1) // block)         # first and last block, where there are any
    noised_cols = (c0 // block, np.minimum(c1, half - 1) // block)
    last_clean_row = (r1 - half) // block
    first_clean_col = (np.maximum(c0, half) - half) // block
    own = rows_noised & cols_noised & (noised_rows[0] <= noised_cols[1]) & (noised_cols[0] <= noised_rows[1])
    before = rows_noised & cols_clean & (first_clean_col < noised_rows[1])
    causal = rows_clean & cols_clean & (first_clean_col <= last_clean_row)
    return own | before | causal


@dataclasses.dataclass(frozen=True)
class _Walk:
    """The order in which a kernel's grid visits its (q tile, kv tile) pairs,
    and the one place that decides it: ``triangular`` where the call is
    causal over one sequence (``causal`` or masked, ``seq_q == seq_k``), so
    that only the tiles (qi, ki) with a visible pair, ``ki * block_k <= qi *
    block_q + block_q - 1``, have a grid step; else the whole rectangle.
    ``kv_major`` walks column by column (the q axis innermost) instead of
    row by row.  Tiles need not be square (ops/sparse_attention.py's are
    256 x 512).  ``window`` (triangular walks only) keeps of those tiles the
    ones with a pair ``0 <= t - s < window``: the band.  ``block_length``: the
    sequence is a block-diffusion stream (`_bd_visible`) and the tiles are the
    ones that hold a pair visible under ITS rule — no longer a triangle: the
    clean queries' tiles over the noised keys have no step, nor have the
    noised-noised tiles off the block diagonal."""

    causal: bool
    seq_q: int
    seq_k: int
    block_q: int
    block_k: int
    kv_major: bool = False
    window: Optional[int] = None
    block_length: Optional[int] = None

    def __post_init__(self) -> None:
        assert self.window is None or (self.triangular and self.window > 0), "a window is causal over one sequence"
        if self.block_length is not None:
            assert self.triangular and self.window is None and self.seq_q % (2 * self.block_length) == 0, (
                "a block-diffusion stream is one sequence of two halves of whole blocks")

    @property
    def half(self) -> int:
        """The data tokens of a block-diffusion stream: its noised copy's positions, and its clean copy's."""
        return self.seq_q // 2

    @property
    def triangular(self) -> bool:
        return self.causal and self.seq_q == self.seq_k

    @property
    def num_q(self) -> int:
        return self.seq_q // self.block_q

    @property
    def num_k(self) -> int:
        return self.seq_k // self.block_k

    @functools.cached_property
    def tables(self) -> tuple:
        """() for the rectangle, else (qi, ki): int32 [T], the visible
        tiles in the walk's order — the kernel's scalar-prefetch operands."""
        import numpy as np

        if not self.triangular:
            return ()
        qi, ki = np.indices((self.num_q, self.num_k), dtype=np.int32)
        if self.block_length is not None:
            visible = _bd_tiles(self.half, self.block_length, qi * self.block_q, self.block_q, ki * self.block_k, self.block_k)
        else:
            visible = ki * self.block_k <= qi * self.block_q + self.block_q - 1
        if self.window is not None:  # the nearest pair of the tile, (first row, last column), is inside
            visible &= qi * self.block_q - (ki * self.block_k + self.block_k - 1) < self.window
        if self.kv_major:
            return qi.T[visible.T], ki.T[visible.T]
        return qi[visible], ki[visible]

    def pairs_seen(self) -> int:
        """The (query, key) pairs a block-diffusion walk's steps cover that the rule keeps: over the tables' tiles,
        row by row, the tile's columns among the row's clean keys (the clean half up to its block's start, or its
        block's end for a clean row) and among its own block's noised keys.  The whole rule has
        ``half ** 2 + half * block_length`` of them: a walk short of a live tile reads less."""
        import numpy as np

        qi, ki = (np.asarray(t, np.int64) for t in self.tables)
        half, b = self.half, self.block_length
        rows = qi[:, None] * self.block_q + np.arange(self.block_q)
        c0 = ki[:, None] * self.block_k
        c1 = c0 + self.block_k
        noised = rows < half
        start = (rows - np.where(noised, 0, half)) // b * b  # the row's block's first token
        clean = np.minimum(c1, half + start + np.where(noised, 0, b)) - np.maximum(c0, half)
        own = np.where(noised, np.minimum(c1, start + b) - np.maximum(c0, start), 0)
        return int(np.maximum(clean, 0).sum() + np.maximum(own, 0).sum())

    def grid(self, outer: int) -> tuple:
        if self.triangular:
            return (outer, len(self.tables[0]))
        return (outer, self.num_k, self.num_q) if self.kv_major else (outer, self.num_q, self.num_k)

    def spec(self, block: tuple, at):
        """A `BlockSpec` whose block index is ``at(b, qi, ki)``, by tile,
        whatever this walk's grid."""
        from jax.experimental import pallas as pl

        if self.triangular:
            return pl.BlockSpec(block, lambda b, t, qi, ki: at(b, qi[t], ki[t]))
        return pl.BlockSpec(block, (lambda b, j, i: at(b, i, j)) if self.kv_major else at)

    def grid_spec(self, outer: int, in_specs, out_specs, scratch_shapes=()):
        from jax.experimental.pallas import tpu as pltpu

        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(self.tables), grid=self.grid(outer),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch_shapes)

    def semantics(self, outer: str) -> tuple:
        return (outer,) + ("arbitrary",) * (len(self.grid(1)) - 1)

    # inside a kernel, whose leading refs are the tables:

    def tile(self, refs):
        """(qi, ki) of this grid step, and the kernel's refs without the tables."""
        from jax.experimental import pallas as pl

        if self.triangular:
            t = pl.program_id(1)
            return refs[0][t], refs[1][t], refs[2:]
        if self.kv_major:
            return pl.program_id(2), pl.program_id(1), refs
        return pl.program_id(1), pl.program_id(2), refs

    def visible(self, qi, ki):
        """Whether the tile holds a visible pair: True, statically, in a
        triangular walk, which has no other steps."""
        if self.causal and not self.triangular:
            return ki * self.block_k <= qi * self.block_q + self.block_q - 1
        return True

    def last_k(self, qi):
        """The last kv tile a walk visits under q tile qi."""
        if self.block_length is not None:
            # the tile's last row's last key: a clean row's own block's end in the clean half, a noised row's
            # block's start there — or, in the stream's first block, which sees no clean key, its own block's end
            # (a tile that straddles the halves has both kinds of row: its last noised row may see further)
            b, half, r1 = self.block_length, self.half, qi * self.block_q + self.block_q - 1
            last_noised = jnp.minimum(r1, half - 1)
            noised = jnp.where(last_noised >= b, half + last_noised // b * b - 1, b - 1)
            clean = half + ((r1 - half) // b + 1) * b - 1
            straddles = qi * self.block_q < half
            return jnp.where(r1 >= half, jnp.where(straddles, jnp.maximum(noised, clean), clean), noised) // self.block_k
        return (qi * self.block_q + self.block_q - 1) // self.block_k if self.triangular else self.num_k - 1

    def first_q(self, ki):
        """The first q tile a walk visits over kv tile ki."""
        if self.block_length is not None:
            # a noised key's first query is its own block's first; a clean key's the noised block after its own
            # or, for the last block, its own block's first clean query
            b, half, c0 = self.block_length, self.half, ki * self.block_k
            after = (jnp.maximum(c0, half) - half) // b * b + b
            clean = jnp.where(after < half, after, after - b + half)
            straddles = c0 + self.block_k > half
            return jnp.where(c0 < half, jnp.where(straddles, jnp.minimum(c0 // b * b, clean), c0 // b * b), clean) // self.block_q
        return (ki * self.block_k) // self.block_q if self.triangular else 0

    def first_k(self, qi):
        """The first kv tile a walk visits under q tile qi: the one that
        holds the oldest key its first row still sees."""
        if self.block_length is not None:  # a noised row's own block's first noised key; a clean row's, the clean half's first
            r0 = qi * self.block_q
            return jnp.where(r0 < self.half, r0 // self.block_length * self.block_length, self.half) // self.block_k
        if self.window is None:
            return 0
        return jnp.maximum(qi * self.block_q - (self.window - 1), 0) // self.block_k

    def last_q(self, ki):
        """The last q tile a walk visits over kv tile ki: the one that holds
        the latest query that still sees its last key."""
        if self.block_length is not None:  # a clean key's: the stream's last; a noised key's: its own block's last
            c1 = ki * self.block_k + self.block_k - 1
            return jnp.where(c1 >= self.half, self.seq_q - 1, (c1 // self.block_length + 1) * self.block_length - 1) // self.block_q
        if self.window is None:
            return self.num_q - 1
        return jnp.minimum((ki * self.block_k + self.block_k - 1 + self.window - 1) // self.block_q, self.num_q - 1)

    def keep(self, qi, ki, shape):
        """[block_q, block_k] bool: the pairs of tile (qi, ki) a causal query
        sees.  Under a window ``t - s`` is read as unsigned, so that one
        comparison drops what lies above the diagonal (negative: a huge
        number) and what lies below the band.  A block-diffusion stream's
        rule (`_bd_visible`) is worked on one column of rows and one row of
        columns, and broadcast by the last two comparisons."""
        if self.block_length is not None:
            rows = qi * self.block_q + jax.lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0)
            cols = ki * self.block_k + jax.lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
            return _bd_visible(rows, cols, self.half, self.block_length)
        rows = qi * self.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = ki * self.block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        if self.window is None:
            return rows >= cols
        return jax.lax.bitcast_convert_type(rows - cols, jnp.uint32) < jnp.uint32(self.window)


# The most heads a grid step carries.  A grid step pays, beside its tile, for
# its blocks' DMAs (started and waited for once a step) and some 270 bundles of
# the pipeline's bookkeeping (at one head a step a forward tile of 28 x 16,384
# x 128 read 1.93 us on a v5e, the one-pass backward 2.71).  The heads of a
# call are independent, so a step takes H of them (`_step_share`), every scratch
# leading with the heads, and the tile's arithmetic runs over them a head at a
# time: the backward's whole (`_bwd_step`), the forward's skewed by one
# (`_fwd_step`).  On the chip (`tools/fa_bwd_probe.py`; PERF.md section 6, PRs
# 52, 62, 64, 65), us a tile at H = 7: forward 1.93 -> 0.92, backward 2.71 ->
# 2.31 at H = 4 under one `jax.vmap`, results bit for bit one head a step's.
# H is read from the shapes (`_heads_per_step`, `_bwd_heads_per_step`) alone.
HEADS_PER_STEP = 8
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _straddles(heads: int, kv_group: int) -> bool:
    """Whether a step of ``heads`` query heads neither lies inside one KV head's group of ``kv_group`` nor holds whole groups."""
    return kv_group % heads != 0 and heads % kv_group != 0


def _kv_span(heads: int, kv_group: int) -> int:
    """How many adjacent KV heads the widest of such steps touches: two for four heads of a group of seven."""
    return 1 + max((b * heads + heads - 1) // kv_group - b * heads // kv_group for b in range(kv_group))


def _kv_held(heads: int, kv_group: int) -> int:
    """How many KV heads a step's k or v block holds (`_kv_spec`)."""
    if heads % kv_group == 0:
        return heads // kv_group
    return 1 if kv_group % heads == 0 else _kv_span(heads, kv_group)


def _first_kv_head(c, q_heads: int, heads: int, kv_group: int, held: int):
    """The first of the ``held`` adjacent KV heads in the block of a
    straddling step, the c-th of its batch entry's: its first query head's,
    and no further than the entry's last.  (`lax.div` and `lax.min` on the
    scalars, which are not negative: one operation each where `//` and
    `jnp.minimum` trace to a dozen.)"""
    return jax.lax.min(jax.lax.div(c * heads, kv_group), q_heads // kv_group - held)


def _step_share(batch: int, q_heads: int, masked: bool) -> int:
    """What the heads of a grid step divide: a batch entry's heads, adjacent column blocks of its rows — or, where an
    entry has ONE head and so no second column block, the entries (a packed mask's tile is one entry's: one a step)."""
    return batch if q_heads == 1 and not masked else q_heads


def _step_heads(batch: int, q_heads: int, heads: int):
    """(entries a step's blocks span, the grid's outer steps, step -> (entry block, column block)) for ``heads`` heads
    a step: ``heads`` column blocks of one entry's rows, or ``heads`` adjacent entries of one head (`_step_share`)."""
    assert (batch if q_heads == 1 else q_heads) % heads == 0, f"{heads} heads a grid step do not divide {q_heads} (x {batch})"
    where = functools.partial(_entry_and_block, batch=batch, blocks=max(q_heads // heads, 1))
    return (heads if q_heads == 1 else 1), batch * q_heads // heads, where


def _entry_and_block(b, batch: int, blocks: int):
    """Grid step b of the outer axis as (batch entry, column block): an entry's ``blocks`` steps follow one another."""
    return (0, b) if batch == 1 else (jax.lax.div(b, blocks), jax.lax.rem(b, blocks))


def _heads_per_step(share: int, most: int = HEADS_PER_STEP) -> int:
    """The largest divisor of ``share`` not above ``most``: the heads of a
    grid step, of the batch entry's they have to lie inside (adjacent columns
    of its rows; under a packed mask its tile is one a step).  Grouped queries
    set no limit of their own: a step reads as many KV heads as its query
    heads belong to (`_kv_spec`)."""
    return max(h for h in range(1, min(share, most) + 1) if share % h == 0)


def _lanes(stat, width: int):
    """A lane-replicated row statistic, [.., rows, 128] with every lane of a
    row the same number, read ``width`` lanes wide: the same array side by
    side (a `concatenate`), cut where ``width`` is no lane multiple."""
    if width == _LANE:
        return stat
    stat = jnp.concatenate([stat] * -(-width // _LANE), axis=-1)
    return stat if stat.shape[-1] == width else stat[..., :width]


def _fwd_scores(q, k, m_prev, l_prev, keep, *, scale: float):
    """The first half of one head's forward tile as a function of values: the
    scores of q's (block_q, d) rows against one (block_k, d) k tile, and the
    online softmax's statistics moved on, ``(m, l) -> (m, alpha, l, p)`` with
    ``alpha`` the old accumulator's rescale and ``p`` [block_q, block_k] the
    unnormalised probabilities, float32.  ``keep`` [block_q, block_k] bool,
    or None where every pair is visible.  Matmuls run in the INPUT dtype with
    f32 accumulation: bf16 model activations hit the MXU at full rate (an f32
    x f32 matmul runs at a fraction of it); softmax statistics stay f32.

    The running max and sum are LANE-REPLICATED, [block_q, 128] in and out as
    the scratch holds them, and so is ``alpha``: a row's max and sum leave
    their lane reduction as one column and are broadcast once, into the
    statistic, and nothing is narrowed to a column to be broadcast again where
    the scores, the accumulator and the scratch want it (`_lanes`).  Every
    row's max, exp, sum and products are what [block_q, 1] statistics
    compute, bit for bit (tests/test_attention_walks.py keeps that tile; PR 62)."""
    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * scale  # [block_q, block_k] f32
    if keep is not None:
        # Unconditional mask.  A `lax.cond` a block in its place read ~3 ms a
        # step slower at the flagship's 32 heads x 1,024 positions (two tiles
        # a side, PR 2) and has not been read at a longer shape; PR 34 bounds
        # what masking only the diagonal's tiles could save at 0.13 us of a
        # 16,384-position tile's 1.9.
        s = jnp.where(keep, s, _NEG_INF)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))    # [block_q, 128]
    p = jnp.exp(s - _lanes(m_cur, s.shape[-1]))                        # [block_q, block_k]
    alpha = jnp.exp(m_prev - m_cur)                                    # rescale old accumulator
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    return m_cur, alpha, l_new, p


def _fwd_accumulate(acc, alpha, p, v):
    """The second half of that tile: the (block_q, d_v) float32 accumulator
    rescaled and ``p``, cast to v's dtype, times the (block_k, d_v) v tile
    added.  Nothing hangs from it but the accumulator's store, so a scheduler
    that sees it alone lets it sink: `_fwd_step` says what it stands beside."""
    return acc * _lanes(alpha, acc.shape[-1]) + jax.lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)


def _bwd_tile(q, k, v, do, lse, delta, keep, *, scale: float, dkdv: bool, dq: bool):
    """One head's backward tile as a function of values: recomputes p and ds
    of the (q block, kv block) tile and returns the products asked for —
    ``(p^T do, ds^T q)`` for dv and dk, ``ds k`` for dq.  ``lse`` and
    ``delta`` are the q block's row statistics as (1, block_q) rows.  Matmul
    operands stay in the input dtype (bf16 on the model path = full MXU
    rate); probabilities and statistics are f32, ds is cast to the input
    dtype for the downstream MXU products."""
    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * scale  # [block_q, block_k] f32
    p = jnp.exp(s - jnp.transpose(lse, (1, 0)))
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)       # [block_q, block_k]
    ds = (p * (dp - jnp.transpose(delta, (1, 0))) * scale).astype(q.dtype)
    out = ()
    if dkdv:
        out += (jax.lax.dot_general(p.astype(do.dtype), do, _TN, preferred_element_type=jnp.float32),  # [block_k, d_v]
                jax.lax.dot_general(ds, q, _TN, preferred_element_type=jnp.float32))                  # [block_k, d]
    if dq:
        out += (jax.lax.dot(ds, k, preferred_element_type=jnp.float32),)                              # [block_q, d]
    return out


def _keep(walk: _Walk, qi, ki, mask_ref):
    """[block_q, block_k] bool, what the queries of tile (qi, ki) see, for
    all the heads of a step: the packed mask's tile (one a batch entry, read
    and converted once), else the causal triangle's or the band's; None where
    every pair is visible."""
    if mask_ref is not None:
        return mask_ref[0, 0].astype(jnp.int32) != 0
    if walk.causal:
        return walk.keep(qi, ki, (walk.block_q, walk.block_k))
    return None


@functools.lru_cache(maxsize=None)
def _traced_once(fn, **static):
    """``fn`` with its static arguments, jitted, and one object for them: a
    kernel's body that calls it once a head holds one trace of it a shape, and
    so does every later trace of the body (a layer's forward, its
    recomputation, its transpose: PR 50 read +9 s of set-up without)."""
    return jax.jit(functools.partial(fn, **static))


def _head(ref, h: int, heads: int):
    """Head h of a step's block [1, rows, heads * width]: its column block, a static lane-aligned
    slice (where an entry is one head, the block is [heads, rows, width] and the head its entry)."""
    cols = heads // ref.shape[0]
    width = ref.shape[2] // cols
    return ref[h // cols, :, h % cols * width:(h % cols + 1) * width]


def _store_heads(ref, x, rows=slice(None)) -> None:
    """x [heads, rows, width] into the block ref, cast to its type, a head where `_head` reads it."""
    cols, width = x.shape[0] // ref.shape[0], x.shape[2]
    for h in range(x.shape[0]):
        ref[h // cols, rows, h % cols * width:(h % cols + 1) * width] = x[h].astype(ref.dtype)


def _kv_head(ref, h: int, heads: int, kv_group: int, q_heads: int):
    """Head h's k or v tile, of a step's ``heads``, out of the step's block (`_kv_spec`: [1, block_k, held * width]):
    the block's own head h, the head of h's group, the one head the step shares, or, where the step straddles
    groups, the KV head of h's group counted from the block's first along the lanes."""
    from jax.experimental import pallas as pl

    held = _kv_held(heads, kv_group)
    if not _straddles(heads, kv_group):
        return _head(ref, h * held // heads, held)
    width = ref.shape[2] // held
    c = jax.lax.rem(pl.program_id(0), q_heads // heads)
    at = jax.lax.div(c * heads + h, kv_group) - _first_kv_head(c, q_heads, heads, kv_group, held)
    return ref[0, :, pl.ds(pl.multiple_of(at * width, width), width)]


def _fwd_step(keep, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *, scale: float, kv_group: int, q_heads: int):
    """A grid step's forward tiles, the heads walked with a SKEW OF ONE: head
    0's scores half; then, for h = 1 .. H - 1, head h's scores half and head
    h - 1's accumulate half; then head H - 1's accumulate half.  A head reads
    its own column block of every block and its slice of every scratch;
    ``keep`` is the step's.  The heads share nothing else, so every head's
    arithmetic is what all heads under one `jax.vmap` computed (and one head a
    step computes), bit for bit: only their order across heads is written down
    (tests/test_attention_walks.py keeps the step under `jax.vmap`).

    Why: nothing hangs from a head's p v but the accumulator's store, so as
    one batched function every p v sank to the end of the step, alone in the
    MXUs after the last exponential.  A head at a time, a head's p v stands
    beside the next head's softmax tile: 1,139 bundles a head at 28 x 16,384 x
    128 where all heads at once were 1,322 (PERF.md section 6, PR 64)."""
    heads = m_scr.shape[0]
    scores, accumulate = _traced_once(_fwd_scores, scale=scale), _traced_once(_fwd_accumulate)
    kv_head = functools.partial(_kv_head, heads=heads, kv_group=kv_group, q_heads=q_heads)
    before = None  # the head before's rescale and probabilities, on their way to its p v
    for h in range(heads):
        m_scr[h], alpha, l_scr[h], p = scores(_head(q_ref, h, heads), kv_head(k_ref, h), m_scr[h], l_scr[h], keep)
        if before is not None:
            acc_scr[h - 1] = accumulate(acc_scr[h - 1], *before, kv_head(v_ref, h - 1))
        before = alpha, p
    acc_scr[heads - 1] = accumulate(acc_scr[heads - 1], *before, kv_head(v_ref, heads - 1))


def _bwd_step(keep, qi, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *, walk: _Walk, scale: float, kv_group: int,
              q_heads: int, dkdv: bool, dq: bool) -> list:
    """A grid step's backward tiles, `_bwd_tile`'s products a head: a head reads its own column block of q's and
    do's blocks, its KV head's of k's and v's (`_kv_head`) and its row of the statistics; ``keep`` is the step's.  A
    head at a time, as the forward's: under one `jax.vmap` the heads' slices were stacked first, a copy; written out,
    32 x 4,096 x 128 schedules at 2,500 bundles a head, the MXUs' slots 94% taken, where the batched form's was 2,780
    at 81% (PERF.md section 6, PR 65).  Bit for bit what one head a step computes."""
    from jax.experimental import pallas as pl

    heads = lse_ref.shape[0]
    tile = _traced_once(_bwd_tile, scale=scale, dkdv=dkdv, dq=dq)
    # The q block's part of the row statistics: they enter the kernels as compact [.., 1, N] rows (4 KB a head
    # and visit) instead of a lane-padded [.., N, 128] layout (260 KB); the tile turns its part into a column.
    rows = pl.ds(qi * walk.block_q, walk.block_q)
    if q_ref.shape[0] == heads > 1:  # entries of one head: the blocks ARE the heads stacked, so one batched product a
        # step's heads, no copy — at 256 / 128 wide its MXU-bound schedule is the shorter (14,326 bundles against 14,872)
        tiles = jax.vmap(tile, in_axes=(0,) * 6 + (None,))(q_ref[...], k_ref[...], v_ref[...], do_ref[...], lse_ref[:, :, rows],
                                                           delta_ref[:, :, rows], keep)
        return [tuple(t[h] for t in tiles) for h in range(heads)]
    return [tile(_head(q_ref, h, heads), _kv_head(k_ref, h, heads, kv_group, q_heads), _kv_head(v_ref, h, heads, kv_group, q_heads),
                 _head(do_ref, h, heads), lse_ref[h, :, rows], delta_ref[h, :, rows], keep) for h in range(heads)]


def _fa_kernel(*refs, walk: _Walk, scale: float, q_heads: int, masked: bool = False, kv_group: int = 1):
    """A grid step: H heads' tile (qi, ki).  q's, k's, v's and the output's blocks are position-major, [1, rows, H *
    width] with a head a lane-aligned column block (k and v the step's KV heads, `_kv_spec`); the statistics' block and
    every scratch lead with the heads.  ``masked``: a fourth operand, an int8 (block_q, block_k) tile of a per-pair mask
    (ops/sparse_attention.py), decides what a query sees in place of the causal triangle; ``causal`` still says which
    tiles are empty."""
    from jax.experimental import pallas as pl

    qi, ki, (q_ref, k_ref, v_ref, *rest) = walk.tile(refs)
    mask_ref = rest[0] if masked else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[1:] if masked else rest

    @pl.when(ki == walk.first_k(qi))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal, on a rectangle: kv blocks strictly above the diagonal
    # contribute nothing.
    run = walk.visible(qi, ki)

    @pl.when(run)
    def _step():
        _fwd_step(_keep(walk, qi, ki, mask_ref), q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, scale=scale, kv_group=kv_group,
                  q_heads=q_heads)

    @pl.when(ki == walk.last_k(qi))
    def _emit():
        l = l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        _store_heads(o_ref, acc_scr[...] / _lanes(safe_l, acc_scr.shape[-1]))
        # lse leaves as the backward reads it, a [1, block_q] row a head (lane-padded: 128 times the bytes, and a copy
        # after) — lane-padded only where `_fa_pallas_call` asks for that
        lse = m_scr[...] + jnp.log(safe_l)
        if lse_ref.shape[1] > 1:
            lse_ref[...] = lse
        else:
            for h in range(lse.shape[0]):
                lse_ref[h] = jnp.transpose(lse[h])[:1]


def _tri(i, j):
    """Where tile (i, j), j <= i, of a lower triangle lies when the tiles are
    stored row by row (a triangular walk visits no other tile)."""
    return i * (i + 1) // 2 + j


def _kv_spec(spec, where, q_heads: int, heads: int, kv_group: int, block_k: int, width: int):
    """k's or v's spec, out of [B, S, kv_heads * width], for a step of ``heads`` of a batch entry's ``q_heads``: along
    the columns the KV heads of the whole groups the step holds, the one KV head its heads share, or, where the step
    straddles groups, the `_kv_span` adjacent KV heads from its first head's on, placed by element (and no further than
    the entry's last).  ``where(b)`` is the step's (batch entry, column block)."""
    from jax.experimental import pallas as pl

    held = _kv_held(heads, kv_group)
    if q_heads == 1:  # an entry a head (and a KV head a head): the step's adjacent entries
        return spec((heads, block_k, width), lambda b, i, j: (b, j, 0))
    if heads % kv_group == 0:
        return spec((1, block_k, held * width), lambda b, i, j: (where(b)[0], j, where(b)[1]))
    if kv_group % heads == 0:
        return spec((1, block_k, width), lambda b, i, j: (where(b)[0], j, jax.lax.div(where(b)[1] * heads, kv_group)))
    return spec((pl.Element(1), pl.Element(block_k), pl.Element(held * width)),
                lambda b, i, j: (where(b)[0], j * block_k, _first_kv_head(where(b)[1], q_heads, heads, kv_group, held) * width))


def _fa_pallas_call(q, k, v, scale: float, causal: bool, interpret: bool = False, mask=None, *, q_heads: int,
                    kv_group: int = 1, window: Optional[int] = None, heads_per_step: Optional[int] = None,
                    block_length: Optional[int] = None):
    """q [B, S, q_heads * d], k [B, S, kv_heads * d], v [B, S, kv_heads * dv], as the projections leave them ->
    (out [B, S, q_heads * dv], lse [B * q_heads, S] float32).  ``mask``: int8 [B, tiles, block_q, block_k], the
    (block_q, block_k) tiles of a per-pair mask's lower triangle row by row (`_tri`), shared by a batch entry's heads;
    None for the causal triangle.  ``kv_group``: k and v hold one head for every ``kv_group`` of q's (read in place).
    ``window``: the band walk, under the name `tpuft_swa_fwd`.  ``block_length``: the sequence is a block-diffusion
    stream and the walk its live tiles' (`_Walk`), under the name `tpuft_bd_fwd`.  ``heads_per_step`` is the probe's and
    the tests': the program reads H from the shapes, the largest divisor of `_step_share` not above `HEADS_PER_STEP`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, seq_k = q.shape[0], q.shape[1], k.shape[1]
    d, dv = q.shape[2] // q_heads, v.shape[2] * kv_group // q_heads  # d: query and key; dv: value and output
    block_q, block_k = _block_sizes(seq_q, seq_k)
    assert mask is None or (seq_q == seq_k and window is None and mask.shape[0] == batch), "a packed mask is one sequence's lower triangle"
    walk = _Walk(causal or mask is not None, seq_q, seq_k, block_q, block_k, window=window, block_length=block_length)
    spec = walk.spec
    heads = heads_per_step or _heads_per_step(_step_share(batch, q_heads, mask is not None))
    across, steps, where = _step_heads(batch, q_heads, heads)
    by_q = lambda b, i, j: (where(b)[0], i, where(b)[1])  # noqa: E731 — a step's heads' columns of q tile i
    operands, mask_specs = (q, k, v), []
    if mask is not None:
        assert across == 1, "a packed mask's tile is one batch entry's"
        operands += (mask,)
        mask_specs = [spec((1, 1, block_q, block_k), lambda b, i, j: (where(b)[0], _tri(i, j), 0, 0))]
    out, lse = pl.pallas_call(
        functools.partial(_fa_kernel, walk=walk, scale=scale, q_heads=q_heads, masked=mask is not None, kv_group=kv_group),
        out_shape=(
            jax.ShapeDtypeStruct((batch, seq_q, q_heads * dv), q.dtype),
            jax.ShapeDtypeStruct((batch * q_heads,) + ((1, seq_q) if across == 1 else (seq_q, _LANE)), jnp.float32),
        ),
        grid_spec=walk.grid_spec(
            steps,
            in_specs=[
                spec((across, block_q, heads // across * d), by_q),
                _kv_spec(spec, where, q_heads, heads, kv_group, block_k, d),
                _kv_spec(spec, where, q_heads, heads, kv_group, block_k, dv),
            ] + mask_specs,
            out_specs=(
                spec((across, block_q, heads // across * dv), by_q),
                spec((heads, 1, block_q), lambda b, i, j: (b, 0, i)) if across == 1 else
                spec((heads, block_q, _LANE), lambda b, i, j: (b, i, 0)),  # `flash_attention` says why
            ),
            scratch_shapes=[
                pltpu.VMEM((heads, block_q, _LANE), jnp.float32),  # running max
                pltpu.VMEM((heads, block_q, _LANE), jnp.float32),  # running sum
                pltpu.VMEM((heads, block_q, dv), jnp.float32),     # output accumulator
            ],
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(heads * _TILE_VMEM_BYTES, _VMEM_BUDGET)),
        interpret=interpret,
        name="tpuft_dsa_attn_fwd" if mask is not None else _family(window, block_length) + "_fwd",
    )(*walk.tables, *operands)
    return out, lse[:, 0] if across == 1 else lse[:, :, 0]


# The one-pass backward keeps one head's whole dq row, (seq_q, d_qk) f32, in a
# VMEM scratch.  A row above this many bytes (65,536 positions at 128 wide is
# the longest that fits) takes the two-pass form instead; the choice reads
# the operands' shapes and nothing else.  A v5e has 128 MiB of VMEM: the row,
# its double-buffered bf16 output block (as much again) and the tiles are
# 80 MiB at the budget, compiled and run at [2, 65536, 128].
_DQ_ROW_VMEM_BUDGET = 32 * 2**20
# What a head's tiles take beside a resident row: the double-buffered
# operand blocks, the dk/dv accumulators and the (block_q, block_k) f32
# temporaries — the compiler's default scoped limit, under which the
# kernels of one head a step without a row ran.
_TILE_VMEM_BYTES = 16 * 2**20
# What a step's heads may ask for together — their rows, the rows' output
# blocks and their tiles: a v5e's VMEM.  Compiled and run at the whole of it
# (PERF.md section 6, PR 52): four heads of 16,384 x 128 and of 8,192 x 256.
_VMEM_BUDGET = 128 * 2**20


def _family(window: Optional[int], block_length: Optional[int]) -> str:
    """The kernels' name by the walk, so that a trace tells the layers' kinds apart."""
    return "tpuft_bd" if block_length is not None else "tpuft_fa" if window is None else "tpuft_swa"


def _dq_row_resident(seq_q: int, d: int) -> bool:
    """Whether the backward is the one-pass kernel: the f32 dq row of one
    head fits the VMEM budget."""
    return seq_q * d * 4 <= _DQ_ROW_VMEM_BUDGET


def _row_vmem_bytes(seq_q: int, d: int, itemsize: int) -> int:
    """A head's dq row in the one-pass backward: f32 in scratch and the
    output's block, double-buffered."""
    return seq_q * d * (4 + 2 * itemsize)


def _bwd_heads_per_step(share: int, row_bytes: int) -> int:
    """The heads of a backward grid step: the largest divisor of ``share``
    not above `HEADS_PER_STEP` whose rows (``row_bytes`` a head: 0 in the
    two-pass form) and tiles fit `_VMEM_BUDGET`; one head where two do not."""
    most = max(1, min(HEADS_PER_STEP, _VMEM_BUDGET // (row_bytes + _TILE_VMEM_BYTES)))
    return _heads_per_step(share, most)


def heads_indicator(heads: int, width: int):
    """float32 [heads, heads * width], 1 where a column is the head's: a product with it sums a head's
    columns, or spreads a number a head over them, in [.., heads * width] as it lies (with the heads
    an axis of their own, [.., heads, width], XLA re-tiles the array whole, S innermost)."""
    return jnp.repeat(jnp.eye(heads, dtype=jnp.float32), width, axis=1)  # a constant of the program


def _row_delta(g, o, q_heads: int):
    """delta = rowsum(g * o) a head, [B, S, q_heads * dv] twice -> [B *
    q_heads, 1, S] float32, as a product with `heads_indicator`, which gives
    its result S innermost (a sum over [.., q_heads, dv] has g * o re-tiled
    whole in float32 first: 234 MB a layer at 28 x 16,384 x 128).  Exact term
    by term: two bfloat16s' product has 16 significant bits, which the
    three-pass form's two pieces hold."""
    precision = jax.lax.Precision.HIGH if g.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    delta = jnp.einsum("hc,bsc->bhs", heads_indicator(q_heads, g.shape[2] // q_heads),
                       g.astype(jnp.float32) * o.astype(jnp.float32), precision=precision)
    return delta.reshape(-1, 1, g.shape[1])


def _fa_bwd_dkdv_kernel(*refs, walk: _Walk, scale: float, with_dq: bool, q_heads: int, masked: bool = False,
                        kv_group: int = 1):
    """Flash backward with the q axis innermost (a ``kv_major`` walk), H
    heads a grid step: dk/dv accumulate in VMEM scratch across the q tiles
    over one kv tile.  With ``with_dq`` (the one-pass form) a third scratch
    holds the heads' whole dq rows in f32: the tile (ki, qi) adds ``ds @ k``
    into its rows qi, and the step of the LAST kv tile that q tile qi sees
    (its diagonal step in a triangular walk, the last kv block's on a
    rectangle) casts those rows into the dq output, whose one block a step's
    heads stays in VMEM until they are done — the s/p/dp/ds tile work is
    computed once, and dq never exists in f32 outside VMEM."""
    from jax.experimental import pallas as pl

    qi, ki, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest) = walk.tile(refs)
    block_q = walk.block_q
    mask_ref = rest[0] if masked else None  # as `_fa_kernel`'s
    dk_ref, dv_ref, *rest = rest[1:] if masked else rest
    if with_dq:
        dq_ref, dk_scr, dv_scr, dq_scr = rest
        q_rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
    else:
        dk_scr, dv_scr = rest

    @pl.when(qi == walk.first_q(ki))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # Causal, on a rectangle: a q block strictly above this kv block's
    # diagonal contributes nothing.
    run = walk.visible(qi, ki)

    @pl.when(run)
    def _step():
        tiles = _bwd_step(_keep(walk, qi, ki, mask_ref), qi, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, walk=walk, scale=scale,
                          kv_group=kv_group, q_heads=q_heads, dkdv=True, dq=with_dq)
        for h, (dv_tile, dk_tile, *_) in enumerate(tiles):
            dv_scr[h] += dv_tile                    # p^T @ do: [block_k, d_v]
            dk_scr[h] += dk_tile                    # ds^T @ q: [block_k, d]
        if with_dq:
            # Every q block runs against its first kv block (block 0 without
            # a window), causal or not, so the first visit assigns and the row
            # is never zeroed.
            @pl.when(ki == walk.first_k(qi))
            def _first():
                for h, tile in enumerate(tiles):
                    dq_scr[h, q_rows, :] = tile[2]  # ds @ k: [block_q, d]

            @pl.when(ki != walk.first_k(qi))
            def _add():
                for h, tile in enumerate(tiles):
                    dq_scr[h, q_rows, :] += tile[2]

    @pl.when(qi == walk.last_q(ki))
    def _emit():
        _store_heads(dk_ref, dk_scr[...])
        _store_heads(dv_ref, dv_scr[...])

    if with_dq:
        # Rows qi are complete once the last kv tile they see has had its
        # turn at them: this step, after its own `ds @ k` above, where the
        # walk is triangular (kv tile qi is the first step of its column);
        # on a rectangle a turn the causal rule may have skipped.
        @pl.when(ki == walk.last_k(qi))
        def _emit_dq():
            _store_heads(dq_ref, dq_scr[:, q_rows, :], q_rows)


def _fa_bwd_dq_kernel(*refs, walk: _Walk, scale: float, q_heads: int, kv_group: int = 1):
    """dq-only second pass, kv axis innermost, for a dq row too long to
    stay in VMEM: dq accumulates one (block_q, d) f32 block a head at a
    time, so memory stays O(block) whatever the length (at the price of
    recomputing p/ds once more)."""
    from jax.experimental import pallas as pl

    qi, ki, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr) = walk.tile(refs)

    @pl.when(ki == walk.first_k(qi))
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(walk.visible(qi, ki))
    def _step():
        tiles = _bwd_step(_keep(walk, qi, ki, None), qi, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, walk=walk, scale=scale,
                          kv_group=kv_group, q_heads=q_heads, dkdv=False, dq=True)
        for h, (dq_tile,) in enumerate(tiles):
            dq_scr[h] += dq_tile

    @pl.when(ki == walk.last_k(qi))
    def _emit():
        _store_heads(dq_ref, dq_scr[...])


def _fa_bwd_pallas(q, k, v, o, lse, g, scale: float, causal: bool, interpret: bool = False, mask=None, *,
                   q_heads: int, kv_group: int = 1, window: Optional[int] = None, heads_per_step: Optional[int] = None,
                   block_length: Optional[int] = None):
    """Flash backward on TPU; q, k, v as `_fa_pallas_call`'s and o, g [B, S, q_heads * dv] as it gives them, lse
    [B * q_heads, S] f32 -> dq in q's form, dk [B, S, q_heads * d] and dv [B, S, q_heads * dv].  One kernel
    (`tpuft_fa_bwd_dkdv_dq`) where one head's f32 dq row fits `_DQ_ROW_VMEM_BUDGET`, else `tpuft_fa_bwd_dkdv` and then
    `tpuft_fa_bwd_dq`.  ``mask``: the masked backward is the one-pass kernel only, under the name
    `tpuft_dsa_attn_bwd_dkdv_dq`; with ``kv_group`` k and v are read in place and dk, dv come out a query head each,
    for the caller to sum over a group (`group_sum`).  ``window``: the band walk, the same kernels as
    `tpuft_swa_bwd_dkdv_dq` (`_bwd_dkdv`, `_bwd_dq`); ``block_length``: a block-diffusion stream's, as `tpuft_bd_bwd_*`.

    A grid step carries H heads (``heads_per_step`` is the probe's and the
    tests'): the largest divisor of what `_fa_pallas_call` divides, not above
    `HEADS_PER_STEP`, such that H heads' dq rows (`_row_vmem_bytes`; none in the
    two-pass form) and H times `_TILE_VMEM_BYTES` fit `_VMEM_BUDGET`: four at
    4,096 x 128, two at 32,768 x 128, one at 65,536 x 128."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, seq_k = q.shape[0], q.shape[1], k.shape[1]
    d, d_v = q.shape[2] // q_heads, g.shape[2] // q_heads
    block_q, block_k = _block_sizes(seq_q, seq_k)
    causal = causal or mask is not None
    family = _family(window, block_length)
    one_pass = _dq_row_resident(seq_q, d)
    row_bytes = _row_vmem_bytes(seq_q, d, q.dtype.itemsize) if one_pass else 0
    heads = heads_per_step or _bwd_heads_per_step(_step_share(batch, q_heads, mask is not None), row_bytes)
    across, steps, where = _step_heads(batch, q_heads, heads)
    cols = heads // across
    # Row stats as [B * H, 1, S]: whole row per visit (4 KB).  delta_i =
    # rowsum(do * o) is O(S*D) and computed once here instead of per tile.
    lse = lse[:, None, :]
    delta = _row_delta(g, o, q_heads)

    def specs(walk):
        """The six operands' specs and dk's and dv's, by tile, for a walk."""
        spec = walk.spec
        row = spec((heads, 1, seq_q), lambda b, i, j: (b, 0, 0))
        by_q = lambda b, i, j: (where(b)[0], i, where(b)[1])  # noqa: E731 — a step's heads' columns of q tile i
        by_k = lambda b, i, j: (where(b)[0], j, where(b)[1])  # noqa: E731
        return [
            spec((across, block_q, cols * d), by_q),
            _kv_spec(spec, where, q_heads, heads, kv_group, block_k, d),
            _kv_spec(spec, where, q_heads, heads, kv_group, block_k, d_v),
            spec((across, block_q, cols * d_v), by_q),
            row, row,
        ], [spec((across, block_k, cols * d), by_k), spec((across, block_k, cols * d_v), by_k)]

    walk = _Walk(causal, seq_q, seq_k, block_q, block_k, kv_major=True, window=window, block_length=block_length)
    in_specs, out_specs = specs(walk)
    operands = (q, k, v, g, lse, delta)
    if mask is not None:
        assert one_pass and seq_q == seq_k and window is None and mask.shape[0] == batch and across == 1, \
            "the masked backward keeps one sequence's dq row in VMEM, its mask's tile one batch entry's"
        operands += (mask,)
        in_specs.append(walk.spec((1, 1, block_q, block_k), lambda b, i, j: (where(b)[0], _tri(i, j), 0, 0)))
    out_shape = [
        jax.ShapeDtypeStruct((batch, seq_k, q_heads * d), k.dtype),
        jax.ShapeDtypeStruct((batch, seq_k, q_heads * d_v), v.dtype),
    ]
    scratch = [
        pltpu.VMEM((heads, block_k, d), jnp.float32),
        pltpu.VMEM((heads, block_k, d_v), jnp.float32),
    ]
    if one_pass:
        # dq's block is the heads' whole rows and ignores the tile: it leaves
        # VMEM once, when the step's heads are done.
        out_shape.append(jax.ShapeDtypeStruct(q.shape, q.dtype))
        out_specs.append(walk.spec((across, seq_q, cols * d), lambda b, i, j: (where(b)[0], 0, where(b)[1])))
        scratch.append(pltpu.VMEM((heads, seq_q, d), jnp.float32))
    vmem_limit = heads * (row_bytes + _TILE_VMEM_BYTES)
    outs = pl.pallas_call(
        functools.partial(
            _fa_bwd_dkdv_kernel, walk=walk, scale=scale, with_dq=one_pass, q_heads=q_heads, masked=mask is not None,
            kv_group=kv_group,
        ),
        out_shape=tuple(out_shape),
        grid_spec=walk.grid_spec(steps, in_specs, tuple(out_specs), scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=walk.semantics("parallel"),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
        # The benchmark books device time to attention by these names'
        # substrings: the one-pass name has to contain `tpuft_fa_bwd_dkdv`.
        name=("tpuft_dsa_attn_bwd_dkdv_dq" if mask is not None
              else family + "_bwd_dkdv_dq" if one_pass else family + "_bwd_dkdv"),
    )(*walk.tables, *operands)
    if one_pass:
        dk, dv, dq = outs
        return dq, dk, dv
    dk, dv = outs

    # Second pass for a row over the budget: dq with the kv axis innermost.
    walk = _Walk(causal, seq_q, seq_k, block_q, block_k, window=window, block_length=block_length)
    in_specs, _ = specs(walk)
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, walk=walk, scale=scale, q_heads=q_heads, kv_group=kv_group),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=walk.grid_spec(
            steps, in_specs, in_specs[0], [pltpu.VMEM((heads, block_q, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name=family + "_bwd_dq",
    )(*walk.tables, q, k, v, g, lse, delta)
    return dq, dk, dv


def _visible(seq_q: int, seq_k: int, window: Optional[int], block_length: Optional[int] = None):
    """[seq_q, seq_k] bool: what a causal query sees, whole — or a block-diffusion stream's (`_bd_visible`)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 1)
    if block_length is not None:
        return _bd_visible(rows, cols, seq_q // 2, block_length)
    if window is None:
        return rows >= cols
    return (rows >= cols) & (rows - cols < window)


def _fa_reference(q, k, v, scale: float, causal: bool, window: Optional[int] = None, block_length: Optional[int] = None):
    """Stable XLA attention returning (out, lse); q/k: [BH, S, D], v: [BH, S, Dv]."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = jnp.where(_visible(s.shape[-2], s.shape[-1], window, block_length), s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bqk,bkd->bqd", (p / l).astype(v.dtype), v)
    lse = (m + jnp.log(l))[..., 0]
    return o.astype(q.dtype), lse


def _to_heads(x, heads: int):
    """[B, S, heads * d] -> [B * heads, S, d]: the XLA formulation's form."""
    return x.reshape(x.shape[:2] + (heads, -1)).transpose(0, 2, 1, 3).reshape(x.shape[0] * heads, x.shape[1], -1)


def _from_heads(x, heads: int):
    """[B * heads, S, d] -> [B, S, heads * d]."""
    return x.reshape((-1, heads) + x.shape[1:]).transpose(0, 2, 1, 3).reshape(x.shape[0] // heads, x.shape[1], -1)


def _fa_forward(q, k, v, heads: Tuple[int, int], scale: float, causal: bool, kernel: bool, window: Optional[int],
                block_length: Optional[int] = None):
    """(out, lse [B * q_heads, S]) of q, k, v [B, S, heads * width], ``heads``
    the (query, KV) heads: the kernels read them as they are, the XLA
    formulation turns to head-major and back inside itself."""
    if kernel:
        return _fa_pallas_call(q, k, v, scale, causal, window=window, q_heads=heads[0], kv_group=heads[0] // heads[1],
                               block_length=block_length)
    o, lse = _fa_reference(_to_heads(q, heads[0]), _to_heads(k, heads[1]), _to_heads(v, heads[1]), scale, causal, window,
                           block_length)
    return _from_heads(o, heads[0]), lse


def group_sum(t, heads: int, group: int):
    """[B, S, heads * group * width], a query head each, -> [B, S, heads *
    width]: a KV head's gradient is its query heads' summed, in float32 and
    rounded once."""
    if group == 1:
        return t
    width = t.shape[2] // (heads * group)  # column blocks added as they lie: [.., heads, group, width] is re-tiled whole
    blocks = [t[:, :, h * width:(h + 1) * width].astype(jnp.float32) for h in range(heads * group)]
    return jnp.concatenate([sum(blocks[kv * group + 1:(kv + 1) * group], blocks[kv * group]) for kv in range(heads)],
                           axis=2).astype(t.dtype)


# `kernel` is decided once, in flash_attention, from the shapes and the mesh
# of the program being traced, so forward and backward cannot disagree.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, heads: Tuple[int, int], scale: float, causal: bool, kernel: bool, window: Optional[int],
           block_length: Optional[int] = None):
    o, _ = _fa_forward(q, k, v, heads, scale, causal, kernel, window, block_length)
    return o


# What a rematerialised layer has to keep so that its backward pass does not
# run the forward kernel again: `jax.checkpoint(...,
# policy=save_only_these_names(*SAVED_NAMES))`.  q, k and v are cheap to make
# again (projections); the output and the row statistics are the kernel's.
SAVED_NAMES = ("tpuft_fa_out", "tpuft_fa_lse")


def _flash_fwd(q, k, v, heads, scale, causal, kernel, window, block_length):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _fa_forward(q, k, v, heads, scale, causal, kernel, window, block_length)
    o, lse = checkpoint_name(o, SAVED_NAMES[0]), checkpoint_name(lse, SAVED_NAMES[1])
    return o, (q, k, v, o, lse)


def _flash_bwd(heads, scale, causal, kernel, window, block_length, res, g):
    q, k, v, o, lse = res
    q_heads, kv_heads = heads
    if kernel:
        group = q_heads // kv_heads
        dq, dk, dv = _fa_bwd_pallas(q, k, v, o, lse, g, scale, causal, window=window, q_heads=q_heads, kv_group=group,
                                    block_length=block_length)
        return dq, group_sum(dk, kv_heads, group), group_sum(dv, kv_heads, group)
    dq, dk, dv = _fa_bwd_xla(_to_heads(q, q_heads), _to_heads(k, kv_heads), _to_heads(v, kv_heads), _to_heads(o, q_heads),
                             lse, _to_heads(g, q_heads), scale, causal, window, block_length)
    return _from_heads(dq, q_heads), _from_heads(dk, kv_heads), _from_heads(dv, kv_heads)


def _fa_bwd_xla(q, k, v, o, lse, g, scale, causal, window: Optional[int] = None, block_length: Optional[int] = None):
    """Off-TPU backward: same math with the scores materialized in XLA.
    Also the oracle the pallas backward kernels are tested against."""
    qf, kf, vf, gf = (t.astype(jnp.float32) for t in (q, k, v, g))
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) * scale
    if causal:
        s = jnp.where(_visible(s.shape[-2], s.shape[-1], window, block_length), s, _NEG_INF)
    p = jnp.exp(s - lse[..., None])                     # recompute softmax
    dv = jnp.einsum("bqk,bqd->bkd", p, gf)
    dp = jnp.einsum("bqd,bkd->bqk", gf, vf)
    delta = jnp.sum(gf * o.astype(jnp.float32), axis=-1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, kf)
    dk = jnp.einsum("bqk,bqd->bkd", ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: float | None = None,
    mesh=None,
    window: Optional[int] = None,
    block_length: Optional[int] = None,
) -> jax.Array:
    """Multi-head attention, position-major as the projections leave it; q:
    [B, S, Hq, D], k: [B, S, Hkv, D], v: [B, S, Hkv, Dv] -> [B, S, Hq, Dv].
    Dv may differ from D (MLA: 192 / 128); the default scale is D ** -0.5.

    GQA: Hkv may divide Hq.  The kernels read a KV head in place for its
    group of query heads (no repeated copy of k or v in HBM, forward or
    backward); the XLA formulation broadcasts kv heads to the query groups.
    ``mesh`` is the mesh of the program being traced (None: the ambient
    abstract mesh); under more than one device the XLA formulation runs,
    see ``_pallas_util.kernels_apply``.

    ``window`` (causal only): a query at t sees the keys s with ``0 <= t - s
    < window``.  One that covers the sequence is no window.

    ``block_length`` (causal only, no window): the sequence is a
    block-diffusion stream — a noised copy of S / 2 data tokens, then their
    clean copy, in blocks of ``block_length`` — and a query sees what
    `_bd_visible` says: the `tpuft_bd_*` kernels walk the tiles that hold such a
    pair and no others.
    """
    b, sq, hq, d = q.shape
    if block_length is not None:
        assert causal and window is None and sq == k.shape[1] and sq % (2 * block_length) == 0, (
            "a block-diffusion stream is one sequence of two halves of whole blocks")
    if window is not None:
        assert causal and sq == k.shape[1] and window > 0, "a window is causal over one sequence"
        if window >= sq:
            window = None
    hkv, dv = k.shape[2], v.shape[3]
    assert hq % hkv == 0, "query heads must be a multiple of kv heads"
    scale = scale if scale is not None else d ** -0.5
    kernel = _use_pallas(sq, k.shape[1], dv, mesh)
    if hkv != hq and (not kernel or d % _LANE):  # the XLA formulation, and heads folded into the batch: a KV head a head
        k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    if kernel and d % _LANE:
        assert block_length is None, "the block-diffusion kernels take heads that are lane multiples"
        # padded, and the heads folded into the batch: the kernels' form of before PR 65 whole — blocks that lead with the
        # heads, lse lane-padded, the backward one batched product.  Moonlight read -2.5% with its heads side by side (30
        # copies of 134 MB) and -1.8% folded but with lse as rows and the backward a head at a time: XLA had hidden the
        # prefetch of the experts' 100 MiB of rows into fast memory behind the lse copy (PERF.md section 6, PR 65 (8))
        pad = [(0, 0)] * 3 + [(0, -d % _LANE)]
        q, k, v = (t.reshape((b * hq,) + t.shape[2:]) for t in (jnp.pad(q.transpose(0, 2, 1, 3), pad), jnp.pad(k.transpose(0, 2, 1, 3), pad),
                                                                 v.transpose(0, 2, 1, 3)))
        return _flash(q, k, v, (1, 1), scale, causal, kernel, window).reshape(b, hq, sq, dv).transpose(0, 2, 1, 3)
    out = _flash(*(t.reshape(t.shape[:2] + (-1,)) for t in (q, k, v)), (hq, k.shape[2]), scale, causal, kernel, window,
                 block_length)
    return out.reshape(b, sq, hq, dv)
