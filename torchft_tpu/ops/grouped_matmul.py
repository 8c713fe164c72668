"""Grouped (ragged) matmul: rows sorted by group, one weight matrix a group.

``out[r] = lhs[r] @ rhs[g]`` for the rows ``r`` of group ``g``, the groups'
rows lying one after another in ``lhs``.  The group boundaries are data (a
mixture-of-experts router decides them every step); every shape is static.

On one TPU device the three products of a training step are pallas kernels:

  tpuft_gmm_fwd    out  [M, N]    = lhs [M, K]  x rhs[g] [K, N]
  tpuft_gmm_dlhs   dlhs [M, K]    = dout [M, N] x rhs[g]^T
  tpuft_gmm_drhs   drhs [G, K, N] = sum over the rows of g of lhs^T x dout

They rest on one promise of the caller: every group starts on a row-tile
boundary (``padded_group_sizes``: each group's row count rounded up to the
tile, and a group without rows holding one tile).  The rows added take part
like any other: the caller keeps them out of its result with zero rows or
zero cotangents.  A row tile then belongs to one group, so each kernel is a plain
tiled matmul whose weight block is picked by a scalar-prefetched
``tile -> group`` table: no masks, no tile visited twice, and consecutive
tiles of one group reuse the weight block already in VMEM.  The price is the
added rows: half a tile a group on average.  Tiles past the last group are
skipped (the forward kernels write zeros there).

``rhs`` may be wider than ``lhs`` (float32 parameters under bfloat16
compute): the kernels read it as it is and round a weight block to ``lhs``'s
type in VMEM, once a group, so no rounded copy of the matrices is written or
kept; and its gradient leaves ``tpuft_gmm_drhs``'s float32 accumulator in
``rhs``'s own type with no rounding in between.

A width that is no whole number of 128-lane tiles (an expert of 1,856 = 14.5
x 128 columns) runs the same kernels over operands padded with zeros up to the
next tile INSIDE the call (``_padded``): zero columns of ``lhs`` against zero
rows of ``rhs`` add nothing and the padded columns of the result are cut away,
so the product is exact, and under autodiff the pads' transposes cut the
gradients back to the leaves' own shapes.  No width changes outside the call.

Off the TPU, under a mesh of several devices, and for shapes the kernels do
not tile, the same product is ``jax.lax.ragged_dot`` under autodiff — and where
the caller promised tiles (``row_tile > 1``) on one TPU device, the fall is
said once a shape in the log (``_say_once``), so that an odd width is seen in a
run and not only in a trace.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops import _pallas_util

__all__ = ["ROW_TILE", "grouped_matmul", "padded_group_sizes"]

logger = logging.getLogger(__name__)

# Rows a tile (v5e measurements behind the choice: PERF.md section 6, PR 27).
ROW_TILE = 128
# A weight block [K, block_n] in the type the weights are held in and the drhs
# accumulator [K, block_n] (f32), each double-buffered by the pipeline: what
# the block solver fits.  OLMoE's [2048, 1024] f32 expert matrix (8 MiB) is one
# block, and so is Moonlight's [2048, 1408] (11 MiB): 1408 = 11 * 128 has no
# divisor between 128 and itself, and at 128 columns a block every row tile is
# fetched eleven times.
_RHS_BLOCK_BYTES = 12 * 1024 * 1024
_ACC_BLOCK_BYTES = 12 * 1024 * 1024
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def padded_group_sizes(counts: jax.Array, row_tile: int = ROW_TILE) -> jax.Array:
    """Each group's rows rounded up to whole tiles, one tile at least: the
    layout ``grouped_matmul``'s kernels ask for.  For ``rows`` assignments
    over ``G`` groups the sizes sum to at most ``rows + G * row_tile``."""
    tiles = jnp.maximum((counts + row_tile - 1) // row_tile, 1)
    return (tiles * row_tile).astype(jnp.int32)


def _block_cols(cols: int, depth: int, itemsize: int, budget: int) -> Optional[int]:
    """Largest multiple of 128 dividing `cols` whose [depth, block] tile fits
    `budget`."""
    best = None
    for cand in range(_pallas_util.LANE, cols + 1, _pallas_util.LANE):
        if cols % cand == 0 and depth * cand * itemsize <= budget:
            best = cand
    return best


def _tiles(m: int, k: int, n: int, row_tile: int, rhs_itemsize: int = 4) -> Optional[Tuple[int, int, int]]:
    """(forward's block of N, dlhs's block of K, drhs's block of N), or None
    where the kernels do not tile these shapes."""
    lane = _pallas_util.LANE
    if row_tile % lane or m % row_tile or k % lane or n % lane:
        return None
    blocks = (
        _block_cols(n, k, rhs_itemsize, _RHS_BLOCK_BYTES),
        _block_cols(k, n, rhs_itemsize, _RHS_BLOCK_BYTES),
        _block_cols(n, k, 4, _ACC_BLOCK_BYTES),
    )
    return None if None in blocks else blocks


def _tile_groups(group_sizes: jax.Array, m: int, row_tile: int):
    """(group of each of the m / row_tile row tiles, number of tiles in use).
    Tiles past the last group read as its last tile's group, so no kernel
    fetches another weight block for them."""
    ends = jnp.cumsum(group_sizes // row_tile)
    used = ends[-1].astype(jnp.int32)
    tile = jnp.minimum(jnp.arange(m // row_tile, dtype=jnp.int32), used - 1)
    groups = jnp.searchsorted(ends, tile, side="right").astype(jnp.int32)
    return jnp.minimum(groups, group_sizes.shape[0] - 1), used.reshape(1)


def _gmm_kernel(groups_ref, used_ref, lhs_ref, rhs_ref, out_ref, *scratch, transpose_rhs: bool):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    rhs = rhs_ref
    if scratch:
        # A wider weight block is rounded once, when its group's first tile
        # arrives, and kept in VMEM for the group's other tiles.
        (rhs,) = scratch
        first = jnp.logical_or(i == 0, groups_ref[i] != groups_ref[jnp.maximum(i - 1, 0)])

        @pl.when(jnp.logical_and(first, i < used_ref[0]))
        def _round_the_block():
            rhs[...] = rhs_ref[...].astype(rhs.dtype)

    @pl.when(i < used_ref[0])
    def _product():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs[...], dims, preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _past_the_last_group():
        out_ref[...] = jnp.zeros_like(out_ref)


def _gmm_pallas(lhs, rhs, groups, used, row_tile: int, block: int, *,
                transpose_rhs: bool = False, interpret: bool = False):
    """lhs [M, K] x rhs [G, K, N] -> [M, N]; with `transpose_rhs`, lhs [M, N]
    x rhs[g]^T -> [M, K].  `block` tiles the output's columns.  `rhs` is read
    in its own type and rounded to `lhs`'s in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, depth = lhs.shape
    cols = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    rhs_block = (block, depth) if transpose_rhs else (depth, block)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, *rhs_block), lambda j, i, groups, used: (groups[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, *rhs_block), lambda j, i, groups, used: (groups[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, cols), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # Row tiles innermost: consecutive tiles of a group keep its
            # weight block in VMEM.
            grid=(cols // block, m // row_tile),
            in_specs=[
                pl.BlockSpec((row_tile, depth), lambda j, i, groups, used: (i, 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((row_tile, block), lambda j, i, groups, used: (i, j)),
            scratch_shapes=[] if rhs.dtype == lhs.dtype else [pltpu.VMEM(rhs_block, lhs.dtype)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="tpuft_gmm_dlhs" if transpose_rhs else "tpuft_gmm_fwd",
    )(groups, used, lhs, rhs)


def _drhs_kernel(groups_ref, used_ref, lhs_ref, dout_ref, out_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    live = i < used_ref[0]
    first = jnp.logical_or(i == 0, groups_ref[i] != groups_ref[jnp.maximum(i - 1, 0)])

    @pl.when(jnp.logical_and(live, first))
    def _first_tile_of_the_group():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live)
    def _accumulate():
        out_ref[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _drhs_pallas(lhs, dout, groups, used, n_groups: int, row_tile: int, block: int,
                 interpret: bool = False):
    """sum over each group's rows of lhs^T [K, rows] x dout [rows, N] ->
    [G, K, N] float32.  The output block of a group stays in VMEM while the
    group's tiles pass (they are consecutive) and is the accumulator."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = dout.shape[1]
    return pl.pallas_call(
        _drhs_kernel,
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // block, m // row_tile),
            in_specs=[
                pl.BlockSpec((row_tile, k), lambda j, i, groups, used: (i, 0)),
                pl.BlockSpec((row_tile, block), lambda j, i, groups, used: (i, j)),
            ],
            out_specs=pl.BlockSpec((None, k, block), lambda j, i, groups, used: (groups[i], 0, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="tpuft_gmm_drhs",
    )(groups, used, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, row_tile: int, interpret: bool):
    return _gmm_fwd(lhs, rhs, group_sizes, row_tile, interpret)[0]


def _gmm_fwd(lhs, rhs, group_sizes, row_tile: int, interpret: bool):
    m, k = lhs.shape
    block_n, _, _ = _tiles(m, k, rhs.shape[2], row_tile, rhs.dtype.itemsize)
    groups, used = _tile_groups(group_sizes, m, row_tile)
    out = _gmm_pallas(lhs, rhs, groups, used, row_tile, block_n, interpret=interpret)
    return out, (lhs, rhs, groups, used)


def _gmm_bwd(row_tile: int, interpret: bool, res, dout):
    import numpy as np

    lhs, rhs, groups, used = res
    m, k = lhs.shape
    n_groups, _, n = rhs.shape
    _, block_k, block_acc = _tiles(m, k, n, row_tile, rhs.dtype.itemsize)
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm_pallas(dout, rhs, groups, used, row_tile, block_k,
                       transpose_rhs=True, interpret=interpret)
    drhs = _drhs_pallas(lhs, dout, groups, used, n_groups, row_tile, block_acc, interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), np.zeros((n_groups,), jax.dtypes.float0)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    *,
    row_tile: int = 1,
    mesh=None,
    interpret: bool = False,
) -> jax.Array:
    """lhs [M, K] x rhs [G, K, N] -> [M, N] in ``lhs``'s type: the first
    ``group_sizes[0]`` rows times ``rhs[0]``, the next ``group_sizes[1]``
    times ``rhs[1]``, and so on; rows past the last group give zeros.

    ``row_tile`` is the caller's promise that ``group_sizes`` came from
    ``padded_group_sizes(counts, row_tile)`` (whole tiles, none empty).  With it, on one TPU device and where the
    shapes tile, the ``tpuft_gmm_*`` kernels run; otherwise
    ``jax.lax.ragged_dot``, which asks nothing of the sizes."""
    m, k = lhs.shape
    n = rhs.shape[2]
    if row_tile > 1 and (interpret or _pallas_util.kernels_apply(mesh)):
        lane = _pallas_util.LANE
        pad_k, pad_n = -k % lane, -n % lane
        if _tiles(m, k + pad_k, n + pad_n, row_tile, rhs.dtype.itemsize) is not None:
            if pad_k or pad_n:
                lhs, rhs = _padded(lhs, rhs, pad_k, pad_n)
            out = _gmm(lhs, rhs, group_sizes.astype(jnp.int32), row_tile, interpret)
            return out[:, :n] if pad_n else out
        _say_once(m, k, n, row_tile)
    return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32))


def _padded(lhs, rhs, pad_k: int, pad_n: int):
    """lhs [M, K] and rhs [G, K, N] with K and N filled up with zeros."""
    if pad_k:
        lhs = jnp.pad(lhs, [(0, 0), (0, pad_k)])
    return lhs, jnp.pad(rhs, [(0, 0), (0, pad_k), (0, pad_n)])


@functools.lru_cache(maxsize=None)
def _say_once(m: int, k: int, n: int, row_tile: int) -> None:
    """Trace time, once a shape: the kernels were promised tiles and do not
    tile this product."""
    logger.warning("grouped_matmul: [%d, %d] x [G, %d, %d] at row_tile %d does not tile for the tpuft_gmm_* kernels; "
                   "it runs jax.lax.ragged_dot", m, k, k, n, row_tile)
