"""`moe_rows`: a token's k rows fetched and summed where they land.

    out[t] = sum_j gates[t, j] * rows[dest[t, j]]        rows [R, E], dest, gates [T, k] -> [T, E]

float32 products and sum, one rounding at the end; a ``dest`` past the end
adds nothing (``jnp.take(mode="fill")``'s zero row).  It is the combine of a
dropless expert layer and, with ``gates=None`` (a plain sum), its dispatch's
transpose (`models/moe.py::_rows_summed`), in place of XLA's row gather into
a ``[T, k, E]`` array and a weighting pass over it.

Why a kernel.  A bfloat16 ``[R, E]`` array lies in HBM in tiles of rows x 128
columns: one row is E / 128 pieces, 4 KB apart, interleaved with its
neighbour's, and XLA's gather reads it at 34-47 ns a row of 2,048-2,560
columns (6 ns where it had prefetched the source into the 128 MiB fast
memory), then the weighting pass reads the gathered rows again.
`tpuft_moe_rows` reads a source whose row is ONE piece: ``[R, E / 128, 128]``,
a row whole tiles of its own (`_tiled`: a 2-D array's single row cannot be
sliced by a DMA).  The turn into that form is a pass of XLA's over the R rows
and the turn of the ``[T, E / 128, 128]`` result back another over T; with
them a call takes 3.5 ms where XLA's two passes took 11.3 (262,144 rows of
2,048 out of 67,584; v5e, PERF.md section 6, PR 67).

A grid step is a block of ``tb`` tokens.  Its ``tb * k`` row indices and
weights arrive in SMEM (a block of their own: the whole of ``dest`` is too
large to prefetch), choice-major, in range; every row is fetched by a DMA of
its own into a VMEM buffer of ``tb * k`` slots, the NEXT block's all issued
before this block's are waited for (two buffers), then each token's rows are
weighted by a scalar and summed in float32, a row a vector register or two.
The one instruction stream issues (5.5 bundles a row with the DMAs' bounds
checks off, 18.5 with: the indices are brought in range in XLA) and sums
(3.6-3.9 bundles a row): 8.5 ns a row of 2,048 columns on the chip.

**Every assignment's row is fetched**, as XLA's gather fetches it: one without
a row reads row ``dest % R`` under a weight of zero.

Who takes it is `applies`: one TPU device, rows of whole 128-column pieces,
a token count the blocks divide — and a source too large for XLA to keep in
the fast memory (`FAST_SOURCE_BYTES`).  Everywhere else the caller's XLA form
stays as it is.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from torchft_tpu.ops import _pallas_util

__all__ = ["FAST_SOURCE_BYTES", "applies", "counts", "moe_rows"]

logger = logging.getLogger(__name__)

LANE = _pallas_util.LANE
# A gather's source up to this size XLA prefetches into the v5e's 128 MiB fast
# memory (`S(1)`) and reads at 6 ns a row — there its gather and weighting pass
# (1.29 ms at Moonlight's 98,304 rows of 2,048) beat the kernel with its turns
# (1.48); above it the rows come out of HBM at 34-47 ns.  Between Moonlight's
# bf16[25600,2048] (100 MiB: prefetched) and SmallThinker's bf16[25600,2560]
# (125 MiB: not): PERF.md section 6, PR 65 (8) and PR 67.
FAST_SOURCE_BYTES = 112 * 1024 * 1024
_TOKENS = 32    # tokens a grid step (`tb`): 6-10% under 64 and 128 a call on the chip (PERF.md section 6, PR 67)
_GROUP = 8      # DMAs a turn of the fetching and the waiting loop (unrolled)
_TURN = 2       # tokens a turn of the summing loop (8 schedule at 10% fewer bundles a token and trace four times as long)
_PIECES = 8     # a row's 128-column pieces come in eights: a DMA slices whole tiles

# Trace time: call sites that took the kernel and that were left to XLA.
counts = {"kernel": 0, "xla": 0}


def _block(tokens: int, tb: Optional[int] = None) -> Optional[int]:
    """Tokens a grid step: the largest power of two up to ``tb`` (default
    `_TOKENS`) that divides the tokens, or None under `_GROUP`."""
    tb = tb or _TOKENS
    while tb >= _GROUP and tokens % tb:
        tb //= 2
    return tb if tb >= _GROUP else None


def _tiles(rows_shape, dtype, dest_shape) -> bool:
    """Whether the kernel tiles these shapes: rows of whole 128-column pieces, 16 or 32 bits wide, and tokens in blocks."""
    return rows_shape[1] % LANE == 0 and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32) and _block(dest_shape[0]) is not None


def applies(rows_shape, dtype, dest_shape, mesh=None) -> bool:
    """Whether ``moe_rows`` takes a gather of ``dest_shape`` [T, k] rows out of
    ``rows_shape`` [R, E] (module docstring).  Counted, and said once a shape."""
    n_rows, cols = rows_shape
    large = n_rows * cols * jnp.dtype(dtype).itemsize > FAST_SOURCE_BYTES
    takes = large and _tiles(rows_shape, dtype, dest_shape) and _pallas_util.kernels_apply(mesh)
    counts["kernel" if takes else "xla"] += 1
    _say_once(tuple(rows_shape), tuple(dest_shape), takes)
    return takes


@functools.lru_cache(maxsize=None)
def _say_once(rows_shape, dest_shape, takes: bool) -> None:
    """Trace time, once a shape and decision, where a run's log shows it (as `grouped_matmul._say_once`)."""
    logger.warning("moe_rows: %s rows out of %s %s (call sites so far: %d tpuft_moe_rows, %d XLA gathers)", dest_shape,
                   rows_shape, "through tpuft_moe_rows" if takes else "stay XLA's gather", counts["kernel"], counts["xla"])


def _kernel(idx_ref, nxt_ref, w_ref, src_ref, out_ref, buf, sem, *, tb: int, k: int, buffers: int):
    """A grid step: block i's rows weighted and summed, block i + 1's on their way."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, n = pl.program_id(0), pl.num_programs(0)

    def row_copy(index, slot, a):
        return pltpu.make_async_copy(src_ref.at[pl.ds(index, 1)], buf.at[slot, pl.ds(a, 1)], sem.at[slot])

    def fetch(ref, slot):
        def some(g, carry):
            for u in range(_GROUP):
                row_copy(ref[0, g * _GROUP + u], slot, g * _GROUP + u).start()
            return carry
        jax.lax.fori_loop(0, tb * k // _GROUP, some, None)

    if buffers == 1:
        slot = 0
        fetch(idx_ref, 0)
    else:
        slot = i % 2
        pl.when(i == 0)(lambda: fetch(idx_ref, 0))
        pl.when(i + 1 < n)(lambda: fetch(nxt_ref, (i + 1) % 2))

    def arrived(g, carry):
        for u in range(_GROUP):
            row_copy(0, slot, g * _GROUP + u).wait()
        return carry
    jax.lax.fori_loop(0, tb * k // _GROUP, arrived, None)

    def tokens(g, carry):
        for u in range(_TURN):
            t = g * _TURN + u
            acc = None
            for j in range(k):
                a = j * tb + t
                row = w_ref[0, a] * buf[slot, a].astype(jnp.float32)
                acc = row if acc is None else acc + row
            out_ref[t] = acc.astype(out_ref.dtype)
        return carry
    jax.lax.fori_loop(0, tb // _TURN, tokens, None)


def _rows_pallas(src, idx, w, *, tb: int, k: int, buffers: int = 2, interpret: bool = False):
    """src [R, E / 128, 128] (a row whole tiles of its own), idx and w [T / tb, 1, tb * k] choice-major ->
    [T, E / 128, 128] in ``src``'s type."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, row = idx.shape[0], src.shape[1:]
    scalars = lambda at: pl.BlockSpec((None, 1, tb * k), at, memory_space=pltpu.SMEM)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, tb=tb, k=k, buffers=buffers),
        out_shape=jax.ShapeDtypeStruct((n * tb, *row), src.dtype),
        grid=(n,),
        in_specs=[scalars(lambda i: (i, 0, 0)), scalars(lambda i: (jnp.minimum(i + 1, n - 1), 0, 0)),
                  scalars(lambda i: (i, 0, 0)), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tb, *row), lambda i: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((buffers, tb * k, *row), src.dtype), pltpu.SemaphoreType.DMA((buffers,))],
        # the steps hand their buffers on: in order, on one core; the indices are in range when they come
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        interpret=interpret,
        name="tpuft_moe_rows",
    )(idx, idx, w, src)


def _moe_rows(rows, dest, gates, *, tb: int, buffers: int, interpret: bool):
    (n_rows, cols), (tokens, k) = rows.shape, dest.shape
    weights = jnp.where((dest >= 0) & (dest < n_rows), gates.astype(jnp.float32), 0.0)
    # An assignment without a row reads row `dest % R` under its weight of zero: `_dropless_ffn` gives each its own
    # `dest`, so they spread over the buffer (all of them on one row read up to 2.5 times slower: PERF.md section 6, PR 67).
    out = _rows_pallas(_tiled(rows), _by_block((dest % n_rows).astype(jnp.int32), tb), _by_block(weights, tb),
                       tb=tb, k=k, buffers=buffers, interpret=interpret)
    return out.reshape(tokens, -1)[:, :cols]


def _by_block(x, tb: int):
    """[T, k] -> [T / tb, 1, k * tb]: a block of ``tb`` tokens' scalars choice-major, as a grid step reads them."""
    tokens, k = x.shape
    return x.reshape(tokens // tb, tb, k).swapaxes(1, 2).reshape(tokens // tb, 1, k * tb)


def _tiled(rows):
    """rows [R, E] -> [R, E / 128 (up to a multiple of 8, zero columns), 128]: a row whole tiles of its own, one
    piece of HBM.  Padded as columns before the turn: XLA then makes two passes of it where it makes three of a pad
    of the pieces (and keeps the producer's layout)."""
    n_rows, cols = rows.shape
    return jnp.pad(rows, ((0, 0), (0, -cols % (_PIECES * LANE)))).reshape(n_rows, -1, LANE)


@functools.lru_cache(maxsize=None)
def _traced_once(**static):
    """One jitted object a set of static arguments: a layer's forward, its
    recomputation and its transpose hold one trace of the kernel a shape
    (`ops/attention._traced_once`)."""
    return jax.jit(functools.partial(_moe_rows, **static))


def moe_rows(rows: jax.Array, dest: jax.Array, gates: Optional[jax.Array] = None, *, tokens_a_step: Optional[int] = None,
             buffers: int = 2, interpret: bool = False) -> jax.Array:
    """rows [R, E] bfloat16 or float32 (E a multiple of 128), dest [T, k] int, gates [T, k]
    or None -> [T, E] in ``rows``'s type: each token the sum of its rows
    ``rows[dest[t, j]]`` weighted by ``gates[t, j]`` (None: by one), a ``dest``
    outside ``0 .. R - 1`` adding nothing.  ``tokens_a_step`` and ``buffers``
    are the probe's (`tools/moe_rows_probe.py`); the program leaves them be."""
    assert _tiles(rows.shape, rows.dtype, dest.shape), (rows.dtype, rows.shape, dest.shape)
    if gates is None:  # ones, so that the plain sum and the combine of a layer are one trace and one lowered function
        gates = jnp.ones(dest.shape, jnp.float32)
    return _traced_once(tb=_block(dest.shape[0], tokens_a_step), buffers=buffers, interpret=interpret)(rows, dest, gates)
