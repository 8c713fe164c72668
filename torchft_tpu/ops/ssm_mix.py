"""`ssm_mix`: what Mamba-2 puts around its scan (arXiv:2405.21060;
`models/mamba.py`), as two pallas TPU kernel pairs in `ops/kda_mix.py`'s
design — one pass a direction, float32 inside the tile, nothing between a
half's inputs and outputs in HBM, the backward recomputing its tile from the
same inputs, the small leaves' gradients leaving as a partial sum a grid step
and summed in XLA.  Position-major in and out, as `ops/ssd.py` reads it.

**before** (`tpuft_ssmmix_fwd`, `tpuft_ssmmix_bwd`), from the in-projection's
u = [x | B | C] [B, S, H P + 2 G N] and dt_raw [B, S, H] in the compute type:

    a  = silu(causal convolution of 4 taps a channel + its bias)   zeros before position 0
    dt = softplus(dt_raw + dt_bias),   la = -exp(A_log) dt         float32 [B, S, H]: two tiny XLA fusions
    x, B, C = a's three parts,   xdt = x * dt                      the float32 product rounded ONCE

A grid step is a (batch, sequence tile, column block): `_lanes` 128-lane tiles
of u's columns, the same walk whether the block is x's, B's or C's — so the
backward writes u's gradient as ONE array in place and nothing is joined
afterwards.  The column blocks are the grid's innermost axis: x's blocks come
first and write x and xdt, B's and C's write theirs, and an output whose turn
it is not keeps its block index, so it is neither fetched nor flushed.  The
convolution's staging, its three earlier rows from the tile before, the
backward's walk from the sequence's end and the taps' folded sums are
`ops/kda_mix.py`'s own helpers, imported.  New against that kernel:

- the bias, whose gradient is one more folded sum;
- a head of P < 128 columns: a lane tile of x carries 128 / P heads' dt.  dt
  [rows, heads] goes onto its heads' columns as a product with a 0/1 matrix on
  the otherwise idle MXU — dt split into three bfloat16 parts that sum to it
  exactly, so the float32 result is dt bit for bit — and the backward's sum
  over a head's columns is the transposed product.  dt and its gradient cross
  HBM padded to whole lane tiles of heads ([B, S, 128] float32 at 64 heads).

**after** (`tpuft_ssmmix_out_fwd`, `tpuft_ssmmix_out_bwd`), from the scan's y,
x and the gate's projection z [B, S, H P]:

    o = RMSNorm_group((y + D x) * silu(z)) * ssm_norm

A grid step is a (batch, sequence tile, group): the norm's reduction is a lane
reduction over the step's block, worked through in blocks of rows that keep an
array of the block's width near sixteen registers.  D enters as a [1, H P] row
made in XLA; its gradient and the norm weight's leave as partial sums a column.

The rounding points are the XLA halves' (`models/mamba.py::_before`,
`_after`): x, xdt, B, C and o land in the compute type, la stays float32.  The
names hold no ``tpuft_ssd_``: the benchmark books every instruction with that
in its name to the scan.

The four calls are jitted (``inline``, so the program's text and its op names
are what they would be without): JAX then traces a kernel's body once a process
and shape, not once for each of a block's three traces of it in each of the
model's blocks — 24 traces where 4 do, +7 s of a run's set-up on the chip's
host (my chip run, PR 57: JAX's own `jaxpr_trace_duration`).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops import _pallas_util
from torchft_tpu.ops.kda_mix import _HALO, _KEEP, TAPS, _conv, _each_block, _fold, _rows, _shifted, _stage, tile_of

LANE = _pallas_util.LANE
_F32 = jnp.float32
_BF16 = jnp.bfloat16
_VMEM_LIMIT = 48 * 2 ** 20
_BLOCK = 16 * 1024      # elements of a block of rows in the after kernels: sixteen float32 registers an array


def applies(seq: int, head_dim: int, heads_a_group: int, state: int, conv: int, mesh=None) -> bool:
    """Whether the `tpuft_ssmmix_*` kernels run these shapes: a convolution of
    four taps, a head that divides the 128 lanes, a group's heads whole lane
    tiles, B and C whole lane tiles, a sequence that has a tile, and a TPU's
    program over one device."""
    return (conv == TAPS and LANE % head_dim == 0 and (heads_a_group * head_dim) % LANE == 0 and state % LANE == 0
            and tile_of(seq) is not None and _pallas_util.kernels_apply(mesh))


def _lanes(inner: int, state: int) -> int:
    """Lane tiles a column block of the before kernels: the most, up to four,
    that divide x's columns and B's alike."""
    return next(k for k in (4, 2, 1) if (inner // LANE) % k == 0 and (state // LANE) % k == 0)


def _at(i: int):
    return slice(i * LANE, (i + 1) * LANE)


# -- dt onto its heads' columns, and back -----------------------------------------


def _spread_matrices(heads: int, p: int, k: int) -> Tuple[jax.Array, jax.Array]:
    """(e [blocks, k, Hp, 128], its transpose [blocks, k, 128, Hp]) in
    bfloat16: e[b, i, h, l] is 1 where column l of lane tile i of x's block b
    is head h's, Hp the heads padded to whole lane tiles."""
    tiles, hp = heads * p // LANE, -(-heads // LANE) * LANE
    head_of = (jnp.arange(tiles * LANE) // p).reshape(tiles, 1, LANE)
    e = (jnp.arange(hp)[None, :, None] == head_of).astype(_BF16).reshape(tiles // k, k, hp, LANE)
    return e, jnp.swapaxes(e, 2, 3)


def _split(v):
    """Three bfloat16 arrays that sum to the float32 v exactly."""
    hi = v.astype(_BF16)
    rest = v - hi.astype(_F32)
    mid = rest.astype(_BF16)
    return hi, mid, (rest - mid.astype(_F32)).astype(_BF16)


def _onto(parts, matrix):
    """The parts' products with a 0/1 matrix, summed in float32."""
    hi, mid, lo = (jnp.dot(part, matrix, preferred_element_type=_F32) for part in parts)
    return hi + mid + lo


# -- before ---------------------------------------------------------------------


def _stage_block(ext_ref, u_ref, halo_ref, first):
    for i in range(u_ref.shape[1] // LANE):
        _stage(ext_ref, i, u_ref.at[:, _at(i)], halo_ref.at[:, _at(i)], first)


def _before_fwd_kernel(u_ref, halo_ref, dt_ref, taps_ref, bias_ref, e_ref, x_ref, xdt_ref, bm_ref, cm_ref, ext_ref, *,
                       nx: int, nb: int):
    from jax.experimental import pallas as pl

    tile, width = u_ref.shape
    k, j = width // LANE, pl.program_id(2)
    _stage_block(ext_ref, u_ref, halo_ref, pl.program_id(1) == 0)

    def activated(i, r0, rows):
        return jax.nn.silu(_conv(ext_ref, i, taps_ref[:, _at(i)], r0, rows) + bias_ref[:, _at(i)])

    @pl.when(j < nx)
    def _x():
        def block(r0, rows, carry):
            at = _rows(r0, rows)
            dt = _split(dt_ref[at, :])
            for i in range(k):
                a = activated(i, r0, rows)
                x_ref[at, _at(i)] = a.astype(x_ref.dtype)
                xdt_ref[at, _at(i)] = (a * _onto(dt, e_ref[i])).astype(xdt_ref.dtype)
            return carry

        _each_block(tile, block)

    @pl.when(j >= nx)
    def _bc():
        def block(r0, rows, carry):
            at = _rows(r0, rows)
            for i in range(k):
                a = activated(i, r0, rows).astype(bm_ref.dtype)

                @pl.when(j < nx + nb)
                def _b():
                    bm_ref[at, _at(i)] = a

                @pl.when(j >= nx + nb)
                def _c():
                    cm_ref[at, _at(i)] = a
            return carry

        _each_block(tile, block)


def _before_bwd_kernel(u_ref, halo_ref, dt_ref, taps_ref, bias_ref, e_ref, et_ref, dx_ref, dxdt_ref, dbm_ref, dcm_ref,
                       du_ref, ddt_ref, dtaps_ref, dbias_ref, ext_ref, dext_ref, next_ref, *, nx: int, nb: int):
    from jax.experimental import pallas as pl

    tile, width = u_ref.shape
    k, j = width // LANE, pl.program_id(2)
    # the walk is from the sequence's end: the first step is the last tile, with no position after it
    dext_ref[:, tile:tile + _KEEP, :] = jnp.where(pl.program_id(1) == 0, 0.0, next_ref[j])
    _stage_block(ext_ref, u_ref, halo_ref, pl.program_id(1) == pl.num_programs(1) - 1)
    zero = jnp.zeros((_KEEP, LANE), _F32)

    @pl.when(j == 0)
    def _start():
        ddt_ref[...] = jnp.zeros_like(ddt_ref)

    def results(r0, rows, sums, activation_s_gradient):
        """The gradient of the convolution's results into `dext_ref`, the
        bias's folded sums beside it."""
        at, out = _rows(r0, rows), []
        for i in range(k):
            c = _conv(ext_ref, i, taps_ref[:, _at(i)], r0, rows) + bias_ref[:, _at(i)]
            sig = jax.nn.sigmoid(c)
            dc = activation_s_gradient(i, at, c * sig) * (sig * (1.0 + c * (1.0 - sig)))
            dext_ref[i, at, :] = dc
            out.append(sums[i] + _fold(dc))
        return tuple(out)

    def bias_s(sums):
        dbias_ref[...] = jnp.concatenate([jnp.sum(total, axis=0, keepdims=True) for total in sums], axis=1)

    @pl.when(j < nx)
    def _x():
        def block(r0, rows, sums):
            at = _rows(r0, rows)
            dt, ddt = _split(dt_ref[at, :]), []

            def da(i, at, a):
                dxdt = dxdt_ref[at, _at(i)].astype(_F32)
                ddt.append(_onto(_split(dxdt * a), et_ref[i]))
                return dx_ref[at, _at(i)].astype(_F32) + dxdt * _onto(dt, e_ref[i])

            sums = results(r0, rows, sums, da)
            ddt_ref[at, :] += functools.reduce(jnp.add, ddt)
            return sums

        bias_s(_each_block(tile, block, (zero,) * k))

    @pl.when(j >= nx)
    def _bc():
        def da(i, at, a):
            return jnp.where(j < nx + nb, dbm_ref[at, _at(i)], dcm_ref[at, _at(i)]).astype(_F32)

        bias_s(_each_block(tile, lambda r0, rows, sums: results(r0, rows, sums, da), (zero,) * k))

    # the convolution's transpose: du_t = sum_back taps[3 - back] dc_{t + back}, the taps' own sums beside it
    for i in range(k):
        taps = taps_ref[:, _at(i)]

        def transposed(r0, rows, sums):              # traced at once, inside this turn of the loop over the lane tiles
            dc = dext_ref[i, _rows(r0, rows), :]
            du = taps[TAPS - 1:TAPS] * dc
            for back in range(1, TAPS):
                du = du + taps[TAPS - 1 - back:TAPS - back] * dext_ref[i, _rows(r0, rows, back), :]
            du_ref[_rows(r0, rows), _at(i)] = du.astype(du_ref.dtype)
            # sums[tap]: the tap `TAPS - 1 - tap` rows back
            return tuple(total + _fold(dc * _shifted(ext_ref, i, r0, rows, TAPS - 1 - tap)) for tap, total in enumerate(sums))

        sums = _each_block(tile, transposed, (zero,) * TAPS)
        dtaps_ref[:, _at(i)] = jnp.concatenate([jnp.sum(total, axis=0, keepdims=True) for total in sums], axis=0)
    # what the tile before this one reads as its next rows
    next_ref[j] = dext_ref[:, 0:_KEEP, :]


def _before_specs(tile: int, n_tiles: int, k: int, nx: int, nb: int, hp: int, reverse: bool):
    from jax.experimental import pallas as pl

    at = (lambda s: n_tiles - 1 - s) if reverse else (lambda s: s)
    width = k * LANE
    rows = lambda to: pl.BlockSpec((None, tile, width), lambda b, s, j: (b, at(s), to(j)))        # noqa: E731
    u = rows(lambda j: j)
    halo = pl.BlockSpec((None, _HALO, width), lambda b, s, j: (b, jnp.maximum(at(s) * (tile // _HALO) - 1, 0), j))
    # an output whose turn it is not stays at the block it wrote last, or will write first
    x, bm, cm = (rows(lambda j, first=first, n=n: jnp.clip(j - first, 0, n - 1)) for first, n in ((0, nx), (nx, nb), (nx + nb, nb)))
    dt = pl.BlockSpec((None, tile, hp), lambda b, s, j: (b, at(s), 0))
    small = lambda lead: pl.BlockSpec((lead, width), lambda b, s, j: (0, j))                     # noqa: E731
    e = pl.BlockSpec((None, k, hp, LANE), lambda b, s, j: (jnp.minimum(j, nx - 1), 0, 0, 0))
    et = pl.BlockSpec((None, k, LANE, hp), lambda b, s, j: (jnp.minimum(j, nx - 1), 0, 0, 0))
    partial = lambda lead: pl.BlockSpec((None, None, lead, width), lambda b, s, j: (b, at(s), 0, j))   # noqa: E731
    return u, halo, x, bm, cm, dt, small, e, et, partial


def _shape_of(u, dt, inner: int):
    b, seq, channels = u.shape
    state = (channels - inner) // 2
    k = _lanes(inner, state)
    return b, seq, channels, state, k, inner // (k * LANE), state // (k * LANE), dt.shape[2]


@functools.partial(jax.jit, static_argnames=("p", "inner", "tile", "interpret"), inline=True)
def _before_fwd_pallas(u, dt, taps, bias, p: int, inner: int, tile: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, seq, channels, state, k, nx, nb, hp = _shape_of(u, dt, inner)
    n = seq // tile
    u_spec, halo, x, bm, cm, dt_spec, small, e_spec, _, _ = _before_specs(tile, n, k, nx, nb, hp, reverse=False)
    e, _ = _spread_matrices(inner // p, p, k)
    out = lambda width: jax.ShapeDtypeStruct((b, seq, width), u.dtype)   # noqa: E731
    return pl.pallas_call(
        functools.partial(_before_fwd_kernel, nx=nx, nb=nb),
        out_shape=[out(inner), out(inner), out(state), out(state)],
        grid=(b, n, nx + 2 * nb),
        in_specs=[u_spec, halo, dt_spec, small(TAPS), small(1), e_spec],
        out_specs=[x, x, bm, cm],
        scratch_shapes=[pltpu.VMEM((k, _KEEP + tile, LANE), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_ssmmix_fwd",
    )(u, u, dt, taps, bias, e)


@functools.partial(jax.jit, static_argnames=("p", "inner", "tile", "interpret"), inline=True)
def _before_bwd_pallas(u, dt, taps, bias, dx, dxdt, dbm, dcm, p: int, inner: int, tile: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, seq, channels, state, k, nx, nb, hp = _shape_of(u, dt, inner)
    n = seq // tile
    u_spec, halo, x, bm, cm, dt_spec, small, e_spec, et_spec, partial = _before_specs(tile, n, k, nx, nb, hp, reverse=True)
    e, et = _spread_matrices(inner // p, p, k)
    sums = lambda lead: jax.ShapeDtypeStruct((b, n, lead, channels), _F32)   # noqa: E731
    du, ddt, dtaps, dbias = pl.pallas_call(
        functools.partial(_before_bwd_kernel, nx=nx, nb=nb),
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype), jax.ShapeDtypeStruct(dt.shape, _F32), sums(TAPS), sums(1)],
        grid=(b, n, nx + 2 * nb),
        in_specs=[u_spec, halo, dt_spec, small(TAPS), small(1), e_spec, et_spec, x, x, bm, cm],
        out_specs=[u_spec, dt_spec, partial(TAPS), partial(1)],
        scratch_shapes=[pltpu.VMEM((k, _KEEP + tile, LANE), _F32), pltpu.VMEM((k, tile + _KEEP, LANE), _F32),
                        pltpu.VMEM((nx + 2 * nb, k, _KEEP, LANE), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_ssmmix_bwd",
    )(u, u, dt, taps, bias, e, et, dx, dxdt, dbm, dcm)
    return du, ddt, jnp.sum(dtaps, axis=(0, 1)), jnp.sum(dbias, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _before(u, dt, taps, bias, p: int, inner: int, tile: int, interpret: bool):
    return tuple(_before_fwd_pallas(u, dt, taps, bias, p, inner, tile, interpret))


def _before_fwd(u, dt, taps, bias, p, inner, tile, interpret):
    return _before(u, dt, taps, bias, p, inner, tile, interpret), (u, dt, taps, bias)


def _before_bwd(p, inner, tile, interpret, res, cotangents):
    return _before_bwd_pallas(*res, *cotangents, p, inner, tile, interpret)


_before.defvjp(_before_fwd, _before_bwd)


def before(u: jax.Array, dt_raw: jax.Array, conv: jax.Array, conv_bias: jax.Array, dt_bias: jax.Array, a_log: jax.Array,
           *, head_dim: int, tile: Optional[int] = None, interpret: bool = False
           ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """x and dt * x [B, S, H P], B and C [B, S, G N] in u's type and the log
    decay [B, S, H] float32 from the in-projection's u [B, S, H P + 2 G N] and
    dt_raw [B, S, H], the taps [4, H P + 2 G N] (the LAST tap the position's
    own) and their bias, `dt_bias` and `A_log` [H].  Differentiable in all six."""
    heads = dt_raw.shape[2]
    tile = tile_of(u.shape[1], tile)
    assert tile is not None and (u.shape[2] - heads * head_dim) % (2 * LANE) == 0, u.shape
    dt = jax.nn.softplus(dt_raw.astype(_F32) + dt_bias.astype(_F32))
    la = -jnp.exp(a_log.astype(_F32)) * dt
    padded = jnp.pad(dt, [(0, 0), (0, 0), (0, -heads % LANE)])                     # whole lane tiles of heads
    x, xdt, bm, cm = _before(u, padded, conv.astype(_F32), conv_bias.astype(_F32)[None], head_dim, heads * head_dim,
                             tile, interpret)
    return x, xdt, bm, cm, la


# -- after ----------------------------------------------------------------------


def _each_rows(tile: int, width: int, body, carry=()):
    """``body(at, carry) -> carry`` over the tile's blocks of rows, `at` the
    block's rows: as many rows as keep an array [rows, width] at `_BLOCK`
    elements, sixteen (a bfloat16 array's smallest) at the least."""
    from jax.experimental import pallas as pl

    rows = min(tile, max(_HALO, _BLOCK // width))
    return jax.lax.fori_loop(0, tile // rows, lambda j, c: body(pl.ds(pl.multiple_of(j * rows, rows), rows), c), carry)


def _gated(y, x, z, d):
    """(the skip's sum, SiLU's sigmoid, the gated rows) in float32."""
    pre, sig = y.astype(_F32) + d * x.astype(_F32), jax.nn.sigmoid(z)
    return pre, sig, pre * (z * sig)


def _after_fwd_kernel(y_ref, x_ref, z_ref, d_ref, norm_ref, out_ref, *, eps: float):
    d, norm = d_ref[...], norm_ref[...]

    def block(at, carry):
        _, _, t = _gated(y_ref[at, :], x_ref[at, :], z_ref[at, :].astype(_F32), d)
        out_ref[at, :] = (t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps) * norm).astype(out_ref.dtype)
        return carry

    _each_rows(*y_ref.shape, block)


def _after_bwd_kernel(y_ref, x_ref, z_ref, d_ref, norm_ref, dout_ref, dy_ref, dx_ref, dz_ref, dd_ref, dnorm_ref, *,
                      eps: float):
    d, norm = d_ref[...], norm_ref[...]
    zero = jnp.zeros((_KEEP, y_ref.shape[1]), _F32)

    def block(at, sums):
        x, z, dout = x_ref[at, :].astype(_F32), z_ref[at, :].astype(_F32), dout_ref[at, :].astype(_F32)
        pre, sig, t = _gated(y_ref[at, :], x, z, d)
        inv = jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)
        unit = t * inv
        dunit = dout * norm
        dt = inv * (dunit - unit * jnp.mean(dunit * unit, axis=-1, keepdims=True))
        dpre = dt * (z * sig)
        dy_ref[at, :] = dpre.astype(dy_ref.dtype)
        dx_ref[at, :] = (dpre * d).astype(dx_ref.dtype)
        dz_ref[at, :] = (dt * pre * (sig * (1.0 + z * (1.0 - sig)))).astype(dz_ref.dtype)
        return sums[0] + _fold(dpre * x), sums[1] + _fold(dout * unit)

    dd, dnorm = _each_rows(*y_ref.shape, block, (zero, zero))
    dd_ref[...] = jnp.sum(dd, axis=0, keepdims=True)
    dnorm_ref[...] = jnp.sum(dnorm, axis=0, keepdims=True)


def _after_tile(seq: int, width: int, tile: Optional[int]) -> Optional[int]:
    """Rows a grid step: a block of [rows, a group's columns] up to a megabyte in bfloat16."""
    return tile_of(seq, tile or max(_HALO, min(1024, 2 ** 19 // width)))


def _after_specs(tile: int, width: int):
    from jax.experimental import pallas as pl

    rows = pl.BlockSpec((None, tile, width), lambda b, s, g: (b, s, g))
    column = pl.BlockSpec((1, width), lambda b, s, g: (0, g))
    partial = pl.BlockSpec((None, None, 1, width), lambda b, s, g: (b, s, 0, g))
    return rows, column, partial


@functools.partial(jax.jit, static_argnames=("groups", "eps", "tile", "interpret"), inline=True)
def _after_fwd_pallas(y, x, z, d, norm, groups: int, eps: float, tile: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, seq, inner = y.shape
    rows, column, _ = _after_specs(tile, inner // groups)
    return pl.pallas_call(
        functools.partial(_after_fwd_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        grid=(b, seq // tile, groups),
        in_specs=[rows, rows, rows, column, column],
        out_specs=rows,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_ssmmix_out_fwd",
    )(y, x, z, d, norm)


@functools.partial(jax.jit, static_argnames=("groups", "eps", "tile", "interpret"), inline=True)
def _after_bwd_pallas(y, x, z, d, norm, dout, groups: int, eps: float, tile: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, seq, inner = y.shape
    rows, column, partial = _after_specs(tile, inner // groups)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)   # noqa: E731
    sums = jax.ShapeDtypeStruct((b, seq // tile, 1, inner), _F32)
    dy, dx, dz, dd, dnorm = pl.pallas_call(
        functools.partial(_after_bwd_kernel, eps=eps),
        out_shape=[like(y), like(x), like(z), sums, sums],
        grid=(b, seq // tile, groups),
        in_specs=[rows, rows, rows, column, column, rows],
        out_specs=[rows, rows, rows, partial, partial],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_ssmmix_out_bwd",
    )(y, x, z, d, norm, dout)
    return dy, dx, dz, jnp.sum(dd, axis=(0, 1)), jnp.sum(dnorm, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _after(y, x, z, d, norm, groups: int, eps: float, tile: int, interpret: bool):
    return _after_fwd_pallas(y, x, z, d, norm, groups, eps, tile, interpret)


def _after_fwd(y, x, z, d, norm, groups, eps, tile, interpret):
    return _after(y, x, z, d, norm, groups, eps, tile, interpret), (y, x, z, d, norm)


def _after_bwd(groups, eps, tile, interpret, res, dout):
    return _after_bwd_pallas(*res, dout, groups, eps, tile, interpret)


_after.defvjp(_after_fwd, _after_bwd)


def after(y: jax.Array, x: jax.Array, z: jax.Array, skip: jax.Array, norm: jax.Array, *, groups: int, eps: float,
          tile: Optional[int] = None, interpret: bool = False) -> jax.Array:
    """The gated group norm's output [B, S, H P] in z's type from the scan's y,
    x and the gate's projection z [B, S, H P], `ssm_D` [H] and `ssm_norm`
    [H P].  Differentiable in all five."""
    inner = y.shape[2]
    tile = _after_tile(y.shape[1], inner // groups, tile)
    assert tile is not None and inner % (groups * LANE) == 0, y.shape
    d = jnp.repeat(skip.astype(_F32), inner // skip.shape[0])[None]                 # [1, H P]: D over its head's columns
    return _after(y, x, z, d, norm.astype(_F32)[None], groups, float(eps), tile, interpret)
