"""`kda_mix`: what a gated delta rule's layer puts around its scan — Kimi
Delta Attention's (arXiv:2510.26692; `models/kda.py`) and Gated DeltaNet's
(arXiv:2412.06464 as Qwen3-Next has it; `models/gdn.py`, its `gdn_mix`) — as
two pallas TPU kernel pairs: one pass a direction, float32 inside the tile,
nothing between input and output in HBM.  One algorithm; what differs between
the two layers is read off the operands' static shapes and off which operands
are there (an operand that is None has no block, no ref and no output).

**before** (`tpuft_kdamix_fwd`, `tpuft_kdamix_bwd`), from the projections
q~, k~ [B, S, Hk * D] and v~ [B, S, H * D] in the compute type to the scan's
operands q, k [B, Hk, S, D] and v [B, H, S, D]:

    c = causal convolution of 4 taps a channel, zeros before position 0;  u = silu(c)
    q = u_q / |u_q| * D**-0.5,  k = u_k / |u_k|  (a head's D columns, + 1e-6 under the root),  v = u_v
    g = -exp(A_log) * softplus(a + dt_bias)      (float32; Kimi's alone: a decay a CHANNEL, a [B, S, H * D])

A grid step is a (batch, key head, sequence tile): a head's 128 columns are
one lane tile, so the head's norm is a lane reduction where the data lie, the
head-major layout is the output's block index and no transpose exists.  Under
Kimi every head has its own key (Hk = H: q, k, v, a and g one lane tile each);
under Gated DeltaNet 16 key heads serve 32 value heads, and v's block is the
H / Hk lane tiles of the value heads that read the step's key head, which have
no norm to cross them; its decay is ONE number a head and position, which is
no column to read or write: `a` is None and g stays XLA's (`_gdn_decay`).  The
convolution's three earlier rows come from the tile before (the last 16 rows
of it, a block of their own: the smallest a bfloat16 array has).  The backward
recomputes its tile from the same inputs — nothing is kept for it beside them
— and walks the sequence from its end: the convolution's transpose needs the
first three rows of the NEXT tile's gradient, which the step before left in
VMEM.  The small leaves' gradients (the taps, `dt_bias`, the rate) leave the
kernel as a partial sum a grid step and are summed in XLA.

**after** (`tpuft_kdamix_out_fwd`, `tpuft_kdamix_out_bwd`), from the scan's
output o [B, H, S, D] and the gate's projection [B, S, H * D] to the heads'
joined output [B, S, H * D]: an RMSNorm over the head's columns with one
weight of D, times ``sigmoid(gate + bias)`` (Kimi's) or, with no bias,
``SiLU(gate)`` (Gated DeltaNet's).

A tile is worked through in blocks of ``_ROWS`` rows so that a block's
intermediates stay near the registers; the rounding points are the XLA
halves' (`models/kda.py::_kda_before`, `_kda_after`; `models/gdn.py::
_gdn_before`, `_gdn_after`): q, k, v and the output land in the compute type,
g stays float32.  The names hold no ``tpuft_kda_``: the benchmark books every
instruction with that in its name to the scan.

The four calls are jitted (``inline``, so the program's text and its op names
are what they would be without, as `ops/ssm_mix.py`'s): JAX then traces a
kernel's body once a process and shape, not once for each of a layer's three
traces of it in each of the model's layers.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops import _pallas_util

LANE = _pallas_util.LANE
TAPS = 4        # the convolution's kernel: a position and the three before it
_HALO = 16      # rows of the tile before that a step fetches: a bfloat16 array's smallest block
_KEEP = 8       # of which the last eight are kept (float32's smallest), three of them read
_TILE = 1024    # rows a grid step
_ROWS = 128     # rows a block inside it
_F32 = jnp.float32
L2_EPS = 1e-6


def tile_of(seq: int, tile: Optional[int] = None) -> Optional[int]:
    """Rows a grid step: the largest power of two up to ``tile`` (default
    `_TILE`) that divides the sequence, or None where that is under `_HALO`."""
    t = tile or _TILE
    while t >= _HALO and seq % t:
        t //= 2
    return t if t >= _HALO else None


def applies(seq: int, width: int, mesh=None) -> bool:
    """Whether the kernels run: a head is whole lane tiles of 128 columns, the
    sequence has a tile, and the program is a TPU's over one device."""
    return width == LANE and tile_of(seq) is not None and _pallas_util.kernels_apply(mesh)


def _each_block(tile: int, body, carry=()):
    """``body(r0, rows, carry) -> carry`` over the tile's blocks of `_ROWS`
    rows, r0 the block's first row: a loop in the kernel, not an unrolled one
    (which is as fast to 5% on the chip and, at sixteen times the text to
    trace, lower and compile in a layer's every call, ten seconds of set-up)."""
    from jax.experimental import pallas as pl

    rows = min(_ROWS, tile)
    return jax.lax.fori_loop(0, tile // rows, lambda j, c: body(pl.multiple_of(j * rows, rows), rows, c), carry)


def _rows(r0, rows, shift: int = 0):
    from jax.experimental import pallas as pl

    return pl.ds(r0 + shift, rows)


def _fold(x):
    """[rows, 128] summed over its rows down to the eight sublanes of one
    register: adds on the vector unit, no shuffle."""
    return jnp.sum(x.reshape(x.shape[0] // _KEEP, _KEEP, x.shape[1]), axis=0)


# -- before ---------------------------------------------------------------------


def _stage(ext_ref, i, z_ref, halo_ref, first):
    """The tile's rows in float32 behind the eight rows before them (zeros
    at the sequence's start): ext_ref[i] [8 + tile, 128]."""
    tile = z_ref.shape[0]
    before = halo_ref[...].astype(_F32)[_HALO - _KEEP:]
    ext_ref[i, 0:_KEEP, :] = jnp.where(first, 0.0, before)
    ext_ref[i, _KEEP:_KEEP + tile, :] = z_ref[...].astype(_F32)


def _shifted(ext_ref, i, r0, rows, back):
    """Rows [r0 - back, r0 - back + rows) of the tile: the rows `back` positions before."""
    return ext_ref[i, _rows(r0, rows, _KEEP - back), :]


def _conv(ext_ref, i, taps, r0, rows):
    """Rows [r0, r0 + rows) of the convolution, summed the position's own
    first, as `_causal_conv` sums them."""
    out = taps[TAPS - 1:TAPS] * _shifted(ext_ref, i, r0, rows, 0)
    for back in range(1, TAPS):
        out = out + taps[TAPS - 1 - back:TAPS - back] * _shifted(ext_ref, i, r0, rows, back)
    return out


def _lane(ref, j: int, n: int):
    """Lane tile j of the n a block holds, a view [rows, 128]: 128 columns of a
    position-major block [rows, n * 128], a head of a head-major one [n, rows, 128]."""
    if n == 1:
        return ref
    return ref.at[j] if len(ref.shape) == 3 else ref.at[:, j * LANE:(j + 1) * LANE]


def _lane_tiles(*streams):
    """A step's lane tiles in the taps' order — q's, k's, then one a value head
    that reads this key head: (the head norm's scale, a view [rows, 128] of
    each of the stream's refs), the stream's position-major input first.  q
    carries the scan's scale, k none; v (None) has no norm.  A stream whose
    refs are None is not in the call."""
    tiles = []
    for scale, refs in zip((LANE ** -0.5, 1.0, None), streams):
        if refs[0] is not None:
            n = refs[0].shape[-1] // LANE
            tiles += [(scale, *(_lane(ref, j, n) for ref in refs)) for j in range(n)]
    return tiles


def _before_fwd_kernel(q0_ref, k0_ref, v0_ref, a_ref, hq_ref, hk_ref, hv_ref, taps_ref, bias_ref, rate_ref,
                       q_ref, k_ref, v_ref, g_ref, ext_ref):
    from jax.experimental import pallas as pl

    tile = ext_ref.shape[1] - _KEEP                  # the scratch is `_stage`'s: eight rows before the tile's
    first = pl.program_id(2) == 0
    lanes = _lane_tiles((q0_ref, hq_ref, q_ref), (k0_ref, hk_ref, k_ref), (v0_ref, hv_ref, v_ref))
    for i, (_, z_ref, halo_ref, _) in enumerate(lanes):
        _stage(ext_ref, i, z_ref, halo_ref, first)
    if a_ref is not None:
        bias, rate = bias_ref[...], rate_ref[...]

    def block(r0, rows, carry):
        at = _rows(r0, rows)
        for i, (scale, _, _, out_ref) in enumerate(lanes):
            u = jax.nn.silu(_conv(ext_ref, i, taps_ref[i], r0, rows))
            if scale is not None:
                u = u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + L2_EPS) * scale
            out_ref[at, :] = u.astype(out_ref.dtype)
        if a_ref is not None:
            g_ref[at, :] = rate * jax.nn.softplus(a_ref[at, :].astype(_F32) + bias)
        return carry

    _each_block(tile, block)


def _before_bwd_kernel(q0_ref, k0_ref, v0_ref, a_ref, hq_ref, hk_ref, hv_ref, taps_ref, bias_ref, rate_ref,
                       dq_ref, dk_ref, dv_ref, dg_ref,
                       dq0_ref, dk0_ref, dv0_ref, da_ref, dtaps_ref, dbias_ref, drate_ref, ext_ref, dext_ref):
    from jax.experimental import pallas as pl

    tile = ext_ref.shape[1] - _KEEP
    n_tiles = pl.num_programs(2)
    first = pl.program_id(2) == n_tiles - 1          # the walk is from the sequence's end: this is tile 0
    lanes = _lane_tiles((q0_ref, hq_ref, dq_ref, dq0_ref), (k0_ref, hk_ref, dk_ref, dk0_ref), (v0_ref, hv_ref, dv_ref, dv0_ref))

    @pl.when(pl.program_id(2) == 0)
    def _last_tile():                                # no position after the sequence's end
        dext_ref[:, tile:tile + _KEEP, :] = jnp.zeros((len(lanes), _KEEP, LANE), _F32)

    for i, (_, z_ref, halo_ref, _, _) in enumerate(lanes):
        _stage(ext_ref, i, z_ref, halo_ref, first)
    if a_ref is not None:
        bias, rate = bias_ref[...], rate_ref[...]
    zero = jnp.zeros((_KEEP, LANE), _F32)

    def results(r0, rows, sums):                     # the gradient of the convolutions' results, and the decay's side whole
        at = _rows(r0, rows)
        for i, (scale, _, _, cot_ref, _) in enumerate(lanes):
            c = _conv(ext_ref, i, taps_ref[i], r0, rows)
            sig = jax.nn.sigmoid(c)
            u, du = c * sig, cot_ref[at, :].astype(_F32)
            if scale is not None:
                inv = jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + L2_EPS)
                du = du * scale
                du = inv * du - u * (inv * inv * inv * jnp.sum(du * u, axis=-1, keepdims=True))
            dext_ref[i, at, :] = du * (sig * (1.0 + c * (1.0 - sig)))
        if a_ref is None:
            return sums
        x = a_ref[at, :].astype(_F32) + bias
        dg = dg_ref[at, :]
        dx = dg * rate * jax.nn.sigmoid(x)
        da_ref[at, :] = dx.astype(da_ref.dtype)
        return sums[0] + _fold(dx), sums[1] + _fold(dg * jax.nn.softplus(x))

    decay_sums = _each_block(tile, results, () if a_ref is None else (zero, zero))
    if a_ref is not None:
        dbias_ref[...] = jnp.sum(decay_sums[0], axis=0, keepdims=True)
        drate_ref[...] = jnp.sum(decay_sums[1], axis=0, keepdims=True)
    # the convolution's transpose: dz_t = sum_back taps[3 - back] dc_{t + back}, the taps' own sums beside it
    for i, (*_, out_ref) in enumerate(lanes):
        taps = taps_ref[i]

        def transposed(r0, rows, sums):              # traced at once, inside this turn of the loop over the lane tiles
            dc = dext_ref[i, _rows(r0, rows), :]
            dz = taps[TAPS - 1:TAPS] * dc
            for back in range(1, TAPS):
                dz = dz + taps[TAPS - 1 - back:TAPS - back] * dext_ref[i, _rows(r0, rows, back), :]
            out_ref[_rows(r0, rows), :] = dz.astype(out_ref.dtype)
            # sums[tap]: the tap `TAPS - 1 - tap` rows back
            return tuple(total + _fold(dc * _shifted(ext_ref, i, r0, rows, TAPS - 1 - tap)) for tap, total in enumerate(sums))

        sums = _each_block(tile, transposed, (zero,) * TAPS)
        dtaps_ref[i] = jnp.concatenate([jnp.sum(total, axis=0, keepdims=True) for total in sums], axis=0)
    # what the tile before this one reads as its next rows
    dext_ref[:, tile:tile + _KEEP, :] = dext_ref[:, 0:_KEEP, :]


def _before_shape(q0, k0, v0):
    """(batch, positions, the grid's heads, lane tiles a grid step of each of
    q, k, v — None for one that is not in the call): the heads are q's, the
    key heads, and v's block is the value heads that read a key head — two
    lane tiles under Gated DeltaNet's 32 value heads over 16 key heads, one
    where every head has its own key."""
    b, seq, hd = next(x for x in (q0, k0, v0) if x is not None).shape
    return b, seq, hd // LANE, tuple(None if x is None else x.shape[2] // hd for x in (q0, k0, v0))


def _before_specs(tile: int, n_tiles: int, reverse: bool, lanes, decay, n_lanes: int):
    """(the inputs' block specs, and those of a position-major array, a
    head-major one and a grid step's partial sums as functions of r, the
    operand's lane tiles a grid step): an operand that is not in the call (r
    None; `decay` None where there is no decay a channel) has no spec."""
    from jax.experimental import pallas as pl

    at = (lambda s: n_tiles - 1 - s) if reverse else (lambda s: s)

    def of(block, index):
        return lambda r, *lead: None if r is None else pl.BlockSpec(
            block(r, *lead), lambda b, h, s: index(b, h, at(s), *lead))

    joined = of(lambda r: (None, tile, r * LANE), lambda b, h, s: (b, s, h))                          # of [B, S, H * r * D]
    halo = of(lambda r: (None, _HALO, r * LANE), lambda b, h, s: (b, jnp.maximum(s * (tile // _HALO) - 1, 0), h))
    major = of(lambda r: (None, None if r == 1 else r, tile, LANE), lambda b, h, s: (b, h, s, 0))      # of [B, H * r, S, D]
    taps = of(lambda n: (n, TAPS, LANE), lambda b, h, s: (0, 0, h))
    column = of(lambda r: (1, LANE), lambda b, h, s: (0, h))                                           # of [1, H * D]
    partial = of(lambda r, *lead: (None, None) + lead + (LANE,), lambda b, h, s, *lead: (b, s) + (0,) * len(lead) + (h,))
    # of q0, k0, v0, a, their three halos, the taps, the bias and the rate: what both directions read
    inputs = [joined(r) for r in lanes] + [joined(decay)] + [halo(r) for r in lanes] + [taps(n_lanes), column(decay), column(decay)]
    return inputs, joined, major, partial


@functools.partial(jax.jit, static_argnames=("tile", "interpret"), inline=True)
def _before_fwd_pallas(q0, k0, v0, a, taps, bias, rate, tile: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, seq, h, lanes = _before_shape(q0, k0, v0)
    decay, n_lanes = None if a is None else 1, taps.shape[0]
    inputs, _, major, _ = _before_specs(tile, seq // tile, False, lanes, decay, n_lanes)
    rows = lambda x, r, dtype=None: None if x is None else jax.ShapeDtypeStruct((b, h * r, seq, LANE), dtype or x.dtype)   # noqa: E731
    return pl.pallas_call(
        _before_fwd_kernel,
        out_shape=[rows(x, r) for x, r in zip((q0, k0, v0), lanes)] + [rows(a, decay, _F32)],
        grid=(b, h, seq // tile),
        in_specs=inputs,
        out_specs=[major(r) for r in lanes] + [major(decay)],
        scratch_shapes=[pltpu.VMEM((n_lanes, _KEEP + tile, LANE), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="tpuft_kdamix_fwd",
    )(q0, k0, v0, a, q0, k0, v0, taps, bias, rate)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"), inline=True)
def _before_bwd_pallas(q0, k0, v0, a, taps, bias, rate, dq, dk, dv, dg, tile: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, seq, h, lanes = _before_shape(q0, k0, v0)
    n, decay, n_lanes = seq // tile, None if a is None else 1, taps.shape[0]
    inputs, joined, major, partial = _before_specs(tile, n, True, lanes, decay, n_lanes)
    like = lambda x: None if x is None else jax.ShapeDtypeStruct(x.shape, x.dtype)   # noqa: E731
    sums = lambda r, *lead: None if r is None else jax.ShapeDtypeStruct((b, n) + lead + (h * LANE,), _F32)   # noqa: E731
    scratch = pltpu.VMEM((n_lanes, _KEEP + tile, LANE), _F32)
    dq0, dk0, dv0, da, dtaps, dbias, drate = pl.pallas_call(
        _before_bwd_kernel,
        out_shape=[like(q0), like(k0), like(v0), like(a), sums(1, n_lanes, TAPS), sums(decay, 1), sums(decay, 1)],
        grid=(b, h, n),
        in_specs=inputs + [major(r) for r in lanes] + [major(decay)],
        out_specs=[joined(r) for r in lanes] + [joined(decay), partial(1, n_lanes, TAPS), partial(decay, 1), partial(decay, 1)],
        scratch_shapes=[scratch, scratch],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="tpuft_kdamix_bwd",
    )(q0, k0, v0, a, q0, k0, v0, taps, bias, rate, dq, dk, dv, dg)
    total = lambda x: None if x is None else jnp.sum(x, axis=(0, 1))   # noqa: E731
    return dq0, dk0, dv0, da, total(dtaps), total(dbias), total(drate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _before(q0, k0, v0, a, taps, bias, rate, tile: int, interpret: bool):
    return tuple(_before_fwd_pallas(q0, k0, v0, a, taps, bias, rate, tile, interpret))


def _before_fwd(q0, k0, v0, a, taps, bias, rate, tile, interpret):
    return _before(q0, k0, v0, a, taps, bias, rate, tile, interpret), (q0, k0, v0, a, taps, bias, rate)


def _before_bwd(tile, interpret, res, cotangents):
    return _before_bwd_pallas(*res, *cotangents, tile, interpret)


_before.defvjp(_before_fwd, _before_bwd)


def before(q0: jax.Array, k0: jax.Array, v0: jax.Array, a: Optional[jax.Array], conv_q: jax.Array, conv_k: jax.Array,
           conv_v: jax.Array, a_log: Optional[jax.Array] = None, dt_bias: Optional[jax.Array] = None, *,
           tile: Optional[int] = None, interpret: bool = False) -> Tuple[jax.Array, ...]:
    """The scan's q, k [B, Hk, S, 128] and v [B, H, S, 128] in the projections'
    type from the projections q0, k0 [B, S, Hk * 128] and v0 [B, S, H * 128]
    and the convolutions' taps [4, Hk * 128], [4, H * 128] (the LAST tap the
    position's own); value head j reads key head ``j // (H // Hk)``, and the
    counts are the operands' shapes.  With a decay a CHANNEL (Kimi Delta
    Attention: ``a`` [B, S, H * 128], `A_log` [H], `dt_bias` [H * 128], H =
    Hk) also g [B, H, S, 128] float32; with ``a`` None (Gated DeltaNet, whose
    decay is a number a head and stays XLA's) q, k, v alone.  Differentiable
    in every array."""
    tile = tile_of(q0.shape[1], tile)
    assert tile is not None and q0.shape[2] % LANE == 0 and v0.shape[2] % q0.shape[2] == 0, (q0.shape, v0.shape)
    r = v0.shape[2] // q0.shape[2]
    # v's taps a value head of the key head's: [r, 4, Hk * D], row j the j-th value head's of every key head
    v_taps = conv_v.reshape(TAPS, -1, r, LANE).transpose(2, 0, 1, 3).reshape(r, TAPS, -1)
    taps = jnp.concatenate([jnp.stack([conv_q, conv_k]), v_taps]).astype(_F32)              # [2 + r, 4, Hk * D]
    if a is None:
        return _before(q0, k0, v0, None, taps, None, None, tile, interpret)[:3]
    rate = -jnp.repeat(jnp.exp(a_log.astype(_F32)), LANE)[None]                           # [1, H * D]
    return _before(q0, k0, v0, a, taps, dt_bias.astype(_F32)[None], rate, tile, interpret)


# -- after ----------------------------------------------------------------------


def _after_fwd_kernel(o_ref, gate_ref, norm_ref, bias_ref, out_ref, *, eps: float):
    norm, bias = norm_ref[...], None if bias_ref is None else bias_ref[...]

    def block(r0, rows, carry):
        at = _rows(r0, rows)
        o = o_ref[at, :].astype(_F32)
        y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * norm
        x = gate_ref[at, :].astype(_F32)
        gate = jax.nn.silu(x) if bias is None else jax.nn.sigmoid(x + bias)
        out_ref[at, :] = (y * gate).astype(out_ref.dtype)
        return carry

    _each_block(o_ref.shape[0], block)


def _after_bwd_kernel(o_ref, gate_ref, norm_ref, bias_ref, dout_ref, do_ref, dgate_ref, dnorm_ref, dbias_ref, *,
                      eps: float):
    norm, bias = norm_ref[...], None if bias_ref is None else bias_ref[...]
    zero = jnp.zeros((_KEEP, o_ref.shape[1]), _F32)

    def block(r0, rows, sums):
        at = _rows(r0, rows)
        o, dout = o_ref[at, :].astype(_F32), dout_ref[at, :].astype(_F32)
        inv = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        x = gate_ref[at, :].astype(_F32)
        sig = jax.nn.sigmoid(x if bias is None else x + bias)
        gate = x * sig if bias is None else sig
        unit = o * inv
        dy = dout * gate
        dpre = dout * (unit * norm) * (sig * (1.0 + x * (1.0 - sig)) if bias is None else gate * (1.0 - gate))
        dgate_ref[at, :] = dpre.astype(dgate_ref.dtype)
        dv = dy * norm
        do = inv * (dv - unit * jnp.mean(dv * unit, axis=-1, keepdims=True))
        do_ref[at, :] = do.astype(do_ref.dtype)
        return (sums[0] + _fold(dy * unit),) + (() if bias is None else (sums[1] + _fold(dpre),))

    sums = _each_block(o_ref.shape[0], block, (zero,) if bias is None else (zero, zero))
    dnorm_ref[...] = jnp.sum(sums[0], axis=0, keepdims=True)
    if bias is not None:
        dbias_ref[...] = jnp.sum(sums[1], axis=0, keepdims=True)


def _after_specs(tile: int):
    from jax.experimental import pallas as pl

    joined = pl.BlockSpec((None, tile, LANE), lambda b, h, s: (b, s, h))
    major = pl.BlockSpec((None, None, tile, LANE), lambda b, h, s: (b, h, s, 0))
    norm = pl.BlockSpec((1, LANE), lambda b, h, s: (0, 0))
    column = pl.BlockSpec((1, LANE), lambda b, h, s: (0, h))
    return joined, major, norm, column


@functools.partial(jax.jit, static_argnames=("eps", "tile", "interpret"), inline=True)
def _after_fwd_pallas(o, gate, norm, bias, eps: float, tile: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, seq, _ = o.shape
    joined, major, norm_spec, column = _after_specs(tile)
    return pl.pallas_call(
        functools.partial(_after_fwd_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(gate.shape, gate.dtype),
        grid=(b, h, seq // tile),
        in_specs=[major, joined, norm_spec, None if bias is None else column],
        out_specs=joined,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="tpuft_kdamix_out_fwd",
    )(o, gate, norm, bias)


@functools.partial(jax.jit, static_argnames=("eps", "tile", "interpret"), inline=True)
def _after_bwd_pallas(o, gate, norm, bias, dout, eps: float, tile: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, seq, _ = o.shape
    n = seq // tile
    joined, major, norm_spec, column = _after_specs(tile)
    by_head = pl.BlockSpec((None, None, None, 1, LANE), lambda b, h, s: (b, h, s, 0, 0))
    by_column = pl.BlockSpec((None, None, 1, LANE), lambda b, h, s: (b, s, 0, h))
    do, dgate, dnorm, dbias = pl.pallas_call(
        functools.partial(_after_bwd_kernel, eps=eps),
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype), jax.ShapeDtypeStruct(gate.shape, gate.dtype),
                   jax.ShapeDtypeStruct((b, h, n, 1, LANE), _F32),
                   None if bias is None else jax.ShapeDtypeStruct((b, n, 1, h * LANE), _F32)],
        grid=(b, h, n),
        in_specs=[major, joined, norm_spec, None if bias is None else column, joined],
        out_specs=[major, joined, by_head, None if bias is None else by_column],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="tpuft_kdamix_out_bwd",
    )(o, gate, norm, bias, dout)
    return do, dgate, jnp.sum(dnorm, axis=(0, 1, 2)), None if bias is None else jnp.sum(dbias, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _after(o, gate, norm, bias, eps: float, tile: int, interpret: bool):
    return _after_fwd_pallas(o, gate, norm, bias, eps, tile, interpret)


def _after_fwd(o, gate, norm, bias, eps, tile, interpret):
    return _after(o, gate, norm, bias, eps, tile, interpret), (o, gate, norm, bias)


def _after_bwd(eps, tile, interpret, res, dout):
    return _after_bwd_pallas(*res, dout, eps, tile, interpret)


_after.defvjp(_after_fwd, _after_bwd)


def after(o: jax.Array, gate: jax.Array, norm: jax.Array, g_bias: Optional[jax.Array], *, eps: float,
          tile: Optional[int] = None, interpret: bool = False) -> jax.Array:
    """The heads' joined output [B, S, H * 128] in the gate's type from the
    scan's o [B, H, S, 128], the gate's projection [B, S, H * 128] and the
    head norm's weight [128]: the normed head times ``sigmoid(gate + g_bias)``
    under a bias [H * 128] (Kimi Delta Attention's gate), times ``SiLU(gate)``
    where `g_bias` is None (Gated DeltaNet's, which has no bias).
    Differentiable in every array."""
    tile = tile_of(o.shape[2], tile)
    assert tile is not None and o.shape[3] == LANE, o.shape
    bias = None if g_bias is None else g_bias.astype(_F32)[None]
    return _after(o, gate, norm.astype(_F32)[None], bias, float(eps), tile, interpret)
