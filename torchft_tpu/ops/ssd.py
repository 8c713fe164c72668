"""Mamba-2's state-space recurrence (state-space duality, arXiv:2405.21060) as
a chunked scan: pallas TPU kernels forward and backward (`tpuft_ssd_fwd`,
`tpuft_ssd_bwd`) and the same chunk algebra in XLA.

The recurrence, a head h of width P in group g = h // (heads a group), with a
state S [N, P] in float32, zero before the first position, a scalar decay
``a_t = exp(la_t)`` a head (``la = dt * A <= 0``) and B_t, C_t [N] the group's:

    S_t = a_t S_{t-1} + B_t (dt_t x_t)^T,        y_t = S_t^T C_t

The caller hands over ``xdt = dt * x`` (the only form in which x enters) and
adds the skip ``D x`` itself.  What runs is the re-association over chunks of
``chunk`` positions, with c the running sum of la inside the chunk:

    Y = ((C B^T) * L) XDT + exp(c) * (C S),      L[t, s] = exp(c_t - c_s), s <= t
    S' = exp(c_last) S + B^T (exp(c_last - c) * XDT)

Every exponent is a sum of la over a range of rows, so <= 0: nothing overflows
and a fast decay underflows to the zero it means.  The sums c are made in XLA
(float32, exact) and enter twice, positions down the rows and across the lanes.

**The grid.**  A step carries ONE GROUP'S chunk: all of the group's heads side
by side on the lanes, as the in-projection lays them ([B, S, H * P] is read
and written in place: no transpose to head-major, and no head of 64 columns
padded to a 128-lane tile).  The group's heads share B and C, so ``C B^T``,
``C S`` and ``B^T (w * XDT)`` are one product each for all of them; only the
[chunk, chunk] decay mask differs a head.  A head's columns are picked by a
lane mask inside blocks of 128 lanes and never sliced: a product over half a
block costs the MXU what the whole block does.  Grid (batch * groups, chunks),
the state [N, heads a group * P] a float32 scratch that the chunks carry — the
walk `ops/delta_attention.py` has: forward, a forward that also writes every
chunk's incoming state (the backward's only, made again and never kept),
backward over the chunks in reverse with dS carried.

**Types.**  The state, c, every exponent and all accumulation are float32;
the products' operands are cast to the compute type (xdt's).  With float32
inputs everything is float32 at the highest precision: what the CPU tests
compare with the loop.

The XLA form (`_forward_xla`, `_backward_xla`) runs the same two chunk
functions under `lax.scan`: off the TPU, under a multi-device mesh, for shapes
the kernels do not tile, and in the tests.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops import _pallas_util
from torchft_tpu.ops.delta_attention import _F32, _NN, _NT, _TN, _dot

CHUNK = 128
_VMEM_LIMIT = 48 * 2 ** 20

# What a rematerialised block keeps so that its backward pass does not run the
# forward kernel a third time (the gated norm after the scan reads the output
# in ITS backward).  The chunks' states are never kept.
SAVED_NAMES = ("tpuft_ssd_out",)


# -- a group's heads on the lanes ------------------------------------------------


def _blocks(width: int, p: int) -> Tuple[int, int]:
    """(lanes a block, heads a block): blocks of 128 lanes where the width
    allows, else the width as one block."""
    lanes = _pallas_util.LANE if width % _pallas_util.LANE == 0 and _pallas_util.LANE % p == 0 else width
    return lanes, lanes // p


def _head_of_lane(shape, p: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) // p


def _column(a, h: int):
    """Column h of a [rows, heads] as [rows, 1]: a masked sum over the lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.sum(jnp.where(lane == h, a, 0.0), axis=1, keepdims=True)


def _wide(a, p: int, width: int):
    """a [rows, heads] -> [rows, heads * p]: each head's number over its p lanes."""
    lanes, k = _blocks(width, p)
    out = []
    for b in range(width // lanes):
        head = _head_of_lane((a.shape[0], lanes), p)
        block = jnp.zeros((a.shape[0], lanes), _F32)
        for i in range(k):
            block = jnp.where(head == i, _column(a, b * k + i), block)
        out.append(block)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _narrow(a, p: int):
    """a [rows, heads * p] -> [rows, heads]: the sum over each head's p lanes."""
    rows, width = a.shape
    lanes, k = _blocks(width, p)
    heads = width // p
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, heads), 1)
    out = jnp.zeros((rows, heads), _F32)
    for b in range(width // lanes):
        block = a[:, b * lanes:(b + 1) * lanes]
        head = _head_of_lane(block.shape, p)
        for i in range(k):
            total = jnp.sum(jnp.where(head == i, block, 0.0), axis=1, keepdims=True)
            out = jnp.where(lane == b * k + i, total, out)
    return out


def _decay_mask(c_col, c_row, h: int):
    """L of head h: exp(c_t - c_s) where s <= t, else 0."""
    chunk = c_col.shape[0]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    return jnp.where(lower, jnp.exp(jnp.minimum(_column(c_col, h) - c_row[h:h + 1, :], 0.0)), 0.0)


# -- one chunk of one group -----------------------------------------------------


def _forward_chunk(state, xdt, bm, cm, c_col, c_row, p: int, dtype):
    """(y [C, W] float32, the state after the chunk).  state [N, W] float32;
    xdt [C, W], bm, cm [C, N] in the compute type; c_col [C, heads], c_row
    [heads, C] float32, the running sum of the log decay inside the chunk."""
    chunk, width = xdt.shape
    lanes, k = _blocks(width, p)
    g = _dot(cm, bm, _NT, dtype)                                                  # C B^T [C, C]
    y_state = _wide(jnp.exp(c_col), p, width) * _dot(cm, state, _NN, dtype)
    to_end = jnp.exp(c_col[chunk - 1:chunk] - c_col)                              # exp(c_last - c_s) [C, heads]
    after = (state * _wide(jnp.exp(c_col[chunk - 1:chunk]), p, width)
             + _dot(bm, xdt.astype(_F32) * _wide(to_end, p, width), _TN, dtype))
    inside = []
    for b in range(width // lanes):
        block = xdt[:, b * lanes:(b + 1) * lanes]
        head = _head_of_lane(block.shape, p)
        acc = jnp.zeros(block.shape, _F32)
        for i in range(k):
            m = g * _decay_mask(c_col, c_row, b * k + i)
            acc = acc + _dot(m, jnp.where(head == i, block, jnp.zeros_like(block)), _NN, dtype)
        inside.append(acc)
    y = inside[0] if len(inside) == 1 else jnp.concatenate(inside, axis=1)
    return y + y_state, after


def _backward_chunk(state, dstate, xdt, bm, cm, c_col, c_row, dy, p: int, dtype):
    """The chunk's gradients from its incoming ``state`` [N, W], the gradient
    ``dstate`` of the state it hands on and ``dy`` [C, W]: (dxdt [C, W], dbm,
    dcm [C, N], dc_col [C, heads], dc_row [heads, C] — dc is the sum of the
    two —, the incoming state's gradient), all float32."""
    chunk, width = xdt.shape
    lanes, k = _blocks(width, p)
    heads = width // p
    xf, dyf = xdt.astype(_F32), dy.astype(_F32)
    g = _dot(cm, bm, _NT, dtype)
    # Y_state = exp(c) * (C S)
    decay = _wide(jnp.exp(c_col), p, width)
    dz = decay * dyf
    dcm = _dot(dz, state, _NT, dtype)
    dstate_in = _dot(cm, dz, _TN, dtype)
    dc_col = _narrow(dz * _dot(cm, state, _NN, dtype), p)
    # S' = exp(c_last) S + B^T (exp(c_last - c) * XDT)
    last = jnp.exp(c_col[chunk - 1:chunk])                                        # [1, heads]
    to_end = _wide(jnp.exp(c_col[chunk - 1:chunk] - c_col), p, width)
    xw = xf * to_end
    dstate_in = dstate_in + dstate * _wide(last, p, width)
    dbm = _dot(xw, dstate, _NT, dtype)
    dxw = _dot(bm, dstate, _NN, dtype)
    dxdt_state = dxw * to_end
    q = _narrow(dxw * xw, p)                                                      # the gradient of (c_last - c_s)
    dlast = _narrow(jnp.sum(dstate * state, axis=0, keepdims=True), p) * last + jnp.sum(q, axis=0, keepdims=True)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, heads), 0)
    dc_col = dc_col - q + jnp.where(row == chunk - 1, dlast, 0.0)
    # Y_inside = ((C B^T) * L_h) XDT_h, a head at a time
    dg = jnp.zeros((chunk, chunk), _F32)
    dc_row = jnp.zeros((heads, chunk), _F32)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk), 0)
    head_col = jax.lax.broadcasted_iota(jnp.int32, (chunk, heads), 1)
    inside = []
    for b in range(width // lanes):
        block, dblock = xdt[:, b * lanes:(b + 1) * lanes], dy[:, b * lanes:(b + 1) * lanes]
        head = _head_of_lane(block.shape, p)
        acc = jnp.zeros(block.shape, _F32)
        for i in range(k):
            h = b * k + i
            decay_h = _decay_mask(c_col, c_row, h)
            m = g * decay_h
            dy_h = jnp.where(head == i, dblock, jnp.zeros_like(dblock))
            dm = _dot(dy_h, block, _NT, dtype)                                    # dY_h XDT_h^T [C, C]
            acc = acc + _dot(m, dy_h, _TN, dtype)
            dg = dg + dm * decay_h
            pairs = dm * m                                                        # the gradient of (c_t - c_s), pair by pair
            dc_col = jnp.where(head_col == h, dc_col + jnp.sum(pairs, axis=1, keepdims=True), dc_col)
            dc_row = jnp.where(head_row == h, dc_row - jnp.sum(pairs, axis=0, keepdims=True), dc_row)
        inside.append(acc)
    dxdt = (inside[0] if len(inside) == 1 else jnp.concatenate(inside, axis=1)) + dxdt_state
    dcm = dcm + _dot(dg, bm, _NN, dtype)
    dbm = dbm + _dot(dg, cm, _TN, dtype)
    return dxdt, dbm, dcm, dc_col, dc_row, dstate_in


# -- the XLA form ---------------------------------------------------------------


def _by_chunk(a):
    """[B, G, n, ...] -> [n, B * G, ...]."""
    return jnp.moveaxis(a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), 1, 0)


def _rows(a, groups: int, chunk: int):
    """[B, S, G * W] -> [n, B * G, chunk, W]: a chunk and group's rows."""
    b, seq, _ = a.shape
    return _by_chunk(jnp.moveaxis(a.reshape(b, seq // chunk, chunk, groups, -1), 3, 1))


def _sums(c_col, c_row, chunk: int):
    """The running sums a chunk and group: [n, B * G, chunk, heads] and [n, B * G, heads, chunk]."""
    b, groups, seq, heads = c_col.shape
    return _by_chunk(c_col.reshape(b, groups, seq // chunk, chunk, heads)), _by_chunk(c_row)


def _unchunked(a, b: int):
    """[n, B * G, chunk, W] -> [B, S, G * W]."""
    n, bg, chunk, width = a.shape
    a = jnp.moveaxis(a, 0, 1).reshape(b, bg // b, n, chunk, width)
    return jnp.moveaxis(a, 1, 3).reshape(b, n * chunk, (bg // b) * width)


def _forward_xla(xdt, bm, cm, c_col, c_row, p: int, chunk: int, with_states: bool):
    dtype, b = xdt.dtype, xdt.shape[0]
    one = jax.vmap(functools.partial(_forward_chunk, p=p, dtype=dtype))

    def step(state, xs):
        y, after = one(state, *xs)
        return after, (y.astype(dtype), state if with_states else None)

    groups = c_col.shape[1]
    xs = tuple(_rows(a, groups, chunk) for a in (xdt, bm, cm)) + _sums(c_col, c_row, chunk)
    start = jnp.zeros((xs[0].shape[1], bm.shape[2] // groups, xs[0].shape[3]), _F32)
    _, (y, states) = jax.lax.scan(step, start, xs)
    return _unchunked(y, b), states                                               # states [n, B * G, N, W]


def _backward_xla(xdt, bm, cm, c_col, c_row, states, dy, p: int, chunk: int):
    dtype, b = xdt.dtype, xdt.shape[0]
    groups = c_col.shape[1]
    one = jax.vmap(functools.partial(_backward_chunk, p=p, dtype=dtype))

    def step(dstate, xs):
        state, x, bb, cc, col, row, d = xs
        dx, dbm, dcm, dcol, drow, dstate = one(state, dstate, x, bb, cc, col, row, d)
        return dstate, (dx.astype(dtype), dbm.astype(dtype), dcm.astype(dtype), dcol, drow)

    x, bb, cc, d = (_rows(a, groups, chunk) for a in (xdt, bm, cm, dy))
    col, row = _sums(c_col, c_row, chunk)
    _, (dx, dbm, dcm, dcol, drow) = jax.lax.scan(step, jnp.zeros_like(states[0]), (states, x, bb, cc, col, row, d),
                                                 reverse=True)
    n = dx.shape[0]
    dcol = jnp.moveaxis(dcol, 0, 1).reshape(b, groups, n * chunk, -1)
    drow = jnp.moveaxis(drow, 0, 1).reshape(b, groups, n, -1, chunk)
    return _unchunked(dx, b), _unchunked(dbm, b), _unchunked(dcm, b), dcol, drow


# -- the kernels ----------------------------------------------------------------


def _fwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, y_ref, *rest, p, dtype, with_states):
    from jax.experimental import pallas as pl

    states_ref, state_scr = rest if with_states else (None, rest[0])

    @pl.when(pl.program_id(1) == 0)
    def _start():
        state_scr[...] = jnp.zeros_like(state_scr)

    state = state_scr[...]
    if with_states:
        states_ref[...] = state
    y, after = _forward_chunk(state, x_ref[...], b_ref[...], c_ref[...], col_ref[...], row_ref[...], p, dtype)
    y_ref[...] = y.astype(y_ref.dtype)
    state_scr[...] = after


def _bwd_kernel(x_ref, b_ref, c_ref, col_ref, row_ref, states_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dcol_ref, drow_ref, dstate_scr, *, p, dtype):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)

    dx, db, dc, dcol, drow, dstate = _backward_chunk(
        states_ref[...], dstate_scr[...], x_ref[...], b_ref[...], c_ref[...], col_ref[...], row_ref[...], dy_ref[...],
        p, dtype)
    dx_ref[...], db_ref[...], dc_ref[...] = dx.astype(dx_ref.dtype), db.astype(db_ref.dtype), dc.astype(dc_ref.dtype)
    dcol_ref[...], drow_ref[...] = dcol, drow
    dstate_scr[...] = dstate


def _specs(groups: int, heads: int, chunk: int, width: int, n_state: int, n_chunks: int, reverse: bool):
    """Block specs of a grid step (batch * groups, chunks): one group's chunk
    j, read in place out of [B, S, G * .]; the backward walks the chunks from
    the last."""
    from jax.experimental import pallas as pl

    at = (lambda j: n_chunks - 1 - j) if reverse else (lambda j: j)
    rows = lambda w: pl.BlockSpec((None, chunk, w), lambda i, j: (i // groups, at(j), i % groups))     # noqa: E731
    col = pl.BlockSpec((None, None, chunk, heads), lambda i, j: (i // groups, i % groups, at(j), 0))
    row = pl.BlockSpec((None, None, None, heads, chunk), lambda i, j: (i // groups, i % groups, at(j), 0, 0))
    state = pl.BlockSpec((None, None, n_state, width), lambda i, j: (at(j), i, 0, 0))
    return rows, col, row, state


def _fwd_pallas(xdt, bm, cm, c_col, c_row, p: int, chunk: int, with_states: bool, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, seq, _ = xdt.shape
    groups, heads = c_col.shape[1], c_col.shape[3]
    width, n_state, n = heads * p, bm.shape[2] // groups, seq // chunk
    rows, col, row, state = _specs(groups, heads, chunk, width, n_state, n, reverse=False)
    out_shape, out_specs = [jax.ShapeDtypeStruct(xdt.shape, xdt.dtype)], [rows(width)]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((n, b * groups, n_state, width), _F32))
        out_specs.append(state)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, dtype=xdt.dtype, with_states=with_states),
        out_shape=out_shape,
        grid=(b * groups, n),
        in_specs=[rows(width), rows(n_state), rows(n_state), col, row],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n_state, width), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_ssd_fwd",
    )(xdt, bm, cm, c_col, c_row)
    return out[0], (out[1] if with_states else None)


def _bwd_pallas(xdt, bm, cm, c_col, c_row, states, dy, p: int, chunk: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, seq, _ = xdt.shape
    groups, heads = c_col.shape[1], c_col.shape[3]
    width, n_state, n = heads * p, bm.shape[2] // groups, seq // chunk
    rows, col, row, state = _specs(groups, heads, chunk, width, n_state, n, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, dtype=xdt.dtype),
        out_shape=[jax.ShapeDtypeStruct(xdt.shape, xdt.dtype), jax.ShapeDtypeStruct(bm.shape, bm.dtype),
                   jax.ShapeDtypeStruct(cm.shape, cm.dtype), jax.ShapeDtypeStruct(c_col.shape, _F32),
                   jax.ShapeDtypeStruct(c_row.shape, _F32)],
        grid=(b * groups, n),
        in_specs=[rows(width), rows(n_state), rows(n_state), col, row, state, rows(width)],
        out_specs=[rows(width), rows(n_state), rows(n_state), col, row],
        scratch_shapes=[pltpu.VMEM((n_state, width), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_ssd_bwd",
    )(xdt, bm, cm, c_col, c_row, states, dy)


# -- the call -------------------------------------------------------------------


def _forward(xdt, bm, cm, c_col, c_row, p, chunk, kernel, with_states):
    if kernel:
        return _fwd_pallas(xdt, bm, cm, c_col, c_row, p, chunk, with_states, interpret=kernel == "interpret")
    return _forward_xla(xdt, bm, cm, c_col, c_row, p, chunk, with_states)


# `kernel` (False, True or "interpret") is decided once, in `ssd`, so that
# forward and backward cannot disagree.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd(xdt, bm, cm, c_col, c_row, p: int, chunk: int, kernel):
    return _forward(xdt, bm, cm, c_col, c_row, p, chunk, kernel, with_states=False)[0]


def _ssd_fwd(xdt, bm, cm, c_col, c_row, p, chunk, kernel):
    from jax.ad_checkpoint import checkpoint_name

    y = checkpoint_name(_ssd(xdt, bm, cm, c_col, c_row, p, chunk, kernel), SAVED_NAMES[0])
    return y, (xdt, bm, cm, c_col, c_row)


def _ssd_bwd(p, chunk, kernel, res, dy):
    xdt, bm, cm, c_col, c_row = res
    # the chunks' incoming states, made again: float32 [chunks, B * G, N, W], alive for this call alone
    _, states = _forward(xdt, bm, cm, c_col, c_row, p, chunk, kernel, with_states=True)
    if kernel:
        return tuple(_bwd_pallas(xdt, bm, cm, c_col, c_row, states, dy, p, chunk, interpret=kernel == "interpret"))
    return _backward_xla(xdt, bm, cm, c_col, c_row, states, dy, p, chunk)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def applies(head_dim: int, heads_a_group: int, state: int, mesh=None) -> bool:
    """Whether the `tpuft_ssd_*` kernels run these shapes: a TPU's program
    over one device, a group's heads a whole number of 128-lane blocks that a
    head divides, the state's rows whole lane tiles."""
    lane = _pallas_util.LANE
    return (lane % head_dim == 0 and (heads_a_group * head_dim) % lane == 0 and state % lane == 0
            and _pallas_util.kernels_apply(mesh))


def ssd(xdt: jax.Array, bm: jax.Array, cm: jax.Array, la: jax.Array, *, head_dim: int, groups: int,
        chunk: int = CHUNK, mesh=None, interpret: bool = False) -> jax.Array:
    """The state-space recurrence over a sequence, position-major as the
    in-projection lays it: xdt [B, S, H * P] (dt * x, a head's P columns side by
    side, a group's heads side by side) and bm, cm [B, S, G * N] in the compute
    type, la [B, S, H] float32 <= 0 the log of the decay a head -> y [B, S, H *
    P] in xdt's type, WITHOUT the skip D x.  The state before the first position
    is zero.  A sequence that ``chunk`` does not divide is padded at its end
    with positions that write nothing (xdt = 0, la = 0) and whose outputs are
    cut away."""
    b, seq, _ = xdt.shape
    heads = la.shape[2]
    per_group, pad = heads // groups, -seq % chunk
    assert heads % groups == 0 and xdt.shape[2] == heads * head_dim and bm.shape == cm.shape
    la = la.astype(_F32)
    if pad:
        xdt, bm, cm, la = (jnp.pad(a, [(0, 0), (0, pad), (0, 0)]) for a in (xdt, bm, cm, la))
    n = (seq + pad) // chunk
    c = jnp.cumsum(la.reshape(b, n, chunk, groups, per_group), axis=2)            # the running sum inside a chunk
    c_col = jnp.moveaxis(c, 3, 1).reshape(b, groups, n * chunk, per_group)        # [B, G, S, heads a group]
    c_row = jnp.transpose(c, (0, 3, 1, 4, 2))                                     # [B, G, n, heads a group, chunk]
    kernel: Any = "interpret" if interpret else applies(head_dim, per_group, bm.shape[2] // groups, mesh)
    y = _ssd(xdt, bm, cm, c_col, c_row, head_dim, chunk, kernel)
    return y[:, :seq]


def ssd_loop(xdt, bm, cm, la, *, head_dim: int, groups: int) -> Tuple[jax.Array, jax.Array]:
    """The recurrence position by position, float32: (y [B, S, H * P], the last
    state [B, H, N, P]).  The tests' yardstick for the chunk form; no program
    runs it."""
    b, seq, _ = xdt.shape
    heads = la.shape[2]
    x = jnp.moveaxis(xdt.astype(_F32).reshape(b, seq, heads, head_dim), 1, 0)     # [S, B, H, P]
    per_group = heads // groups
    bb, cc = (jnp.repeat(jnp.moveaxis(a.astype(_F32).reshape(b, seq, groups, -1), 1, 0), per_group, axis=2)
              for a in (bm, cm))                                                  # [S, B, H, N]

    def step(state, xs):
        xt, bt, ct, lt = xs
        state = state * jnp.exp(lt)[..., None, None] + bt[..., :, None] * xt[..., None, :]
        return state, jnp.einsum("bhn,bhnp->bhp", ct, state)

    start = jnp.zeros((b, heads, bb.shape[3], head_dim), _F32)
    with jax.default_matmul_precision("highest"):
        last, y = jax.lax.scan(step, start, (x, bb, cc, jnp.moveaxis(la.astype(_F32), 1, 0)))
    return jnp.moveaxis(y, 0, 1).reshape(b, seq, heads * head_dim), last
