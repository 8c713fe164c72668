"""The gated delta rule's scan — Kimi Delta Attention's, a decay a channel
(arXiv:2510.26692), and Gated DeltaNet's, a decay a head under shared key
heads (arXiv:2412.06464) — chunk by chunk, as pallas TPU kernels forward and backward
(`tpuft_kda_fwd`, `tpuft_kda_bwd`) and as the same chunk algebra in XLA.

The recurrence, a head, with a state S [keys, values] in float32 and zero
before the first position:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,    o_t = S_t^T q_t

g_t <= 0 is the log of the decay, a number a channel of the key.  What runs is
its re-association over chunks of ``CHUNK`` positions (`_forward_chunk`), with
G_r the sum of g over the chunk's rows up to r:

    R[r, i]  = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c]),  i < r        (Rq the same with q_r, i <= r)
    M = (I + Diag(beta) R)^-1,   T = M Diag(beta)
    W = T (K * exp(G)),   U = T V,   D = U - W S
    O = (Q * exp(G)) S + Rq D,   S' = Diag(exp(G_last)) S + (K * exp(G_last - G))^T D

**Exponents stay bounded.**  No `exp(-G)` is ever formed: every decay is the
exponential of a sum of g over a range of rows, which is <= 0.  The pair
(r, i), i < r, is taken at the LEVEL of the highest bit in which r and i
differ: they lie in one block of 2h rows, i in its lower half and r in its
upper, and with b the lower half's last row `exp(G_r - G_i) = exp(G_r - G_b)
exp(G_b - G_i)`, both factors in (0, 1].  So R is log2(CHUNK) masked products
of (K * u_level)(K * w_level)^T, all on the MXU, and g = -20 a position
underflows to the zero it means instead of overflowing.  The sums of g (G, the
levels' exponents, the sum to the chunk's end) are products of constant 0/1
matrices with g, exact in float32 by three bfloat16 passes (`_sum01`).

**A decay a head.**  Gated DeltaNet's decay is ONE number a head and position
(`kda`'s g of rank 3), and its q and k are shared by the value heads of a key
head.  With a scalar, `exp(G_r - G_i)` is one [C, C] matrix a head and chunk:
R = (K K^T) o exp(G_r - G_i) and Rq are ONE masked product each where the
channel form takes log2(CHUNK) = 6, the difference is <= 0 wherever the mask
is 1 (masked BEFORE the `exp`, so nothing overflows and no level is needed),
the level sums of `_sum01` fall away, `W = T (K e^G)`, `S' = e^{G_last} S +
(K e^{G_last - G})^T D`, and dg is a [C] vector a head.  That form is NOT what
runs yet: `kda` broadcasts the head's decay over the key's 128 channels and
repeats a key head for its value heads (float32 [H, S, K] of g, and of dg, a
layer), and runs the channel form below — correct by construction, and paying
for what the channel decay alone needs.  What is left — the chunk functions
reading the decay's rank off their operand, and the q / k blocks' index map
reading key head j // 2 in place — is PERF.md section 7's first item for the
cell that runs it.

**Types.**  The state, g, G, every exponent, R, M and all accumulation are
float32.  The operands of the products with a head-wide side (R's, W, U, D, O
and the state's update, and their transposes in the backward) are cast to the
compute type, the type q arrives in (bfloat16 in the benchmark's
configuration, where the state is rounded as an operand and kept in float32).
M comes from five squarings of the strictly lower matrix (`_solve`:
(I + N)(I + N^2)...(I + N^32), N = -Diag(beta) R, nilpotent), in float32 by
three bfloat16 passes (`_precise`).  With float32 inputs everything is float32
at the highest precision: what the CPU tests compare with the loop.

**Backward** (`_backward_chunk`): the chunks in reverse with dS carried; a
chunk's incoming state is made again by a forward pass of the kernel that
writes every chunk's (the backward's only: `kda`'s primal call writes none),
and everything inside the chunk is recomputed from q, k, v, g, beta.  It gives
dq, dk, dv, dg, dbeta.  Nothing is kept for the backward beside the inputs.

**The grid.**  A grid step of either kernel carries H heads' chunk j, not one
head's: grid (heads / H, chunks), blocks [H, CHUNK, width], the carried state a
scratch [H, V, K].  Why: a head's chunk is a chain of 11 dependent small
products forward and 18 backward (the sums of g, a level, R, five squarings one
after another, T, W and U, D, O and the state), each waiting for the one before
— half of a one-head step's cycles were stalls on results in flight — and the
heads are independent recurrences.  The two chunk functions run over the H heads
under `jax.vmap` (`_heads_at_once`), so that every product is one batched
product and the heads' chains stand side by side in the program's order, which
the scheduler keeps.  H is read from the shape (`_heads_per_step`: the largest
divisor of the heads not above ``HEADS_PER_STEP``), so 4 or 5 heads run the same
code as 32.

The XLA form (`_forward_xla`, `_backward_xla`) runs the same two chunk functions under `lax.scan`:
off the TPU, under a multi-device mesh and in the tests, which compare it with
the recurrence position by position and the kernels (``interpret``) with it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.ops import _pallas_util

CHUNK = 64
_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


# -- constants of a chunk size ------------------------------------------------


@functools.lru_cache(maxsize=None)
def _constants(chunk: int) -> Dict[str, np.ndarray]:
    """The 0/1 matrices of a chunk of ``chunk`` rows (a power of two):

    ``sums`` [(2 + levels) * chunk, chunk], stacked: the inclusive lower
    triangle (G); level by level a block whose row r holds G_r - G_b where r
    lies in an upper half and G_b - G_r where it lies in a lower one (a row is
    a level's u or its w, never both); and the strict upper triangle (the sum
    to the chunk's end).  ``masks`` [levels *
    chunk, chunk]: the pairs (r, i) of each level.  ``back`` [chunk, 2 *
    chunk]: the transposes of the first and the last block of ``sums`` side
    by side, which turn dG and the end sum's gradient into dg."""
    assert chunk >= 2 and chunk & (chunk - 1) == 0, "a chunk is a power of two"
    r, t = np.arange(chunk)[:, None], np.arange(chunk)[None, :]
    levels, masks = [], []
    h = 1
    while h < chunk:
        same = (r // (2 * h)) == (t // (2 * h))
        upper_r, upper_t = (r % (2 * h)) >= h, (t % (2 * h)) >= h
        levels.append(same & (upper_r & upper_t & (t <= r)          # r above b: b < t <= r
                              | ~upper_r & ~upper_t & (t > r)))     # r up to b: r < t <= b
        masks.append(same & upper_r & ~upper_t)              # [r, i]
        h *= 2
    sums = np.concatenate([t <= r] + levels + [t > r]).astype(np.float32)
    back = np.concatenate([(t >= r), (t < r)], axis=1).astype(np.float32)
    return {"sums": sums, "masks": np.concatenate(masks).astype(np.float32), "back": back}


def _levels(chunk: int) -> int:
    return chunk.bit_length() - 1


# -- products -------------------------------------------------------------------


def _dot(a, b, dims, dtype):
    """A product with its operands in ``dtype`` and float32 accumulation."""
    precision = jax.lax.Precision.HIGHEST if dtype == _F32 else None
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims, precision=precision,
                               preferred_element_type=_F32)


def _split(x, parts: int):
    """x as a sum of ``parts`` bfloat16 arrays, largest first."""
    out = []
    for _ in range(parts):
        piece = x.astype(jnp.bfloat16)
        out.append(piece)
        x = x - piece.astype(_F32)
    return out


def _sum01(ones, x, dtype):
    """``ones`` (entries 0 and 1) times the float32 x, exact to float32: three
    bfloat16 passes (8 + 8 + 8 bits of x; 0 and 1 are exact) where the compute
    type is bfloat16."""
    if dtype == _F32:
        return _dot(ones, x, _NN, _F32)
    ones = ones.astype(jnp.bfloat16)
    return sum(_dot(ones, piece, _NN, jnp.bfloat16) for piece in _split(x, 3))


def _precise(a, b, dims, dtype):
    """A product of two float32 matrices to about 2**-16: high x high, high x
    low and low x high in bfloat16 where the compute type is bfloat16."""
    if dtype == _F32:
        return _dot(a, b, dims, _F32)
    (ah, al), (bh, bl) = _split(a, 2), _split(b, 2)
    return _dot(ah, bh, dims, jnp.bfloat16) + _dot(ah, bl, dims, jnp.bfloat16) + _dot(al, bh, dims, jnp.bfloat16)


def _solve(a, dtype):
    """(I + a)^-1 for a strictly lower triangular [C, C]: with N = -a, nilpotent,
    (I + N)(I + N^2)(I + N^4)... up to the power C / 2."""
    chunk = a.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, a.shape, 0) == jax.lax.broadcasted_iota(jnp.int32, a.shape, 1))
    power = -a
    inverse = eye.astype(_F32) + power
    for _ in range(_levels(chunk) - 1):
        power = _precise(power, power, _NN, dtype)
        inverse = inverse + _precise(inverse, power, _NN, dtype)
    return inverse


# -- one chunk ------------------------------------------------------------------


def _inside(q, k, v, g, beta_col, beta_row, consts, dtype):
    """What a chunk computes from its own rows alone: the decays, R and Rq by
    levels, M, T, W, U.  q, k [C, K], v [C, V] in the compute type; g [C, K],
    beta_col [C, 1], beta_row [1, C] float32."""
    chunk, n = q.shape[0], _levels(q.shape[0])
    sums = _sum01(consts["sums"], g, dtype)                      # [(2 + n) C, K]
    block = lambda i: sums[i * chunk:(i + 1) * chunk]            # noqa: E731
    decay = jnp.exp(block(0))                                    # exp(G): to the chunk's start
    to_end = jnp.exp(block(1 + n))                           # exp(G_last - G)
    last = jnp.exp(block(0)[chunk - 1:chunk])                    # exp(G_last) [1, K]
    kf, qf = k.astype(_F32), q.astype(_F32)
    r = jnp.zeros((chunk, chunk), _F32)
    rq = jnp.zeros((chunk, chunk), _F32)
    levels = []
    for level in range(n):
        e = jnp.exp(block(1 + level))                            # row r: u_r above the level's b, w_r up to it
        mask = consts["masks"][level * chunk:(level + 1) * chunk]
        ke, qe = kf * e, qf * e
        both = _dot(jnp.concatenate([ke, qe], axis=0), ke, _NT, dtype)   # [2C, C]
        r = r + mask * both[:chunk]
        rq = rq + mask * both[chunk:]
        levels.append((e, mask, ke, qe))
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = (row == col).astype(_F32)
    rq = rq + eye * jnp.sum(qf * kf, axis=1, keepdims=True)      # the pair (r, r): no decay between
    inverse = _solve(beta_col * r, dtype)                        # M
    t = inverse * beta_row
    kg, qg, kend = kf * decay, qf * decay, kf * to_end
    w_ = _dot(t, kg, _NN, dtype)
    u_ = _dot(t, v, _NN, dtype)
    return dict(decay=decay, to_end=to_end, last=last, r=r, rq=rq, inverse=inverse, t=t, kg=kg, qg=qg, kend=kend,
                w=w_, u=u_, levels=levels, eye=eye, lower=(col < row).astype(_F32), kf=kf, qf=qf)


def _forward_chunk(state, q, k, v, g, beta_col, beta_row, consts, dtype):
    """(o [C, V] float32, the state after the chunk); ``state`` [V, K]
    float32 is S TRANSPOSED, so that a decay a key channel is a factor a lane."""
    c = _inside(q, k, v, g, beta_col, beta_row, consts, dtype)
    d = c["u"] - _dot(c["w"], state, _NT, dtype)
    o = _dot(c["qg"], state, _NT, dtype) + _dot(c["rq"], d, _NN, dtype)
    return o, state * c["last"] + _dot(d, c["kend"], _TN, dtype)


def _backward_chunk(state, dstate, q, k, v, g, beta_col, beta_row, do, consts, dtype):
    """The chunk's gradients from its incoming ``state`` [V, K], the gradient
    ``dstate`` of the state it hands on and ``do`` [C, V]: (dq, dk, dv, dg
    [C, .] float32, dbeta_col [C, 1], dbeta_row [1, C], the incoming state's
    gradient).  dbeta is the sum of the two."""
    chunk = q.shape[0]
    c = _inside(q, k, v, g, beta_col, beta_row, consts, dtype)
    kf, qf, decay = c["kf"], c["qf"], c["decay"]
    d = c["u"] - _dot(c["w"], state, _NT, dtype)
    # O = Qg S + Rq D;  S' = Diag(last) S + Kend^T D
    dd = _dot(c["rq"], do, _TN, dtype) + _dot(c["kend"], dstate, _NT, dtype)     # [C, V]
    dqg = _dot(do, state, _NN, dtype)                                             # [C, K]
    drq = _dot(do, d, _NT, dtype) * (c["lower"] + c["eye"])                       # [C, C]
    dkend = _dot(d, dstate, _NN, dtype)                                           # [C, K]
    dlast = jnp.sum(dstate * state, axis=0, keepdims=True)                        # [1, K]
    dstate_in = dstate * c["last"] + _dot(do, c["qg"], _TN, dtype)
    # D = U - W S;  W = T Kg;  U = T V
    dw = -_dot(dd, state, _NN, dtype)                                             # [C, K]
    dstate_in = dstate_in - _dot(dd, c["w"], _TN, dtype)
    dt = _dot(dw, c["kg"], _NT, dtype) + _dot(dd, v, _NT, dtype)                  # [C, C]
    dkg = _dot(c["t"], dw, _TN, dtype)
    dv = _dot(c["t"], dd, _TN, dtype)
    # T = M Diag(beta);  M = (I + A)^-1;  A = Diag(beta) R
    dbeta_row = jnp.sum(dt * c["inverse"], axis=0, keepdims=True)
    dm = dt * beta_row
    da = -_precise(_precise(c["inverse"], dm, _TN, dtype), c["inverse"], _NT, dtype) * c["lower"]
    dbeta_col = jnp.sum(da * c["r"], axis=1, keepdims=True)
    dr = da * beta_col
    # R and Rq, level by level: the pair's decay is u_r w_i, both rows of the level's e
    dq = dqg * decay
    dk = dkg * decay + dkend * c["to_end"]
    dsum = dkg * c["kg"] + dqg * c["qg"]          # the gradient of G, row by row
    for e, mask, ke, qe in c["levels"]:           # the mask leaves rows above b on one side, rows up to b on the other
        pairs = jnp.concatenate([dr * mask, drq * mask], axis=0)                  # [2C, C]
        by_row = _dot(pairs, ke, _NN, dtype)                                      # [2C, K]
        dk_r, dq_r = by_row[:chunk] * e, by_row[chunk:] * e
        dk_i = _dot(pairs, jnp.concatenate([ke, qe], axis=0), _TN, dtype) * e     # [C, K]
        dq, dk = dq + dq_r, dk + dk_r + dk_i
        dsum = dsum + kf * dk_r + qf * dq_r - kf * dk_i
    diagonal = jnp.sum(drq * c["eye"], axis=1, keepdims=True)
    dq, dk = dq + diagonal * kf, dk + diagonal * qf
    dend = dkend * c["kend"]                      # the gradient of (G_last - G), row by row
    dg = _sum01(consts["back"], jnp.concatenate([dsum, dend], axis=0), dtype) + dlast * c["last"]
    return dq, dk, dv, dg, dbeta_col, dbeta_row, dstate_in


# -- the XLA form ---------------------------------------------------------------


def _jnp_constants(chunk: int) -> Dict[str, jax.Array]:
    return {name: jnp.asarray(value) for name, value in _constants(chunk).items()}


def _chunks(x, chunk: int):
    """[BH, S, ...] -> [chunks, BH, chunk, ...]."""
    bh, seq = x.shape[:2]
    return jnp.moveaxis(x.reshape(bh, seq // chunk, chunk, *x.shape[2:]), 1, 0)


def _unchunk(x):
    """[chunks, BH, chunk, ...] -> [BH, S, ...]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _forward_xla(q, k, v, g, beta, chunk: int, with_states: bool):
    """o [BH, S, V] in q's type and, where asked, every chunk's incoming
    state [BH, chunks, V, K]."""
    dtype, consts = q.dtype, _jnp_constants(chunk)
    one = jax.vmap(functools.partial(_forward_chunk, consts=consts, dtype=dtype))

    def step(state, xs):
        qc, kc, vc, gc, bc = xs
        o, after = one(state, qc, kc, vc, gc, bc[..., None], bc[:, None, :])
        return after, (o.astype(dtype), state if with_states else None)

    start = jnp.zeros((q.shape[0], v.shape[2], k.shape[2]), _F32)
    _, (o, states) = jax.lax.scan(step, start, tuple(_chunks(a, chunk) for a in (q, k, v, g, beta)))
    return _unchunk(o), (jnp.moveaxis(states, 0, 1) if with_states else None)


def _backward_xla(q, k, v, g, beta, states, do, chunk: int):
    dtype, consts = q.dtype, _jnp_constants(chunk)
    one = jax.vmap(functools.partial(_backward_chunk, consts=consts, dtype=dtype))

    def step(dstate, xs):
        state, qc, kc, vc, gc, bc, doc = xs
        dq, dk, dv, dg, db_col, db_row, dstate = one(state, dstate, qc, kc, vc, gc, bc[..., None], bc[:, None, :], doc)
        return dstate, (dq.astype(dtype), dk.astype(dtype), dv.astype(dtype), dg, db_col[..., 0] + db_row[:, 0, :])

    xs = (jnp.moveaxis(states, 1, 0),) + tuple(_chunks(a, chunk) for a in (q, k, v, g, beta, do))
    _, grads = jax.lax.scan(step, jnp.zeros_like(states[:, 0]), xs, reverse=True)
    return tuple(_unchunk(a) for a in grads)


# -- the kernels ----------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _heads_at_once(chunk_fn, dtype):
    """``chunk_fn`` over the leading axis of its blocks, the heads of a grid
    step, as a function of (the constants, *blocks): each product becomes one
    batched product, so a head's chain of dependent products stands op by op
    beside the other heads' and the scheduler fills one's latency with the
    others' work (unrolled head after head it kept them in sequence: PERF.md
    section 6, PR 50).  The constants are broadcast to a head each: left
    unbatched, their products would come out [rows, heads, lanes], the heads
    on the sublanes.  Jitted, and one object a chunk function and type, so that
    the batched trace — three times the plain one's seconds — is made once for
    all the calls of a program and not once for each time JAX traces a kernel
    (a layer's forward, its recomputation, its transpose)."""
    def run(consts, *blocks):
        heads = blocks[0].shape[0]
        each = {name: jnp.broadcast_to(value, (heads,) + value.shape) for name, value in consts.items()}
        return jax.vmap(functools.partial(chunk_fn, dtype=dtype))(*blocks, each)

    return jax.jit(run)


def _fwd_kernel(sums_ref, masks_ref, q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, o_ref, *rest, dtype, with_states):
    from jax.experimental import pallas as pl

    states_ref, state_scr = rest if with_states else (None, rest[0])

    @pl.when(pl.program_id(1) == 0)
    def _start():
        state_scr[...] = jnp.zeros_like(state_scr)

    state = state_scr[...]
    if with_states:
        states_ref[...] = state
    consts = {"sums": sums_ref[...], "masks": masks_ref[...]}
    o, after = _heads_at_once(_forward_chunk, dtype)(consts, state, q_ref[...], k_ref[...], v_ref[...], g_ref[...],
                                                     bc_ref[...], br_ref[...])
    o_ref[...] = o.astype(o_ref.dtype)
    state_scr[...] = after


def _bwd_kernel(sums_ref, masks_ref, back_ref, q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbc_ref, dbr_ref, dstate_scr, *, dtype):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _start():
        dstate_scr[...] = jnp.zeros_like(dstate_scr)

    consts = {"sums": sums_ref[...], "masks": masks_ref[...], "back": back_ref[...]}
    dq, dk, dv, dg, db_col, db_row, dstate = _heads_at_once(_backward_chunk, dtype)(
        consts, states_ref[...], dstate_scr[...], q_ref[...], k_ref[...], v_ref[...], g_ref[...], bc_ref[...], br_ref[...],
        do_ref[...])
    dq_ref[...], dk_ref[...], dv_ref[...] = dq.astype(dq_ref.dtype), dk.astype(dk_ref.dtype), dv.astype(dv_ref.dtype)
    dg_ref[...], dbc_ref[...], dbr_ref[...] = dg, db_col, db_row
    dstate_scr[...] = dstate


# The most heads a grid step carries: at 8 the backward kernel's blocks, twice each, are 5.3 MB and what it keeps
# between products 7.8 MB more (13.1 MB, the compiler's count at 128 wide); 16 would need 26.7 MB.  The limit leaves
# a step of 8 twice its need.
HEADS_PER_STEP = 8
_VMEM_LIMIT = 32 * 2 ** 20


def _heads_per_step(bh: int, most: int = HEADS_PER_STEP) -> int:
    """The largest divisor of ``bh`` not above ``most``."""
    return max(h for h in range(1, min(bh, most) + 1) if bh % h == 0)


def _specs(heads: int, chunk: int, dk: int, dv: int, n_chunks: int, reverse: bool):
    """Block specs of a grid step, ``heads`` heads' chunk j; the backward
    walks the chunks from the last."""
    from jax.experimental import pallas as pl

    at = (lambda j: n_chunks - 1 - j) if reverse else (lambda j: j)
    rows = lambda width: pl.BlockSpec((heads, chunk, width), lambda b, j: (b, at(j), 0))    # noqa: E731
    beta_row = pl.BlockSpec((heads, None, 1, chunk), lambda b, j: (b, at(j), 0, 0))
    state = pl.BlockSpec((heads, None, dv, dk), lambda b, j: (b, at(j), 0, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda b, j: (0, 0))                          # noqa: E731
    return rows, beta_row, state, whole


def _fwd_pallas(q, k, v, g, beta, chunk: int, with_states: bool, interpret: bool = False, heads_per_step=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq, dk = q.shape
    dv, n = v.shape[2], seq // chunk
    consts = _constants(chunk)
    heads = heads_per_step or _heads_per_step(bh)
    rows, beta_row, state, whole = _specs(heads, chunk, dk, dv, n, reverse=False)
    sums, masks = jnp.asarray(consts["sums"], jnp.bfloat16), jnp.asarray(consts["masks"])
    out_shape = [jax.ShapeDtypeStruct((bh, seq, dv), q.dtype)]
    out_specs = [rows(dv)]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((bh, n, dv, dk), _F32))
        out_specs.append(state)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, dtype=q.dtype, with_states=with_states),
        out_shape=out_shape,
        grid=(bh // heads, n),
        in_specs=[whole(sums.shape), whole(masks.shape), rows(dk), rows(dk), rows(dv), rows(dk), rows(1), beta_row],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_kda_fwd",
    )(sums, masks, q, k, v, g, beta[..., None], beta.reshape(bh, n, 1, chunk))
    return out[0], (out[1] if with_states else None)


def _bwd_pallas(q, k, v, g, beta, states, do, chunk: int, interpret: bool = False, heads_per_step=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq, dk = q.shape
    dv, n = v.shape[2], seq // chunk
    consts = _constants(chunk)
    heads = heads_per_step or _heads_per_step(bh)
    rows, beta_row, state, whole = _specs(heads, chunk, dk, dv, n, reverse=True)
    sums, back = jnp.asarray(consts["sums"], jnp.bfloat16), jnp.asarray(consts["back"], jnp.bfloat16)
    masks = jnp.asarray(consts["masks"])
    dq, dk_, dv_, dg, db_col, db_row = pl.pallas_call(
        functools.partial(_bwd_kernel, dtype=q.dtype),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct(g.shape, _F32),
            jax.ShapeDtypeStruct((bh, seq, 1), _F32), jax.ShapeDtypeStruct((bh, n, 1, chunk), _F32),
        ],
        grid=(bh // heads, n),
        in_specs=[whole(sums.shape), whole(masks.shape), whole(back.shape), rows(dk), rows(dk), rows(dv), rows(dk),
                  rows(1), beta_row, state, rows(dv)],
        out_specs=[rows(dk), rows(dk), rows(dv), rows(dk), rows(1), beta_row],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="tpuft_kda_bwd",
    )(sums, masks, back, q, k, v, g, beta[..., None], beta.reshape(bh, n, 1, chunk), states, do)
    return dq, dk_, dv_, dg, db_col[..., 0] + db_row.reshape(bh, seq)


# -- the call -------------------------------------------------------------------


def _forward(q, k, v, g, beta, chunk, kernel, with_states):
    if kernel:
        return _fwd_pallas(q, k, v, g, beta, chunk, with_states, interpret=kernel == "interpret")
    return _forward_xla(q, k, v, g, beta, chunk, with_states)


# `kernel` (False, True or "interpret") is decided once, in `kda`, so that
# forward and backward cannot disagree.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, chunk: int, kernel):
    return _forward(q, k, v, g, beta, chunk, kernel, with_states=False)[0]


# What a rematerialised layer keeps so that its backward pass does not run the
# forward kernel a third time: the output (the gated norm after the scan reads
# it in ITS backward).  The chunks' states are never kept: `_kda_bwd` makes them
# again, so a layer runs `tpuft_kda_fwd` twice and `tpuft_kda_bwd` once.
SAVED_NAMES = ("tpuft_kda_out",)


def _kda_fwd(q, k, v, g, beta, chunk, kernel):
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(_kda(q, k, v, g, beta, chunk, kernel), SAVED_NAMES[0]), (q, k, v, g, beta)


def _kda_bwd(chunk, kernel, res, do):
    q, k, v, g, beta = res
    # the chunks' incoming states, made again: float32 [BH, chunks, V, K], alive for this call alone
    _, states = _forward(q, k, v, g, beta, chunk, kernel, with_states=True)
    if kernel:
        return _bwd_pallas(q, k, v, g, beta, states, do, chunk, interpret=kernel == "interpret")
    return _backward_xla(q, k, v, g, beta, states, do, chunk)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *, chunk: int = CHUNK,
        mesh=None, interpret: bool = False) -> jax.Array:
    """The gated delta rule over a sequence, head-major like the flash call:
    q, k [B, Hk, S, K] and v [B, H, S, V] in the compute type (q carries its
    scale), g float32 <= 0 the log of the decay, beta [B, H, S] float32 in
    [0, 1] -> o [B, H, S, V] in q's type.  The operands' shapes say which rule:

    - the decay: g [B, H, S, K] a number a channel of the key (Kimi Delta
      Attention), or g [B, H, S] ONE number a head and position (Gated
      DeltaNet);
    - the key heads: Hk = H, or Hk a divisor of H, value head j then reading
      key head ``j // (H // Hk)`` (Gated DeltaNet at 16 key heads under 32
      value heads), dq and dk the sums over the value heads of a key head.

    Today a head's decay is broadcast over the key's channels and a shared key
    head repeated for its value heads before the one scan that runs (module
    docstring, "A decay a head"); the results are the rule's either way.  The
    state before the first position is zero.  A sequence that ``chunk`` does
    not divide is padded at its end with positions that write nothing (k = 0,
    beta = 0, g = 0) and whose outputs are cut away."""
    b, h, seq, dv = v.shape
    dk, shared = q.shape[3], h // q.shape[1]
    assert q.shape == k.shape and h % q.shape[1] == 0 and g.shape in ((b, h, seq, dk), (b, h, seq)), (
        q.shape, k.shape, v.shape, g.shape)
    if shared > 1:
        q, k = jnp.repeat(q, shared, axis=1), jnp.repeat(k, shared, axis=1)
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], (b, h, seq, dk))
    pad = -seq % chunk
    flat = [a.reshape(b * h, seq, *a.shape[3:]) for a in (q, k, v, g.astype(_F32), beta.astype(_F32))]
    if pad:
        flat = [jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in flat]
    kernel: Any = "interpret" if interpret else (
        dk % _pallas_util.LANE == 0 and dv % _pallas_util.LANE == 0 and _pallas_util.kernels_apply(mesh))
    o = _kda(*flat, chunk, kernel)
    return o[:, :seq].reshape(b, h, seq, dv)


def kda_loop(q, k, v, g, beta) -> Tuple[jax.Array, jax.Array]:
    """The recurrence position by position, float32, for either shape of the
    decay and either count of key heads (`kda`): (o, the last state
    [B, H, K, V]).  The tests' yardstick for the chunk form; no program runs it."""
    shared = v.shape[1] // q.shape[1]
    qf, kf, vf, gf, bf = (jnp.moveaxis(a.astype(_F32), 2, 0) for a in (q, k, v, g, beta))

    def step(state, xs):
        qt, kt, vt, gt, bt = xs                                   # [B, H, .]; qt, kt [B, Hk, K]
        qt, kt = jnp.repeat(qt, shared, axis=1), jnp.repeat(kt, shared, axis=1)   # value head j reads key head j // shared
        state = state * (jnp.exp(gt)[..., None] if gt.ndim == 3 else jnp.exp(gt)[..., None, None])
        state = state + (bt[..., None] * kt)[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, state))[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state)

    start = jnp.zeros(v.shape[:2] + (k.shape[3], v.shape[3]), _F32)
    with jax.default_matmul_precision("highest"):
        last, o = jax.lax.scan(step, start, (qf, kf, vf, gf, bf))
    return jnp.moveaxis(o, 0, 2), last
