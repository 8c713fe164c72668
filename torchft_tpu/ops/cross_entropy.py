"""Fused lm-head + cross-entropy: pallas TPU kernels, never materializing
the f32 [N, vocab] logits in HBM.

Motivation (flagship profile, v5e): the unfused path — bf16 [N, E] @ [E, V]
matmul to f32 logits, logsumexp, target gather, then the backward's softmax
recompute and two grad matmuls — moves the 2.1 GB f32 logits array through
HBM repeatedly (~18 ms/step of pure bandwidth), and holds it as an autodiff
residual.  The fused op:

  forward   — one kernel, grid (row_blocks, vocab_blocks) with vocab
              innermost: online logsumexp in VMEM scratch; only the O(N)
              lse ever reaches HBM.  The target logit is extracted
              OUTSIDE the kernel as rowsum(x * w.T[targets]) — an O(N*E)
              gather+reduce in XLA — because the in-kernel
              iota/compare/select variant added ~4 VPU passes over the
              full [N, V] tile stream (measured slower than the XLA
              gather by ~1 ms).
  backward  — one kernel recomputes the logits block, forms the scaled
              bf16 dlogits = (softmax - onehot) * g/N tile, and writes it
              once; dx and dw are then plain XLA bf16 matmuls (XLA runs
              them near MXU peak, which hand-written accumulation kernels
              measured 2x worse at).  Peak transient is the bf16 [N, V]
              dlogits (half the f32 logits the unfused path keeps alive),
              and the f32 logits never exist.

The reference has no analogue (torch CE over materialized logits); this op
exists because the TPU build owns its compute path.  Off-TPU (CPU test
mesh) and for shapes the kernels do not tile, callers should use the plain
XLA formulation (see models/transformer.lm_head_loss) — this module only
decides applicability via `fused_ce_applicable`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.ops import _pallas_util
from torchft_tpu.ops._pallas_util import row_stat_col

_LANE = 128

# Per-operand VMEM budgets the block sizes are solved against (double
# buffering means each block effectively costs ~2x its size; the f32
# logits tile [block_rows, block_v] is the largest single allocation).
_X_BLOCK_BYTES = 2 * 1024 * 1024
_W_BLOCK_BYTES = 3 * 1024 * 1024
# The most the backward's bf16 dlogits [N, V] may take whole: a head whose
# [N, V] would pass it runs in pieces (`head_row_block`, `head_slab`).  2 GiB is
# above every head the kernels were written for (8,192 x 92,544: 1.5 GB) and
# an eighth of a v5e's memory; at 16,384 x 131,584 the whole is 4.3 GB beside
# 11 GB of weights, gradient and moments.  The pieces are cut along the axis
# whose sum is the small one, and a piece takes at most `_DLOGITS_BLOCK_BYTES`
# of bf16.  Forward, by rows: a block's log-sum-exp needs all of its columns
# and nothing of another block, and no [rows, V] array exists at all; 16 blocks
# of 1,024 rows at that head ([1,024, 131,584]: 0.27 GB, and 2,048 rows are
# 2 MB over).  Backward, by columns: a slab's rows of dW are complete after
# ONE product over all N rows and are written once, and dx [N, E] float32
# (134 MB) is what is summed over the slabs — cut by rows it was dW [V, E]
# float32 (1.08 GB) that every block read and wrote, 34.5 GB a step.  9 slabs
# there: 8 of 16,384 columns ([16,384, 16,384]: 0.54 GB) and the last 512 with
# the padding in them.  A wider slab sums dx fewer times (8,192 columns
# measured 2.4 ms a step more in that product and nothing in the kernel,
# PR 46).  The gradient program compiles to 3.90 GB of
# temporaries with the layers' weight gradients finished layer by layer
# (`models/transformer.py` `_grads_inside`; the same with slabs of 8,192), and
# to 4.20 GB without that at 8,192; by rows it took 4.04 at 1,024, 4.74 at
# 2,048 and 5.85 at 4,096, over the chip (compiled for a described v5e, PRs
# 41 and 46).  The kernels read the weight once a tile of `_X_BLOCK_BYTES`
# rows whatever the piece, so smaller pieces re-read nothing; below a thousand
# rows or a few thousand columns the loop's own steps would begin to show.
_DLOGITS_BYTES = 2 * 1024 * 1024 * 1024
_DLOGITS_BLOCK_BYTES = 512 * 1024 * 1024


def _block_v(v: int, e: int) -> Optional[int]:
    """Largest multiple of 128 dividing V whose [E, block_v] bf16 tile
    fits the weight budget, capped at 2048."""
    cap = min(2048, _W_BLOCK_BYTES // (2 * e) // _LANE * _LANE)
    best = None
    for mult in range(1, max(cap, _LANE) // _LANE + 1):
        cand = mult * _LANE
        if v % cand == 0:
            best = cand
    return best


def _block_rows(n: int, e: int) -> Optional[int]:
    """Largest power-of-two row block whose [block_rows, E] bf16 tile
    fits the activation budget."""
    cap = _X_BLOCK_BYTES // (2 * e)
    for cand in (1024, 512, 256, 128):
        if cand <= cap and n % cand == 0:
            return cand
    return None


def fused_ce_applicable(n: int, e: int, v: int, mesh=None) -> bool:
    """True when the pallas kernels can and should run: a valid tiling
    exists (blocks are solved against explicit per-operand VMEM budgets, so
    no separate size check can drift from what the kernels allocate), and
    the program being traced runs on one TPU device
    (``_pallas_util.kernels_apply``: ``mesh`` when given, else the ambient
    abstract mesh).  Sharded configurations keep the plain XLA formulation,
    which propagates shardings (vocab-parallel logsumexp etc.) natively."""
    return (
        _block_v(v, e) is not None
        and _block_rows(n, e) is not None
        and e % _LANE == 0
        and _pallas_util.kernels_apply(mesh)
    )


def _ce_lse_kernel(
    x_ref, w_ref, lse_ref, m_scr, l_scr, *, num_v: int, valid_v: Optional[int] = None,
):
    """``valid_v``: the head's real width where its columns were padded to
    a tileable one (`padded_vocab`); the padding's logits count as -inf."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)

    # Logits block in the input dtype (bf16 = full MXU rate), f32 accum.
    s = jax.lax.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )                                              # [block_rows, block_v]
    if valid_v is not None:
        cols = j * s.shape[1] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < valid_v, s, -1e30)
    m_prev = m_scr[:, :1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    l_new = alpha * l_scr[:, :1] + jnp.sum(
        jnp.exp(s - m_cur), axis=-1, keepdims=True
    )
    # Partial column stores: broadcasting across the (rows, 128) scratch
    # measured ~19% of the attention kernel's time; same pattern here.
    m_scr[:, 0:1] = m_cur
    l_scr[:, 0:1] = l_new

    @pl.when(j == num_v - 1)
    def _emit():
        lse = m_scr[:, :1] + jnp.log(l_scr[:, :1])     # (block_rows, 1)
        lse_ref[0, 0:1, :] = jnp.transpose(lse, (1, 0))


def _ce_dlogits_kernel(
    x_ref, w_ref, tgt_ref, lse_ref, scale_ref, dl_ref,
    *, block_rows: int, block_v: int, valid_v: Optional[int] = None, first_ref=None, row_scale: bool = False,
):
    """``first_ref``: where the call covers a slab of w's columns, the slab's
    first tile of w (SMEM), so that ``cols`` are the head's own columns.
    ``row_scale``: ``scale_ref`` holds a scale a row, laid out as the
    log-sum-exp is ([1, 1, N]), and not the one number in SMEM."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    j = pl.program_id(1)
    if first_ref is not None:
        j = j + first_ref[0]

    s = jax.lax.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )
    p = jnp.exp(s - row_stat_col(lse_ref, i, block_rows))
    tg = row_stat_col(tgt_ref, i, block_rows)
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    p = jnp.where(cols == tg, p - 1.0, p)          # softmax - onehot
    if valid_v is not None:
        p = jnp.where(cols < valid_v, p, 0.0)      # the padding has no logit
    scale = row_stat_col(scale_ref, i, block_rows) if row_scale else scale_ref[0, 0]
    dl_ref[...] = (p * scale).astype(dl_ref.dtype)


def _ce_lse_pallas(x, w, interpret: bool = False, valid_v: Optional[int] = None):
    """x: [N, E], w: [E, V] (same dtype as x) -> lse [N] f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, e = x.shape
    v = w.shape[1]
    br, bv = _block_rows(n, e), _block_v(v, e)
    num_i, num_v = n // br, v // bv

    lse = pl.pallas_call(
        functools.partial(_ce_lse_kernel, num_v=num_v, valid_v=valid_v),
        out_shape=jax.ShapeDtypeStruct((1, 1, n), jnp.float32),
        grid=(num_i, num_v),
        in_specs=[
            pl.BlockSpec((br, e), lambda i, j: (i, 0)),        # x
            pl.BlockSpec((e, bv), lambda i, j: (0, j)),        # w
        ],
        out_specs=pl.BlockSpec((1, 1, br), lambda i, j: (0, 0, i)),
        scratch_shapes=[
            pltpu.VMEM((br, _LANE), jnp.float32),   # running max
            pltpu.VMEM((br, _LANE), jnp.float32),   # running sumexp
        ],
        interpret=interpret,
        name="tpuft_ce_lse",
    )(x, w)
    return lse[0, 0]


def _ce_dlogits_pallas(x, w, targets, lse, scale, interpret: bool = False, valid_v: Optional[int] = None,
                       cols=None):
    """Scaled bf16 dlogits = (softmax(x@w) - onehot(targets)) * scale.
    scale is a traced scalar (folded in here so no extra [N, V] pass), or
    [N]: a scale a row, which the kernel reads as it reads the log-sum-exp.
    ``cols`` = (j, slab, width), j traced: the ``width`` columns from column
    j * slab alone, [N, width] — the same kernel at the same tiles, reading w
    in place from the slab's first tile on, a prefetched scalar that the index
    map of w and the kernel's column numbers add, so that targets and
    ``valid_v`` stay the head's own column numbers."""
    import math

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, e = x.shape
    j, slab, v = cols or (None, None, w.shape[1])
    br, bv = _block_rows(n, e), _block_v(v if cols is None else math.gcd(v, slab), e)  # a tile divides j * slab
    tgt = targets.astype(jnp.int32)[None, None, :]
    lse3 = lse[None, None, :]
    row_scale = jnp.ndim(scale) == 1
    scale2 = jnp.asarray(scale, jnp.float32).reshape((1, 1, n) if row_scale else (1, 1))
    kernel = functools.partial(_ce_dlogits_kernel, block_rows=br, block_v=bv, valid_v=valid_v, row_scale=row_scale)
    specs = dict(
        grid=(n // br, v // bv),
        in_specs=[
            pl.BlockSpec((br, e), lambda i, j, *first: (i, 0)),        # x
            pl.BlockSpec((e, bv), lambda i, j, *first: (0, first[0][0] + j if first else j)),  # w
            pl.BlockSpec((1, 1, n), lambda i, j, *first: (0, 0, 0)),   # targets
            pl.BlockSpec((1, 1, n), lambda i, j, *first: (0, 0, 0)),   # lse
            pl.BlockSpec((1, 1, n), lambda i, j, *first: (0, 0, 0)) if row_scale
            else pl.BlockSpec(memory_space=pltpu.SMEM),                # scale
        ],
        out_specs=pl.BlockSpec((br, bv), lambda i, j, *first: (i, j)),
    )
    call = dict(out_shape=jax.ShapeDtypeStruct((n, v), x.dtype), interpret=interpret, name="tpuft_ce_dlogits")
    if cols is None:
        return pl.pallas_call(kernel, **specs, **call)(x, w, tgt, lse3, scale2)
    first = (jnp.asarray(j, jnp.int32) * (slab // bv)).reshape(1)
    return pl.pallas_call(
        lambda first_ref, *refs: kernel(*refs, first_ref=first_ref),
        grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, **specs), **call,
    )(first, x, w, tgt, lse3, scale2)


def _target_logit(x, w, targets):
    """rowsum(x * w[:, t]): O(N*E) gather + reduce, no [N, V] involved.
    w.T is materialized so the gather reads contiguous rows."""
    wt = jnp.transpose(w)[targets]                 # [N, E]
    return jnp.einsum(
        "ne,ne->n", x, wt, preferred_element_type=jnp.float32
    )


@jax.custom_vjp
def fused_linear_cross_entropy(x, w, targets):
    """Mean cross-entropy of softmax(x @ w) against integer targets,
    computed blockwise on TPU so the f32 [N, V] logits never reach HBM.

    x: [N, E] (bf16 on the model path), w: [E, V] same dtype, targets:
    [N] integer.  Returns a f32 scalar.  Callers gate on
    fused_ce_applicable; off-TPU the same math runs as one materialized
    XLA computation (used by the correctness tests)."""
    lse, tl = _ce_fwd(x, w, targets)
    return jnp.mean(lse - tl)


def _ce_fwd(x, w, targets, interpret: bool = False, valid_v: Optional[int] = None):
    if _pallas_util.on_tpu() or interpret:
        lse = _ce_lse_pallas(x, w, interpret=interpret, valid_v=valid_v)
        return lse, _target_logit(x, w, targets)
    logits = jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    if valid_v is not None:
        logits = jnp.where(jnp.arange(w.shape[1]) < valid_v, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return lse, tl


def _ce_vjp_fwd(x, w, targets):
    lse, tl = _ce_fwd(x, w, targets)
    return jnp.mean(lse - tl), (x, w, targets, lse)


def _ce_dlogits(x, w, targets, lse, scale, valid_v: Optional[int] = None, cols=None):
    """(softmax(x @ w) - onehot(targets)) * scale in x's dtype, [N, V] — scale
    one number, or [N]: one a row; with ``cols`` = (j, slab, width), j traced,
    its ``width`` columns from column j * slab alone."""
    if _pallas_util.on_tpu():
        # dlogits tile-by-tile in bf16 (pallas) — the f32 logits never
        # exist in HBM.
        return _ce_dlogits_pallas(x, w, targets, lse, scale, valid_v=valid_v, cols=cols)
    first = 0
    if cols is not None:
        first = cols[0] * cols[1]
        w = jax.lax.dynamic_slice_in_dim(w, first, cols[2], 1)
    at = first + jnp.arange(w.shape[1])
    logits = jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    if valid_v is not None:
        logits = jnp.where(at < valid_v, logits, -1e30)
    p = jnp.exp(logits - lse[:, None])
    p = p - (targets[:, None] == at)
    return (p * (scale[:, None] if jnp.ndim(scale) == 1 else scale)).astype(x.dtype)


def _ce_vjp_bwd(res, g, valid_v: Optional[int] = None):
    return _ce_grads(res, g / res[0].shape[0], valid_v)


def _ce_grads(res, scale, valid_v: Optional[int] = None):
    """(dx, dw, no gradient of the targets) from the dlogits times ``scale``:
    the mean's one number g / N, or the per-row form's cotangent a row [N]."""
    x, w, targets, lse = res
    dl = _ce_dlogits(x, w, targets, lse, scale, valid_v)
    # Two plain XLA matmuls — XLA runs these bf16 matmuls near MXU peak,
    # which hand-written scratch-accumulation kernels measured 2x worse at.
    dx = jax.lax.dot_general(
        dl, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dw = jax.lax.dot_general(
        x, dl, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (
        dx.astype(x.dtype),
        dw.astype(w.dtype),
        np.zeros(targets.shape, jax.dtypes.float0),
    )


fused_linear_cross_entropy.defvjp(_ce_vjp_fwd, _ce_vjp_bwd)


# -- the loss of every row ------------------------------------------------------------


def fused_linear_cross_entropy_per_row(x, w, targets):
    """`fused_linear_cross_entropy` before its mean: the loss of every row,
    [N] float32, for a loss that weighs the rows itself (a looped model's
    exit-weighted loss, block diffusion's 1 / t, `models/transformer.py`).  Its
    backward takes a cotangent a row, which `tpuft_ce_dlogits` reads as a scale
    a row, so the bf16 dlogits are still written once and nothing [N, V] is
    scaled after.  x, w, targets and the gate (`fused_ce_applicable`) as there."""
    return _ce_per_row(x, w, targets, None)


def fused_linear_cross_entropy_per_row_padded(x, w, targets):
    """The same for a head whose width V is no lane multiple, as
    `fused_linear_cross_entropy_padded` is the mean's: zero columns pad w to
    `padded_vocab(V)` and their logits count as -inf (`valid_v`)."""
    v = w.shape[1]
    return _ce_per_row(x, jnp.pad(w, ((0, 0), (0, padded_vocab(v) - v))), targets, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ce_per_row(x, w, targets, valid_v: Optional[int]):
    return _ce_per_row_fwd(x, w, targets, valid_v)[0]


def _ce_per_row_fwd(x, w, targets, valid_v):
    lse, tl = _ce_fwd(x, w, targets, valid_v=valid_v)
    return lse - tl, (x, w, targets, lse)


_ce_per_row.defvjp(_ce_per_row_fwd, lambda valid_v, res, g: _ce_grads(res, g, valid_v))


# -- a head whose width no block divides ------------------------------------------


def padded_vocab(v: int) -> int:
    """The width a head of V columns is padded to where V is no lane
    multiple (an eighth of a 151,936-row vocabulary is 18,992 = 16 x 1,187):
    the next multiple of 512, which `_block_v` tiles in blocks of 512."""
    return v if v % _LANE == 0 else -(-v // 512) * 512


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ce_valid(x, w, targets, valid_v: int):
    lse, tl = _ce_fwd(x, w, targets, valid_v=valid_v)
    return jnp.mean(lse - tl)


def _ce_valid_fwd(x, w, targets, valid_v):
    lse, tl = _ce_fwd(x, w, targets, valid_v=valid_v)
    return jnp.mean(lse - tl), (x, w, targets, lse)


_fused_ce_valid.defvjp(_ce_valid_fwd, lambda valid_v, res, g: _ce_vjp_bwd(res, g, valid_v))


def fused_linear_cross_entropy_padded(x, w, targets):
    """`fused_linear_cross_entropy` for a head whose width V is no lane
    multiple: zero columns pad w to `padded_vocab(V)`, the kernels treat
    the padding's logits as -inf (`valid_v`), and autodiff slices the
    padding's (zero) gradient away.  Callers gate on
    ``fused_ce_applicable(n, e, padded_vocab(v))``."""
    v = w.shape[1]
    return _fused_ce_valid(x, jnp.pad(w, ((0, 0), (0, padded_vocab(v) - v))), targets, v)


# -- a head in pieces: blocks of rows forward, slabs of columns backward ------------


def head_row_block(n: int, v: int) -> Optional[int]:
    """Rows a block where the head of N rows and V (padded) columns runs in
    pieces — the largest power of two whose bf16 [block, V] stay within
    `_DLOGITS_BLOCK_BYTES` — and None where the one [N, V] stays within
    `_DLOGITS_BYTES`."""
    if n * v * 2 <= _DLOGITS_BYTES:
        return None
    return 1 << ((_DLOGITS_BLOCK_BYTES // (2 * v)).bit_length() - 1)


def head_slab(n: int, v: int) -> int:
    """Columns a slab of that head's backward pass: the largest power of two
    times 512 (what `padded_vocab` pads to, and `_block_v` tiles) whose bf16
    dlogits [N, slab] stay within `_DLOGITS_BLOCK_BYTES`, and all V where
    `head_row_block` is None."""
    if head_row_block(n, v) is None:
        return v
    return 512 << max((_DLOGITS_BLOCK_BYTES // (2 * n * 512)).bit_length() - 1, 0)


def _kernel_weight(w, dtype, vocab_major: bool):
    """w as the kernels read it — ``dtype``, [E, padded V] — and its real V."""
    wk = w.astype(dtype).T if vocab_major else w.astype(dtype)
    v = wk.shape[1]
    return jnp.pad(wk, ((0, 0), (0, padded_vocab(v) - v))), v


def _row_blocks(a, block: int):
    """a [N, ...] -> [blocks, block, ...], zero rows after the last: their
    dlogits meet a zero x in dw, and their loss and dx are cut off."""
    rows = -(-a.shape[0] // block) * block
    a = jnp.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))
    return a.reshape(rows // block, block, *a.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy_rows(x, w, targets, block: int, vocab_major: bool = False):
    """`fused_linear_cross_entropy` for a head whose bf16 dlogits [N, V] are
    too large to exist whole.  Forward over blocks of ``block`` rows: a
    block's log-sum-exp at a time.  Backward over slabs of `head_slab` columns
    and all rows: the slab's dlogits from the kernel, its rows of w's
    gradient from ONE product over the N rows, written once where they belong
    and never read, and its part of dx added into a float32 [N, E] — so that
    one slab's dlogits exist at a time and the sum that is carried is the
    small one.  N need be no whole number of blocks, V none of slabs: the
    last slab is the narrower one.

    ``w`` is the weight as the parameter tree holds it, in the parameters'
    dtype: [E, V], or with ``vocab_major`` [V, E] — a tied head, the
    embedding itself, whose transposed copy is made in x's dtype and never in
    float32, and whose gradient is written [V, E].  V may be any width
    (`padded_vocab`).  Callers gate on ``fused_ce_applicable(block, e,
    padded_vocab(v))``."""
    return _ce_rows_fwd(x, w, targets, block, vocab_major)[0]


def _ce_rows_fwd(x, w, targets, block: int, vocab_major: bool):
    n = x.shape[0]
    wk, v = _kernel_weight(w, x.dtype, vocab_major)
    valid_v = None if wk.shape[1] == v else v
    xs, ts = _row_blocks(x, block), _row_blocks(targets, block)
    if _pallas_util.on_tpu():
        lse = jax.lax.map(lambda xb: _ce_lse_pallas(xb, wk, valid_v=valid_v), xs)
        # the target's logit from the rows of w, outside the loop: no [V, E] copy of a [E, V] head a block
        rows = w.astype(x.dtype)[targets] if vocab_major else jnp.transpose(wk)[targets]
        tl = jnp.einsum("ne,ne->n", x, rows, preferred_element_type=jnp.float32)
        losses = lse.reshape(-1)[:n] - tl
    else:
        lse, tl = jax.lax.map(lambda a: _ce_fwd(a[0], wk, a[1], valid_v=valid_v), (xs, ts))
        losses = (lse - tl).reshape(-1)[:n]
    return jnp.sum(losses) / n, (x, w, wk, targets, lse)


def _ce_rows_bwd(block: int, vocab_major: bool, res, g):
    x, w, wk, targets, lse = res
    n, e = x.shape
    vp = wk.shape[1]
    v = w.shape[0] if vocab_major else w.shape[1]
    slab = head_slab(n, vp)
    xp = _row_blocks(x, block).reshape(-1, e)
    tp, lse = _row_blocks(targets, block).reshape(-1), lse.reshape(-1)
    scale = g / n

    def one(dx, dw, j, width: int, real: int):
        """The ``width`` columns from the j-th multiple of ``slab``, ``real`` of them the head's own."""
        dl = _ce_dlogits(xp, wk, tp, lse, scale, None if real == width else v, (j, slab, width))
        wj = jax.lax.dynamic_slice_in_dim(wk, j * slab, width, 1)
        dx = dx + jax.lax.dot_general(dl, wj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        lhs, rhs = (dl[:, :real], xp) if vocab_major else (xp, dl[:, :real])
        dwj = jax.lax.dot_general(lhs, rhs, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dx, jax.lax.dynamic_update_slice_in_dim(dw, dwj, j * slab, 0 if vocab_major else 1)

    # every slab but the last holds none of the padding (under 512 columns, and a slab is 512 at least):
    # those run in the loop; the last one, with what it holds of the head as a static width, before it,
    # or the compiled program holds 135 MB more at its peak (15.17e9 for 15.04e9 at ZAYA's head, PR 46)
    full = (vp - 1) // slab
    dx, dw = jnp.zeros(xp.shape, jnp.float32), jnp.zeros(w.shape, jnp.float32)
    dx, dw = one(dx, dw, full, vp - full * slab, v - full * slab)
    if full:
        dx, dw = jax.lax.fori_loop(0, full, lambda j, c: one(*c, j, slab, slab), (dx, dw))
    return dx[:n].astype(x.dtype), dw.astype(w.dtype), np.zeros(targets.shape, jax.dtypes.float0)


fused_linear_cross_entropy_rows.defvjp(_ce_rows_fwd, _ce_rows_bwd)
