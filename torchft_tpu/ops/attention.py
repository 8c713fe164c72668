"""Flash attention: pallas TPU forward + backward kernels.

Design notes (MXU/HBM-minded):
  - forward streams K/V blocks through VMEM with the classic online-softmax
    accumulator, so HBM traffic is O(S*D) instead of materializing the
    O(S^2) score matrix;
  - the log-sum-exp per query row is saved, and the backward pass recomputes
    scores blockwise from (q, k, lse) — the flash recompute trade: extra
    FLOPs on the MXU instead of an O(S^2) residual in HBM.  On TPU the
    backward is ONE merged pallas kernel for typical shapes (q axis
    innermost; dk/dv accumulate in VMEM scratch, dq is emitted as
    per-kv-block f32 partials in HBM and summed in XLA — the s/p/dp/ds
    tile work that dominates on the VPU is computed once).  When num_k
    exceeds _DQ_PARTIAL_MAX_K the partials' (num_k, BH, S, D) transient
    would dwarf dq itself, so long-context shapes switch to two passes
    (dk/dv with q innermost, dq with kv innermost, both O(S*D) memory).
    Off-TPU the same math is expressed in XLA with the scores
    materialized;
  - grid layout (batch*heads, outer_blocks, inner_blocks) with the
    reduction axis innermost: TPU executes the innermost grid dimension
    sequentially, which is what makes the VMEM scratch accumulator legal.

Query and key share one head width (``d_qk``), value and output another
(``d_v``): equal for plain multi-head attention, 192 / 128 for latent
attention (MLA), whose keys carry 64 rotary columns beside the 128 that the
values match.  Each kernel's blocks span a whole head width, so both have to
be lane multiples; ``flash_attention`` pads a ``d_qk`` that is not (MLA's
192 -> 256) with zero columns, which add nothing to a score, and autodiff
slices the padding's gradient away again.

The reference XLA attention runs off-TPU (CPU test mesh), under a
multi-device mesh (a pallas call has no partitioning rule), and for shapes
the kernel does not tile (seq not divisible by the block size).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops import _pallas_util
from torchft_tpu.ops._pallas_util import row_stat_col

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
    # 512x512: these kernels are VPU-bound on the S^2 elementwise tile, so
    # the finest block that keeps the MXU fed wins — fatter q blocks were
    # measured slower because causal masking can only skip whole blocks
    # (a 1024-row block straddling the diagonal computes 33% more masked
    # elements at the flagship seq=1024 than two 512-row blocks).
    return min(512, seq_q), min(512, seq_k)


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, num_k: int,
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: kv blocks strictly above the diagonal contribute nothing.
    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        # Matmuls run in the INPUT dtype with f32 accumulation: bf16 model
        # activations hit the MXU at full rate (an f32xf32 matmul runs at a
        # fraction of it); softmax statistics stay f32 throughout.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k] f32
        if causal:
            # Unconditional mask: branching per block via lax.cond measured
            # ~3 ms/step SLOWER than these VPU passes (Mosaic conditional
            # overhead exceeds the saved work at flagship shapes).
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)

        m_prev = m_scr[:, :1]                      # [block_q, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)                     # [block_q, block_k]
        alpha = jnp.exp(m_prev - m_cur)            # rescale old accumulator
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        # Partial column stores: broadcasting the stats across the full
        # (block_q, 128) scratch measured ~19% of the kernel.
        m_scr[:, 0:1] = m_cur
        l_scr[:, 0:1] = l_new

    @pl.when(ki == num_k - 1)
    def _emit():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        # lse output is lane-padded to (block_q, _LANE) to satisfy TPU tiling.
        lse_ref[0] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(safe_l), lse_ref.shape[1:]
        ).astype(lse_ref.dtype)


def _fa_pallas_call(q, k, v, scale: float, causal: bool, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, d = q.shape  # d: query and key; dv: value and output
    seq_k, dv = k.shape[1], v.shape[2]
    block_q, block_k = _block_sizes(seq_q, seq_k)
    num_k = seq_k // block_k
    grid = (bh, seq_q // block_q, num_k)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=num_k,
    )
    out, lse_padded = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bh, seq_q, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, _LANE), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANE), jnp.float32),  # running sum
            pltpu.VMEM((block_q, dv), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
        name="tpuft_fa_fwd",
    )(q, k, v)
    return out, lse_padded[:, :, 0]


# Above this many kv blocks the merged backward's per-kv-block dq partials
# ((num_k, BH, S, D) f32 transient in HBM) cost more than a second
# recompute pass; long-context shapes switch to the two-kernel form.
_DQ_PARTIAL_MAX_K = 4


def _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
               *, scale, causal, block_q, block_k):
    """Shared flash-backward block body: recomputes p and ds for the
    (q-block qi, kv-block ki) tile.  Matmul operands stay in the input
    dtype (bf16 on the model path = full MXU rate); probabilities and
    statistics are f32.  Returns (p, ds) with ds cast to the input dtype
    for the downstream MXU products."""
    q = q_ref[0]
    k = k_ref[0]
    do = do_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # [block_q, block_k] f32
    p = jnp.exp(s - row_stat_col(lse_ref, qi, block_q))
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p = jnp.where(rows >= cols, p, 0.0)
    dp = jax.lax.dot_general(
        do, v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                           # [block_q, block_k]
    ds = (p * (dp - row_stat_col(delta_ref, qi, block_q)) * scale).astype(q.dtype)
    return p, ds


def _fa_bwd_dkdv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *rest,
    scale: float, causal: bool, block_q: int, block_k: int, num_q: int,
    emit_dq: bool,
):
    """Flash backward with the q axis innermost: dk/dv accumulate in VMEM
    scratch across the sequential inner q dimension.  With emit_dq (the
    merged one-pass form for typical shapes) the dq contribution of this
    kv block is additionally emitted to a per-kv-block f32 partial (one
    visit per output block, summed in XLA) — the s/p/dp/ds tile work that
    dominates on the VPU is then computed once instead of twice."""
    from jax.experimental import pallas as pl

    if emit_dq:
        dqp_ref, dk_scr, dv_scr = rest
    else:
        dk_scr, dv_scr = rest

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # Causal: a q block strictly above this kv block's diagonal contributes
    # nothing — but its dq partial (if any) must still be zeroed.
    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _step():
        p, ds = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        do = do_ref[0]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # p^T @ do: [block_k, d]
        dk_scr[...] += jax.lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # ds^T @ q: [block_k, d]
        if emit_dq:
            dqp_ref[0, 0] = jax.lax.dot(
                ds, k_ref[0], preferred_element_type=jnp.float32
            ).astype(dqp_ref.dtype)                 # ds @ k: [block_q, d]

    if emit_dq and causal:
        @pl.when(jnp.logical_not(run))
        def _zero():
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    @pl.when(qi == num_q - 1)
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, num_k: int,
):
    """dq-only pass for the long-context form, kv axis innermost: dq
    accumulates in f32 VMEM scratch, so memory stays O(S*D) regardless of
    num_k (at the price of recomputing p/ds once more)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        _, ds = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        dq_scr[...] += jax.lax.dot(
            ds, k_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when(ki == num_k - 1)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_bwd_pallas(q, k, v, o, lse, g, scale: float, causal: bool,
                   interpret: bool = False):
    """Flash backward on TPU; q/k: [BH, S, D], v/o/g: [BH, S, Dv], lse:
    [BH, S] f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, d = q.shape
    seq_k, d_v = k.shape[1], v.shape[2]
    block_q, block_k = _block_sizes(seq_q, seq_k)
    num_q, num_k = seq_q // block_q, seq_k // block_k
    # Row stats as [BH, 1, S]: whole row per visit (4 KB).  delta_i =
    # rowsum(do * o) is O(S*D) and computed once here instead of per tile.
    lse = lse[:, None, :]
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, None, :]

    q_spec_ji = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    do_spec_ji = pl.BlockSpec((1, block_q, d_v), lambda b, j, i: (b, i, 0))
    k_spec_ji = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    v_spec_ji = pl.BlockSpec((1, block_k, d_v), lambda b, j, i: (b, j, 0))
    row_spec_ji = pl.BlockSpec((1, 1, seq_q), lambda b, j, i: (b, 0, 0))
    in_specs_ji = [q_spec_ji, k_spec_ji, v_spec_ji, do_spec_ji,
                   row_spec_ji, row_spec_ji]
    dkdv_scratch = [
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, d_v), jnp.float32),
    ]
    merged = num_k <= _DQ_PARTIAL_MAX_K
    out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    out_specs = [k_spec_ji, v_spec_ji]
    if merged:
        # dq as f32 per-kv-block partials: the cross-block sum loses no
        # precision vs the f32 XLA backward this replaced.
        out_shape.append(
            jax.ShapeDtypeStruct(
                (num_k, bh, seq_q, d), q.dtype if num_k == 1 else jnp.float32
            )
        )
        out_specs.append(
            pl.BlockSpec((1, 1, block_q, d), lambda b, j, i: (j, b, i, 0))
        )
    outs = pl.pallas_call(
        functools.partial(
            _fa_bwd_dkdv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q=num_q, emit_dq=merged,
        ),
        out_shape=tuple(out_shape),
        grid=(bh, num_k, num_q),
        in_specs=in_specs_ji,
        out_specs=tuple(out_specs),
        scratch_shapes=dkdv_scratch,
        interpret=interpret,
        name="tpuft_fa_bwd_dkdv",
    )(q, k, v, g, lse, delta)
    if merged:
        dk, dv, dq_part = outs
        if num_k == 1:
            dq = dq_part[0]
        else:
            dq = jnp.sum(dq_part, axis=0).astype(q.dtype)
        return dq, dk, dv
    dk, dv = outs

    # Long-context second pass: dq with the kv axis innermost.
    q_spec_ij = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    do_spec_ij = pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0))
    k_spec_ij = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    v_spec_ij = pl.BlockSpec((1, block_k, d_v), lambda b, i, j: (b, j, 0))
    row_spec_ij = pl.BlockSpec((1, 1, seq_q), lambda b, i, j: (b, 0, 0))
    dq = pl.pallas_call(
        functools.partial(
            _fa_bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k=num_k,
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(bh, num_q, num_k),
        in_specs=[q_spec_ij, k_spec_ij, v_spec_ij, do_spec_ij,
                  row_spec_ij, row_spec_ij],
        out_specs=q_spec_ij,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="tpuft_fa_bwd_dq",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _fa_reference(q, k, v, scale: float, causal: bool):
    """Stable XLA attention returning (out, lse); q/k: [BH, S, D], v: [BH, S, Dv]."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        seq_q, seq_k = s.shape[-2], s.shape[-1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bqk,bkd->bqd", (p / l).astype(v.dtype), v)
    lse = (m + jnp.log(l))[..., 0]
    return o.astype(q.dtype), lse


def _fa_forward(q, k, v, scale: float, causal: bool, kernel: bool):
    if kernel:
        return _fa_pallas_call(q, k, v, scale, causal)
    return _fa_reference(q, k, v, scale, causal)


# `kernel` is decided once, in flash_attention, from the shapes and the mesh
# of the program being traced, so forward and backward cannot disagree.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale: float, causal: bool, kernel: bool):
    o, _ = _fa_forward(q, k, v, scale, causal, kernel)
    return o


# What a rematerialised layer has to keep so that its backward pass does not
# run the forward kernel again: `jax.checkpoint(...,
# policy=save_only_these_names(*SAVED_NAMES))`.  q, k and v are cheap to make
# again (projections); the output and the row statistics are the kernel's.
SAVED_NAMES = ("tpuft_fa_out", "tpuft_fa_lse")


def _flash_fwd(q, k, v, scale, causal, kernel):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _fa_forward(q, k, v, scale, causal, kernel)
    o, lse = checkpoint_name(o, SAVED_NAMES[0]), checkpoint_name(lse, SAVED_NAMES[1])
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, kernel, res, g):
    q, k, v, o, lse = res
    if kernel:
        return _fa_bwd_pallas(q, k, v, o, lse, g, scale, causal)
    return _fa_bwd_xla(q, k, v, o, lse, g, scale, causal)


def _fa_bwd_xla(q, k, v, o, lse, g, scale, causal):
    """Off-TPU backward: same math with the scores materialized in XLA.
    Also the oracle the pallas backward kernels are tested against."""
    qf, kf, vf, gf = (t.astype(jnp.float32) for t in (q, k, v, g))
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) * scale
    if causal:
        seq_q, seq_k = s.shape[-2], s.shape[-1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (seq_q, seq_k), 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
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
) -> jax.Array:
    """Multi-head attention; q: [B, Hq, S, D], k: [B, Hkv, S, D], v:
    [B, Hkv, S, Dv] -> [B, Hq, S, Dv].  Dv may differ from D (MLA: 192 for
    query and key, 128 for value); the default scale is D ** -0.5.

    GQA: Hkv may divide Hq; kv heads are broadcast to query groups.
    ``mesh`` is the mesh of the program being traced (None: the ambient
    abstract mesh); under more than one device the XLA formulation runs,
    see ``_pallas_util.kernels_apply``.
    """
    b, hq, sq, d = q.shape
    hkv, dv = k.shape[1], v.shape[3]
    if hkv != hq:
        assert hq % hkv == 0, "query heads must be a multiple of kv heads"
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else d ** -0.5
    kernel = _use_pallas(sq, k.shape[2], dv, mesh)
    if kernel and d % _LANE:
        # Zero columns up to the next lane multiple: nothing in a score.
        pad = [(0, 0)] * 3 + [(0, -d % _LANE)]
        q, k = jnp.pad(q, pad), jnp.pad(k, pad)
        d = q.shape[3]
    out = _flash(
        q.reshape(b * hq, sq, d),
        k.reshape(b * hq, k.shape[2], d),
        v.reshape(b * hq, v.shape[2], dv),
        scale,
        causal,
        kernel,
    )
    return out.reshape(b, hq, sq, dv)
