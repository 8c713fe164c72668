"""The selection-side kernels of learned sparse attention alone, in interpret
mode: `tpuft_dsa_select`'s thresholds against an order statistic taken in
NumPy, `tpuft_dsa_index_loss` at several heads a loop body against one, and
the reader of the compiler's schedule (`tools/dsa_probe.py`) on a recorded
excerpt of a dump.  (The five kernels together against the XLA formulation:
tests/test_dsa_moe.py.)"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import sparse_attention as sa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BQ, BK = sa.BLOCK_Q, sa.BLOCK_K


def _index_operands(seed: int, seq: int, heads: int = 2, di: int = 64, quantised: bool = False):
    """Index queries, the key head transposed and the head weights, drawn on
    a lattice on which every score is exact in float32 in any order and with
    or without a fused multiply-add (sixteenths up to 4 in a and b, a power of
    two a weight), so that the reference below and the kernel under
    XLA:CPU's interpret mode see the same bits; positive weights, so that no
    score is -0.0 (which `_sortable` makes +0.0 on the chip and XLA:CPU
    leaves as it is)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lattice = lambda key, shape: (jnp.clip(jnp.round(jax.random.normal(key, shape) * 16.0), -64, 64) / 16.0).astype(jnp.bfloat16)  # noqa: E731
    a, b = lattice(ks[0], (1, heads, seq, di)), lattice(ks[1], (1, seq, di))
    w = 2.0 ** -jax.random.randint(ks[2], (1, seq, heads), 2, 6).astype(jnp.float32)
    if quantised:  # a handful of distinct scores: most rows tie at their threshold
        a, b = jnp.sign(a) * (jnp.abs(a) > 1.2), jnp.sign(b) * (jnp.abs(b) > 1.2)
        w = jnp.full_like(w, 0.25)
    return a, b.transpose(0, 2, 1), w


def _keys(a, bt, w) -> np.ndarray:
    """The int32 keys of every pair [S, S], tile by tile through the kernels'
    own `_index_tile` (the same products on the same shapes, so the same bits)."""
    seq = a.shape[2]
    keys = np.empty((seq, seq), np.int32)
    for r0 in range(0, seq, BQ):
        for c0 in range(0, seq, BK):
            score = sa._index_tile(a[:, :, r0:r0 + BQ], bt[0, :, c0:c0 + BK], w[0, r0:r0 + BQ], a.shape[1])
            keys[r0:r0 + BQ, c0:c0 + BK] = np.asarray(sa._sortable(score))
    return keys


def _order_statistic(keys: np.ndarray, topk: int):
    """Per query the min(t + 1, topk)-th largest of its visible keys, and the
    cut by position among the keys equal to it: where some query of a block
    of 256 has more of them than it may keep, the position of the last one
    kept (the lower positions first); in every other block the sequence's
    length."""
    seq = keys.shape[0]
    tau, cut = np.empty(seq, np.int32), np.full(seq, seq, np.int32)
    over = np.zeros(seq, bool)
    for t in range(seq):
        visible = keys[t, :t + 1]
        want = min(t + 1, topk)
        tau[t] = np.sort(visible)[-want]
        equal = np.flatnonzero(visible == tau[t])
        need = want - int((visible > tau[t]).sum())
        assert 1 <= need <= len(equal)
        over[t] = len(equal) > need
        cut[t] = equal[need - 1]
    for r0 in range(0, seq, BQ):
        if not over[r0:r0 + BQ].any():
            cut[r0:r0 + BQ] = seq
    return tau, cut, over


@pytest.mark.parametrize("quantised", [False, True], ids=["scores_from_the_seed", "most_rows_tie_at_the_threshold"])
@pytest.mark.parametrize("n", [1, 3, 5])
def test_the_select_kernels_thresholds_are_the_order_statistic(n, quantised) -> None:
    """`tpuft_dsa_select` at 512 n positions: `tau` is the min(t + 1, topk)-th
    largest of the row's visible keys and `cut` the position that keeps the
    first of the keys equal to it, bit for bit, whatever the order in which
    the kernel's counts add a tile's rows and lane blocks; `z` is the
    selection's log-sum-exp.  (Both draws leave every block of 256 queries a
    row with more keys at its threshold than it may keep, so `cut_step` runs
    in each; blocks without one are tests/test_dsa_moe.py's.)"""
    seq, topk = 512 * n, 200
    a, bt, w = _index_operands(10 + n, seq, quantised=quantised)
    tau, cut, z = sa._select_pallas(a, bt, w, topk, interpret=True)
    keys = _keys(a, bt, w)
    want_tau, want_cut, over = _order_statistic(keys, topk)
    if quantised:
        assert over.mean() > 0.5  # the `cut_step` passes run in every block
    assert np.array_equal(np.asarray(tau[0, :, 0]), want_tau)
    assert np.array_equal(np.asarray(cut[0, :, 0]), want_cut)
    cols = np.arange(seq)
    keep = (keys > want_tau[:, None]) | ((keys == want_tau[:, None]) & (cols[None] <= want_cut[:, None]))
    keep &= cols[None] <= cols[:, None]
    assert (keep.sum(1) == np.minimum(cols + 1, topk)).all()
    scores = np.asarray(sa._unsortable(jnp.asarray(keys)), np.float64)
    top = np.where(keep, scores, -np.inf).max(1)
    want_z = top + np.log(np.where(keep, np.exp(scores - top[:, None]), 0.0).sum(1))
    np.testing.assert_allclose(np.asarray(z[0, :, 0]), want_z, atol=1e-5)


@pytest.mark.parametrize("q_heads,kv,n,heads_a_body", [
    (32, 4, 2, 4), (8, 1, 2, 4), (8, 1, 5, 4), (6, 2, 2, 2), (6, 2, 5, 2), (3, 1, 2, 1), (3, 1, 5, 1),
])
def test_the_index_loss_at_several_heads_a_loop_body_is_bit_for_bit_the_loop_of_one(q_heads, kv, n, heads_a_body) -> None:
    """`tpuft_dsa_index_loss` with the heads a body that it reads from the
    shapes (four, two or one) against one head a body: the heads are added
    in the same order, so the KL rows and the three gradients keep their bits."""
    assert sa._heads_a_body(q_heads) == heads_a_body
    seq, d, topk = 512 * n, 128, 200
    ks = jax.random.split(jax.random.PRNGKey(q_heads + n), 2)
    q = jax.random.normal(ks[0], (1, seq, q_heads, d), jnp.bfloat16)  # position-major, as the kernel reads them
    k = jax.random.normal(ks[1], (1, seq, kv, d), jnp.bfloat16)
    a, bt, w = _index_operands(n, seq)
    scores = sa.index_scores(a, bt, w)
    keep = sa.selection_mask(scores, topk)
    mask = sa.packed_lower_triangle(keep.astype(jnp.int8))
    z = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)[..., None]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, q_heads // kv, axis=2), preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(jnp.where(keep[:, None], s * d ** -0.5, -jnp.inf), axis=-1)
    one = sa._index_loss_pallas(q, k, lse, a, bt, w, z, mask, d ** -0.5, interpret=True, heads_a_body=1)
    got = sa._index_loss_pallas(q, k, lse, a, bt, w, z, mask, d ** -0.5, interpret=True)
    for name, x, y in zip(("kl", "da", "dbt", "dw"), got, one):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name
    assert np.isfinite(np.asarray(got[0])).all() and float(jnp.sum(got[0])) > 0.0
    if heads_a_body > 1:
        with pytest.raises(AssertionError):
            sa._index_loss_pallas(q, k, lse, a, bt, w, z, mask, d ** -0.5, interpret=True, heads_a_body=q_heads + 1)


# -- tools/dsa_probe.py's reader ------------------------------------------------

# A dump's `final_bundles` as the compiler writes it, cut to a kernel of 16
# bundles: a grid loop (`>`) holding a loop of three bundles and one of four
# with a loop of two inside it (`>>>`); an empty bundle is left unmarked, a
# long comment runs over lines, and `PF` marks a predicated region, no loop.
_BUNDLES = """\
= control target key start
LH: loop header
LB: loop body
= control target key end

     0   :  { %s1_s0 = inlined_call_operand.hbm [shape: bf16[1,16,512,64], index: 0, kind: input, shape index: {}] }
   0x1   :  { %11 = vsyncpa [#allocation4], 0 } /* Start region 1 */
   0x2 LB: > { %s31_s24 = sadd.s32 4294967295, %s57_s23 /* iteration index, stage = 1 */  ;;  %s57_s23 = sphi %s64_s23, %s28_s23 }
   0x3   : > { %75 = dma.hbm_to_vmem [thread:$0]  (%p80_p9), /*hbm=*/%s593_s3 /*
base_bounds: (1, 4, 4096, 1)
hlo: tpuft_dsa_select.1
 */ }
   0x4 LB: >> { %v100_v0 = vld [vmem:[%s1_s1] sm:$0xff]  ;;  %v101_v1 = vld [vmem:[%s1_s1 + $0x8] sm:$0xff] }
   0x5   : >> { %v102_v2 = vcmp.ge.s32.totalorder %v100_v0, %v90_v9  ;;  %103 = vst [vmem:[#allocation9_spill] sm:$0xff] %v101_v1 }
   0x6   : >> { %v104_v3 = vadd.s32 %v102_v2, %v99_v8 }
   0x7   :  {}
   0x8 PF: > { %s40_s2 = sadd.s32 1, %s39_s2 }
   0x9 LB: >> { %v200_v0 = vmov 0 }
   0xa LB: >>> { %v201_v1 = vld [vmem:[%s1_s1] sm:$0xff]  ;;  %v202_v2 = vmatmul.bf16.vlgmr.msra.gmr.mxu0 %v200_v0 }
   0xb   : >>> { %v203_v3 = vadd.s32 %v201_v1, %v200_v0 }
   0xc   : >> { %v204_v4 = vsel %vm1_vm0, %v203_v3, %v200_v0 }
   0xd   : >> { %v205_v5 = vpow2.f32 %v204_v4 }
   0xe   : > { %s41_s2 = sadd.s32 1, %s40_s2 }
   0xf   :  { %12 = vsyncpa [#allocation4], 1 }
"""

_UTILIZATION = """\
== CAPACTIY:
MXU, XLU, VALU, EUP, VLOAD, VLOAD:FILL, VSTORE, VSTORE:SPILL, SALU
    4     3     4     1     3     3     1     1     2
== UTILIZATION:
0 0 0 0 0 0 0 0 0
0 0 0 0 0 0 0 0 0
0 0 0 0 0 0 0 0 2
0 0 0 0 0 0 0 0 0
0 0 0 0 2 0 0 0 0
0 0 1 0 0 0 1 1 0
0 0 1 0 0 0 0 0 0
0 0 0 0 0 0 0 0 0
0 0 0 0 0 0 0 0 1
0 0 1 0 0 0 0 0 0
1 0 0 0 1 1 0 0 0
0 0 1 0 0 0 0 0 0
0 0 1 0 0 0 0 0 0
0 0 0 1 0 0 0 0 0
0 0 0 0 0 0 0 0 1
0 0 0 0 0 0 0 0 0
"""


def test_the_schedule_reader_counts_a_recorded_dump_loop_by_loop(tmp_path) -> None:
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import dsa_probe
        import fa_bwd_probe
    finally:
        sys.path.remove(os.path.join(ROOT, "tools"))
    stem = "1790937878346104264-tpuft_dsa_select.1-"
    (tmp_path / (stem + "03-final_bundles.txt")).write_text(_BUNDLES)
    (tmp_path / (stem + "02-schedule-analysis_final_bundles.txt")).write_text("not the schedule\n")
    (tmp_path / (stem + "01-final_hlo-static-per-bundle-utilization.txt")).write_text(_UTILIZATION)
    bundles = fa_bwd_probe.schedule_bundles(str(tmp_path), "tpuft_dsa_select")
    assert [(number, label, depth) for number, label, depth, _ in bundles] == [
        (0, None, 0), (1, None, 0), (2, "LB", 1), (3, None, 1), (4, "LB", 2), (5, None, 2), (6, None, 2), (7, None, 2),
        (8, "PF", 1), (9, "LB", 2), (10, "LB", 3), (11, None, 3), (12, None, 2), (13, None, 2), (14, None, 1), (15, None, 0)]
    assert bundles[5][3] == ["vcmp.ge.s32.totalorder", "vst"] and bundles[3][3] == ["dma.hbm_to_vmem"]
    read = dsa_probe.read_loops(str(tmp_path), "tpuft_dsa_select")
    assert read["bundles"] == 16 and read["slots_a_bundle"]["VALU"] == 4 and read["slots_a_bundle"]["VSTORE"] == 1
    grid, first, second, inner = read["loops"]
    assert [(loop["depth"], loop["first_bundle"], loop["bundles"], loop["with_inner"]) for loop in read["loops"]] == [
        (1, 2, 4, 13), (2, 4, 4, 4), (2, 9, 3, 5), (3, 10, 2, 2)]
    assert (first["spill_stores"], first["slots_taken"]["VALU"], first["slots_taken"]["VLOAD"]) == (1, 2, 2)
    assert (inner["spill_fills"], inner["slots_taken"]["MXU"], inner["slots_taken"]["VALU"]) == (1, 1, 1)
    assert second["slots_taken"]["EUP"] == 1 and second["slots_taken"]["MXU"] == 0
    assert grid["spill_stores"] == 0 and sum(grid["slots_taken"].values()) == 0


def test_the_forward_attention_tile_schedules_under_1200_bundles_a_head() -> None:
    """`tpuft_fa_fwd` at 8 x 4,096 x 128, eight heads a grid step, compiled
    for a described v5e (no chip: `tools/fa_bwd_probe.py`'s child process,
    which ends in the compiler's abort after the schedule is written) and the
    compiler's final schedule counted: a head's tile stands at 1,127 bundles
    over 1,024 cycles of products, the MXUs' slots 89% taken, since the step
    walks its heads with a skew of one and every head's p v but the last
    starts under the next head's softmax tile, before the last exponential
    (PR 64).  It was 1,317 at 75% with the heads under one `jax.vmap`, all
    eight p v back to back after the last exponential (PR 62: lane-replicated
    statistics; 1,860 with one-column statistics narrowed and broadcast again
    a row group).  A change that strands the p v again, or puts the broadcasts
    back, fails here and not in a cell."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import fa_bwd_probe
    finally:
        sys.path.remove(os.path.join(ROOT, "tools"))
    read = fa_bwd_probe.kernel_schedule("8x4096x128", "fwd")
    if "libtpu multi-process lockfile" in read.get("error", ""):
        pytest.skip("another process holds the TPU's library and ALLOW_MULTIPLE_LIBTPU_LOAD is not set")
    assert "error" not in read, read["error"]
    heads = len(read["pv_starts"])
    assert heads == len(read["qk_starts"]) == 8 and 1024 < read["tile_bundles"] / heads < 1200, read
    assert read["mxu_slots_percent"] >= 85, read
    assert read["pv_before_last_exp"] >= heads - 1, read
