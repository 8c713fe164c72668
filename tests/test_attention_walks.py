"""The flash-attention kernels' walks, traced or in interpret mode: the lower
triangle tile by tile, several heads a grid step, the band under a window, the
grids at the cells' lengths."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import attention_forms as forms
from test_ops import pallas_call_grids, pallas_call_names


def _masked_reference(q, k, v, keep, scale):
    """Dense softmax attention over the pairs `keep` [S, S] allows: (out, lse)."""
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    s = jnp.where(keep, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", jnp.exp(s - lse[..., None]), v), lse


def check_the_triangular_walk(n: int, d_qk: int, d_v: int, kv_group: int, masked: bool) -> None:
    """The flash kernels in interpret mode at n tiles a side, forward (out,
    lse) and one-pass backward (dq, dk, dv), against the XLA formulations:
    causal against `_fa_reference` / `_fa_bwd_xla`, under a packed per-pair
    mask (a seeded third of the visible pairs, the diagonal among them)
    against dense masked softmax attention and its autodiff.  With
    `kv_group` the kernels read one KV head for a group of query heads and
    give dk, dv a query head each.  Each call's grid is (heads / H, n (n +
    1) / 2): a step for each tile of the lower triangle and no other, H heads
    a step."""
    from torchft_tpu.ops import attention as fa
    from torchft_tpu.ops import sparse_attention as sa

    seq, heads = 512 * n, max(2, kv_group)
    ks = jax.random.split(jax.random.PRNGKey(100 * n + kv_group + masked), 5)
    q = jax.random.normal(ks[0], (heads, seq, d_qk), jnp.float32)
    k = jax.random.normal(ks[1], (heads // kv_group, seq, d_qk), jnp.float32)
    v = jax.random.normal(ks[2], (heads // kv_group, seq, d_v), jnp.float32)
    g = jax.random.normal(ks[3], (heads, seq, d_v), jnp.float32)
    scale = d_qk ** -0.5
    k_all, v_all = jnp.repeat(k, kv_group, axis=0), jnp.repeat(v, kv_group, axis=0)
    more = {"kv_group": kv_group}
    if masked:
        keep = (jax.random.bernoulli(ks[4], 0.3, (seq, seq)) | jnp.eye(seq, dtype=bool)) & jnp.tril(jnp.ones((seq, seq), bool))
        more["mask"] = sa.packed_lower_triangle(keep[None]).astype(jnp.int8)
        (want_o, want_lse), vjp = jax.vjp(lambda *qkv: _masked_reference(*qkv, keep, scale), q, k_all, v_all)
        want = vjp((g, jnp.zeros_like(want_lse)))
    else:
        want_o, want_lse = fa._fa_reference(q, k_all, v_all, scale, True)
        want = fa._fa_bwd_xla(q, k_all, v_all, want_o, want_lse, g, scale, True)
    fwd = functools.partial(forms.fwd, scale=scale, causal=True, interpret=True, **more)
    bwd = functools.partial(forms.bwd, scale=scale, causal=True, interpret=True, **more)
    got_o, got_lse = fwd(q, k, v)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got_lse), np.asarray(want_lse), rtol=2e-3, atol=2e-3)
    got = bwd(q, k, v, got_o, got_lse, g)
    assert [a.shape for a in got] == [q.shape, (heads, seq, d_qk), (heads, seq, d_v)]
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=name)
    # H heads a grid step, H read from the shapes: all of these heads (one KV head's, or a batch
    # entry's) forward; backward as many of them as their dq rows leave room for
    tiles, share = n * (n + 1) // 2, heads
    fwd_heads = fa._heads_per_step(share)
    bwd_heads = fa._bwd_heads_per_step(share, fa._row_vmem_bytes(seq, d_qk, 4))
    assert fwd_heads == heads and bwd_heads > 1
    assert pallas_call_grids(fwd, q, k, v) == {("tpuft_dsa_attn_fwd" if masked else "tpuft_fa_fwd"): (heads // fwd_heads, tiles)}
    assert pallas_call_grids(bwd, q, k, v, got_o, got_lse, g) == {
        ("tpuft_dsa_attn_bwd_dkdv_dq" if masked else "tpuft_fa_bwd_dkdv_dq"): (heads // bwd_heads, tiles)}


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "masked"])
@pytest.mark.parametrize("kv_group", [1, 8])
@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_flash_kernels_walk_the_lower_triangle(n, kv_group, masked) -> None:
    check_the_triangular_walk(n, 128, 128, kv_group, masked)


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "masked"])
def test_short_rows_of_dq_are_cast_out_at_their_diagonal_step(masked) -> None:
    """Three tiles a side, one pass: on the square grid every q tile's dq
    rows left the f32 row at kv tile 2's steps, which a triangular walk
    visits for q tile 2 alone.  The rows of q tiles 0 and 1 are complete —
    and have to be cast into the output — at kv tiles 0 and 1."""
    from torchft_tpu.ops import attention as fa
    from torchft_tpu.ops import sparse_attention as sa

    seq = 1536
    q, k, v, g = (jax.random.normal(kk, (2, seq, 128), jnp.bfloat16) for kk in jax.random.split(jax.random.PRNGKey(3), 4))
    mask = sa.packed_lower_triangle(jnp.tril(jnp.ones((1, seq, seq), jnp.int8))) if masked else None
    o, lse = forms.fwd(q, k, v, 0.088, True, interpret=True, mask=mask)
    dq, _, _ = forms.bwd(q, k, v, o, lse, g, 0.088, True, interpret=True, mask=mask)
    want, _, _ = fa._fa_bwd_xla(q, k, v, o, lse, g, 0.088, True)
    dq, want = np.asarray(dq, np.float32), np.asarray(want, np.float32)
    for qi in range(3):
        rows = slice(512 * qi, 512 * (qi + 1))
        assert np.abs(dq[:, rows]).max() > 0.01, f"q tile {qi}: nothing was written"
        assert np.linalg.norm(dq[:, rows] - want[:, rows]) < 0.01 * np.linalg.norm(want[:, rows]), f"q tile {qi}"


@pytest.mark.parametrize("heads_per_step", [2, 4])
@pytest.mark.parametrize("kind", ["causal", "rectangle", "window", "masked_kv_group_8", "unequal_widths"])
def test_heads_a_grid_step_are_bitwise_one_head_a_step(kind, heads_per_step) -> None:
    """out, lse, dq, dk, dv with H heads a grid step — a head a column block of
    its operands' blocks, every scratch leading with the heads, the tile's
    arithmetic a head at a time — are bit for bit those of one head a step: over the triangle, a rectangle (queries
    against a longer key sequence, not causal), the band, a packed mask whose
    eight query heads read one KV head in place (and share the mask's tile),
    and query and key 256 wide beside a value of 128."""
    from torchft_tpu.ops import attention as fa
    from torchft_tpu.ops import sparse_attention as sa

    bh, seq_q, seq_k, d, dv, kv_group = 8, 1024, 1024, 128, 128, 1
    causal, more = True, {}
    if kind == "rectangle":
        causal, seq_k = False, 1536
    elif kind == "window":
        seq_q = seq_k = 1536
        more["window"] = 600
    elif kind == "masked_kv_group_8":
        kv_group = 8
        keep = jax.random.bernoulli(jax.random.PRNGKey(5), 0.3, (seq_q, seq_q)) | jnp.eye(seq_q, dtype=bool)
        more.update(mask=sa.packed_lower_triangle((keep & jnp.tril(jnp.ones_like(keep)))[None]).astype(jnp.int8), kv_group=8)
    elif kind == "unequal_widths":
        bh, d = 4, 256
    ks = jax.random.split(jax.random.PRNGKey(len(kind)), 4)
    q = jax.random.normal(ks[0], (bh, seq_q, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (bh // kv_group, seq_k, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (bh // kv_group, seq_k, dv), jnp.bfloat16)
    g = jax.random.normal(ks[3], (bh, seq_q, dv), jnp.bfloat16)

    def kernels(heads):
        fwd = functools.partial(forms.fwd, scale=0.07, causal=causal, interpret=True, heads_per_step=heads, **more)
        bwd = functools.partial(forms.bwd, scale=0.07, causal=causal, interpret=True, heads_per_step=heads, **more)
        o, lse = fwd(q, k, v)
        grids = {**pallas_call_grids(fwd, q, k, v), **pallas_call_grids(bwd, q, k, v, o, lse, g)}
        assert len(grids) == 2 and {grid[0] for grid in grids.values()} == {bh // heads}, grids
        return (o, lse) + tuple(bwd(q, k, v, o, lse, g))

    want = kernels(1)
    assert all(float(jnp.abs(x.astype(jnp.float32)).max()) > 0.01 for x in want)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), kernels(heads_per_step), want):
        assert a.dtype == b.dtype and a.shape == b.shape and bool(jnp.array_equal(a, b)), name


def _column_stats_fwd_scores(q, k, m_prev, l_prev, keep, *, scale):
    """The forward tile's first half as it stood before PR 62, kept here: the
    running max and sum ONE COLUMN, [block_q, 1], broadcast along the lanes
    wherever the scores want them.  It takes the kernel's lane-replicated
    [block_q, 128] statistics by their first column and hands its own back
    broadcast, so that `_fa_kernel` runs it in `_fwd_scores`' place; the
    rescale goes on to the second half as the column it is."""
    from torchft_tpu.ops import attention as fa

    lanes = m_prev.shape
    m_prev, l_prev = m_prev[:, :1], l_prev[:, :1]
    s = jax.lax.dot_general(q, k, fa._NT, preferred_element_type=jnp.float32) * scale
    if keep is not None:
        s = jnp.where(keep, s, fa._NEG_INF)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    return jnp.broadcast_to(m_cur, lanes), alpha, jnp.broadcast_to(l_new, lanes), p


def _column_stats_fwd_accumulate(acc, alpha, p, v):
    """The second half of that tile, in `_fwd_accumulate`'s place: the
    accumulator rescaled by the one column."""
    return acc * alpha + jax.lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)


# kind: (batch * heads, positions, query and key width, query heads a KV head, H by the module's rule)
LANE_REPLICATED_CASES = {
    "causal": (8, 1024, 128, 1, 8),
    "window_512": (8, 1536, 128, 1, 8),
    "packed_mask": (8, 1024, 128, 8, 8),
    "unequal_widths": (4, 1024, 256, 1, 4),
    "kv_group_4_aligned": (8, 1024, 128, 4, 8),
    "kv_group_4_straddling": (12, 1024, 128, 4, 6),
}


@pytest.mark.parametrize("heads_per_step", [1, None], ids=["one_head", "the_rule"])
@pytest.mark.parametrize("kind", sorted(LANE_REPLICATED_CASES))
def test_lane_replicated_statistics_are_bitwise_one_column(kind, heads_per_step, monkeypatch) -> None:
    """The forward kernel keeps a row's running max and sum lane-replicated,
    [block_q, 128] from scratch to scratch (PR 62).  Its out and lse are bit
    for bit those of the same kernel around the tile of one-column statistics
    kept above: over the triangle, the band under a window of 512, a packed
    mask, query and key 256 wide beside a value of 128, and a KV head read in
    place for four query heads by steps that hold whole groups and by steps
    that straddle two, at one head a step and at the H the shapes give."""
    from torchft_tpu.ops import attention as fa
    from torchft_tpu.ops import sparse_attention as sa

    bh, seq, d, kv_group, rule = LANE_REPLICATED_CASES[kind]
    more = {"kv_group": kv_group, "heads_per_step": heads_per_step}
    if kind == "window_512":
        more["window"] = 512
    elif kind == "packed_mask":
        keep = jax.random.bernoulli(jax.random.PRNGKey(62), 0.3, (seq, seq)) | jnp.eye(seq, dtype=bool)
        more["mask"] = sa.packed_lower_triangle((keep & jnp.tril(jnp.ones_like(keep)))[None]).astype(jnp.int8)
    heads = heads_per_step or fa._heads_per_step(bh)
    assert heads == (heads_per_step or rule) and fa._straddles(heads, kv_group) == (kind == "kv_group_4_straddling" and heads > 1)
    ks = jax.random.split(jax.random.PRNGKey(len(kind)), 3)
    q = jax.random.normal(ks[0], (bh, seq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (bh // kv_group, seq, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (bh // kv_group, seq, 128), jnp.bfloat16)
    fwd = functools.partial(forms.fwd, scale=0.07, causal=True, interpret=True, **more)
    assert pallas_call_grids(fwd, q, k, v).popitem()[1][0] == bh // heads
    o, lse = fwd(q, k, v)
    monkeypatch.setattr(fa, "_fwd_scores", _column_stats_fwd_scores)
    monkeypatch.setattr(fa, "_fwd_accumulate", _column_stats_fwd_accumulate)
    want_o, want_lse = fwd(q, k, v)
    assert float(jnp.abs(o.astype(jnp.float32)).max()) > 0.01 and bool(jnp.all(jnp.isfinite(lse)))
    assert o.dtype == want_o.dtype and bool(jnp.array_equal(o, want_o)), "out"
    assert lse.dtype == want_lse.dtype and bool(jnp.array_equal(lse, want_lse)), "lse"


def _vmapped_fwd_step(keep, q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *, scale, kv_group, q_heads):
    """The forward step as it stood before PR 64, kept here: all the step's
    heads at once, the tile's two halves one function of values under
    `jax.vmap`, every block (its heads side by side since PR 65, stacked
    here) and scratch read and stored whole."""
    from torchft_tpu.ops import attention as fa

    heads = m_scr.shape[0]

    def tile(q, k, v, m_prev, l_prev, acc):
        m_cur, alpha, l_new, p = fa._fwd_scores(q, k, m_prev, l_prev, keep, scale=scale)
        return m_cur, l_new, fa._fwd_accumulate(acc, alpha, p, v)

    stacked = lambda head: jnp.stack([head(h) for h in range(heads)])  # noqa: E731
    m_scr[...], l_scr[...], acc_scr[...] = jax.vmap(tile)(
        stacked(lambda h: fa._head(q_ref, h, heads)), stacked(lambda h: fa._kv_head(k_ref, h, heads, kv_group, q_heads)),
        stacked(lambda h: fa._kv_head(v_ref, h, heads, kv_group, q_heads)), m_scr[...], l_scr[...], acc_scr[...])


# At seven heads a step the cases run 28 heads (smallthinker's 7 of 28), their KV heads a group of seven where a step
# holds whole groups and of four where it straddles two; at one head and at the rule's H, `LANE_REPLICATED_CASES`' shapes.
SEVEN_OF_28 = {"packed_mask": 7, "kv_group_4_aligned": 7}


@pytest.mark.parametrize("heads_per_step", [1, 7, None], ids=["one_head", "seven_of_28", "the_rule"])
@pytest.mark.parametrize("kind", sorted(LANE_REPLICATED_CASES))
def test_the_skewed_heads_of_a_step_are_bitwise_the_heads_at_once(kind, heads_per_step, monkeypatch) -> None:
    """The forward step walks its heads with a skew of one (PR 64: head h's
    scores, max, exp and sum, then head h - 1's rescale and p v).  Out and lse
    are bit for bit those of the same kernel around the step kept above, all
    heads under one `jax.vmap`: over the triangle, the band under a window of
    512, a packed mask (its tile read once for the step's heads), query and key
    256 wide beside a value of 128, and KV heads read in place by steps that
    hold whole groups and by steps that straddle two — at one head a step
    (first half, then second: no skew to speak of), at seven of 28 and at the H
    the shapes give.  The backward step runs `_bwd_tile` a head at a time (PR
    65): dq, dk and dv bit for bit those of the step kept beside the forward's,
    the heads' slices stacked under one `jax.vmap`."""
    from torchft_tpu.ops import attention as fa
    from torchft_tpu.ops import sparse_attention as sa

    bh, seq, d, kv_group, rule = LANE_REPLICATED_CASES[kind]
    if heads_per_step == 7:
        bh, kv_group = 28, SEVEN_OF_28.get(kind, kv_group)
    more = {"kv_group": kv_group, "heads_per_step": heads_per_step}
    if kind == "window_512":
        more["window"] = 512
    elif kind == "packed_mask":
        keep = jax.random.bernoulli(jax.random.PRNGKey(64), 0.3, (seq, seq)) | jnp.eye(seq, dtype=bool)
        more["mask"] = sa.packed_lower_triangle((keep & jnp.tril(jnp.ones_like(keep)))[None]).astype(jnp.int8)
    heads = heads_per_step or fa._heads_per_step(bh)
    assert heads == (heads_per_step or rule)
    assert fa._straddles(heads, kv_group) == (kind == "kv_group_4_straddling" and heads > 1)
    ks = jax.random.split(jax.random.PRNGKey(len(kind) + heads), 4)
    q = jax.random.normal(ks[0], (bh, seq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (bh // kv_group, seq, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (bh // kv_group, seq, 128), jnp.bfloat16)
    g = jax.random.normal(ks[3], (bh, seq, 128), jnp.bfloat16)
    fwd = functools.partial(forms.fwd, scale=0.07, causal=True, interpret=True, **more)
    bwd = functools.partial(forms.bwd, scale=0.07, causal=True, interpret=True, **more)
    assert pallas_call_grids(fwd, q, k, v).popitem()[1][0] == bh // heads
    o, lse = fwd(q, k, v)
    grads = bwd(q, k, v, o, lse, g)
    monkeypatch.setattr(fa, "_fwd_step", _vmapped_fwd_step)
    monkeypatch.setattr(fa, "_bwd_step", _vmapped_bwd_step)  # the backward's heads, one at a time since PR 65
    want_o, want_lse = fwd(q, k, v)
    assert float(jnp.abs(o.astype(jnp.float32)).max()) > 0.01 and bool(jnp.all(jnp.isfinite(lse)))
    assert o.dtype == want_o.dtype and bool(jnp.array_equal(o, want_o)), "out"
    assert lse.dtype == want_lse.dtype and bool(jnp.array_equal(lse, want_lse)), "lse"
    for name, a, b in zip(("dq", "dk", "dv"), grads, bwd(q, k, v, o, lse, g)):
        assert float(jnp.abs(a.astype(jnp.float32)).max()) > 0.01 and a.dtype == b.dtype and bool(jnp.array_equal(a, b)), name


def test_two_dq_rows_over_the_vmem_budget_run_one_head_a_step() -> None:
    """H is read from the shapes: the largest divisor of the heads not above
    `HEADS_PER_STEP` whose dq rows and tiles fit VMEM.  At 65,536 x 128 one
    head's row, its output block and tiles are 80 MiB: two do not fit, the
    backward stays at one head a step while the forward, which keeps no row,
    takes both; the two-pass form over a longer row keeps no row either."""
    from torchft_tpu.ops import attention as fa

    mib = 2 ** 20
    row = fa._row_vmem_bytes(65536, 128, 2)
    assert row == 64 * mib and 2 * (row + fa._TILE_VMEM_BYTES) > fa._VMEM_BUDGET
    assert fa._bwd_heads_per_step(8, row) == 1 and fa._bwd_heads_per_step(8, 0) == fa.HEADS_PER_STEP == 8
    assert [fa._bwd_heads_per_step(32, fa._row_vmem_bytes(seq, d, 2)) for seq, d in
            ((4096, 128), (8192, 256), (16384, 128), (16384, 256), (32768, 128))] == [4, 4, 4, 2, 2]
    assert [fa._heads_per_step(share) for share in (1, 2, 7, 8, 28, 32, 48, 64)] == [1, 2, 7, 8, 7, 8, 8, 8]
    bh, seq = 2, 65536
    n = seq // 512
    qkv = jax.ShapeDtypeStruct((bh, seq, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32)
    assert pallas_call_grids(functools.partial(forms.fwd, scale=0.088, causal=True), qkv, qkv, qkv) == {
        "tpuft_fa_fwd": (1, n * (n + 1) // 2)}
    assert pallas_call_grids(functools.partial(forms.bwd, scale=0.088, causal=True), qkv, qkv, qkv, qkv, lse, qkv) == {
        "tpuft_fa_bwd_dkdv_dq": (2, n * (n + 1) // 2)}
    longer = jax.ShapeDtypeStruct((bh, seq + 512, 128), jnp.bfloat16)
    assert {grid[0] for grid in pallas_call_grids(
        functools.partial(forms.bwd, scale=0.088, causal=True), longer, longer, longer, longer,
        jax.ShapeDtypeStruct((bh, seq + 512), jnp.float32), longer).values()} == {1}


def pallas_call_operands(fn, *args) -> dict:
    """{name: the operands' shapes, the walk's tables left out} of every `pallas_call` in `fn`'s jaxpr, however deep."""
    def calls(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["name"], [v.aval.shape for v in e.invars[e.params["grid_mapping"].num_index_operands:]]
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from calls(sub)

    return dict(calls(jax.make_jaxpr(fn)(*args).jaxpr))


# (batch * heads, positions, query and key width, value width, window, query heads a KV head, under a packed mask): the
# forward's and the backward's heads a grid step
CELL_SHAPES = {
    "dense_16_heads": ((32, 4096, 128, 128, None, 2, False), (8, 4)),   # InternLM2: 16 / 8 heads
    "olmoe": ((32, 4096, 128, 128, None, 1, False), (8, 4)),
    "dense_32_heads": ((64, 4096, 128, 128, None, 4, False), (8, 4)),   # Mistral: 32 / 8
    "moonlight": ((32, 8192, 256, 128, None, 1, False), (8, 4)),
    "keye_masked": ((32, 32768, 128, 128, None, 8, True), (8, 2)),
    "laguna_full": ((48, 16384, 128, 128, None, 6, False), (8, 4)),
    "laguna_window": ((64, 16384, 128, 128, 512, 8, False), (8, 4)),
    "zaya": ((8, 16384, 128, 128, None, 4, False), (8, 4)),
    "kimi": ((32, 16384, 256, 128, None, 1, False), (8, 2)),
    "smallthinker_full": ((28, 16384, 128, 128, None, 7, False), (7, 4)),
    "smallthinker_window": ((28, 16384, 128, 128, 4096, 7, False), (7, 4)),
    "nemotron": ((32, 16384, 128, 128, None, 16, False), (8, 4)),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_heads_a_grid_step_at_the_cells_shapes(cell) -> None:
    """The traced `pallas_call`s at every cell's attention shape (no kernel
    runs): grid (batch * heads / H, tiles) with more than one head a step in
    both directions, every operand position-major — q [1, S, heads * d], k and
    v given to the kernels with their own KV heads, heads / `kv_group` column
    blocks, no repeated copy — while dk and dv leave a query head each."""
    from torchft_tpu.ops import attention as fa

    (bh, seq, d, dv, window, kv_group, masked), (fwd_heads, bwd_heads) = CELL_SHAPES[cell]
    n = seq // 512
    tiles = len(fa._Walk(True, seq, seq, 512, 512, window=window).tables[0])
    assert tiles == (n * (n + 1) // 2 if window is None else {512: 2 * n - 1, 4096: 252}[window])
    q = jax.ShapeDtypeStruct((bh, seq, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((bh // kv_group, seq, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((bh // kv_group, seq, dv), jnp.bfloat16)
    o = jax.ShapeDtypeStruct((bh, seq, dv), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32)
    mask = jax.ShapeDtypeStruct((1, tiles, 512, 512), jnp.int8) if masked else None
    more = {"kv_group": kv_group, "window": window}
    family = "tpuft_dsa_attn" if masked else "tpuft_fa" if window is None else "tpuft_swa"
    assert min(fwd_heads, bwd_heads) > 1
    fwd = lambda q_, k_, v_, m_: forms.fwd(q_, k_, v_, 0.088, True, mask=m_, **more)  # noqa: E731
    bwd = lambda q_, k_, v_, o_, l_, g_, m_: forms.bwd(q_, k_, v_, o_, l_, g_, 0.088, True, mask=m_, **more)  # noqa: E731
    assert pallas_call_grids(fwd, q, k, v, mask) == {family + "_fwd": (bh // fwd_heads, tiles)}
    assert pallas_call_grids(bwd, q, k, v, o, lse, o, mask) == {family + "_bwd_dkdv_dq": (bh // bwd_heads, tiles)}
    wide = [(1, seq, bh * d), (1, seq, bh // kv_group * d), (1, seq, bh // kv_group * dv)]
    (operands,) = pallas_call_operands(fwd, q, k, v, mask).values()
    assert operands[:3] == wide
    (operands,) = pallas_call_operands(bwd, q, k, v, o, lse, o, mask).values()
    assert operands[:3] == wide and operands[3] == (1, seq, bh * dv)
    dq, dk, dv_ = jax.eval_shape(bwd, q, k, v, o, lse, o, mask)
    assert (dq.shape, dk.shape, dv_.shape) == (q.shape, (bh, seq, d), (bh, seq, dv))


def test_flash_attention_gives_the_kernels_k_and_v_unrepeated(monkeypatch) -> None:
    """`flash_attention` where the kernels run, through autodiff: every
    operand of the forward and the backward `pallas_call` is position-major,
    [B, S, heads * d], q with its query heads' columns and k and v with their
    KV heads' — no repeated copy — and dk, dv come back in k's and v's shapes."""
    from torchft_tpu.ops import attention as fa

    monkeypatch.setattr(fa._pallas_util, "kernels_apply", lambda mesh=None: True)
    b, hq, hkv, seq = 2, 14, 2, 1024
    q = jax.ShapeDtypeStruct((b, seq, hq, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, seq, hkv, 128), jnp.bfloat16)
    for window, family in ((None, "tpuft_fa"), (600, "tpuft_swa")):
        loss = lambda q_, k_, v_: jnp.sum(fa.flash_attention(q_, k_, v_, window=window).astype(jnp.float32))  # noqa: E731,B023
        grads = jax.grad(loss, argnums=(0, 1, 2))
        assert [a.shape for a in jax.eval_shape(grads, q, kv, kv)] == [q.shape, kv.shape, kv.shape]
        found = pallas_call_operands(grads, q, kv, kv)
        assert sorted(found) == [family + "_bwd_dkdv_dq", family + "_fwd"]
        for operands in found.values():
            assert operands[:3] == [(b, seq, hq * 128), (b, seq, hkv * 128), (b, seq, hkv * 128)]


# query heads a KV head: (batch * heads, heads a step forward, backward) of an interpret-mode case whose steps hold whole
# groups or lie inside one, and of one whose steps straddle two KV heads' groups
IN_PLACE_CASES = {
    "aligned": {2: (8, 8, 4), 4: (8, 8, 4), 6: (12, 6, 3), 7: (14, 7, 7), 16: (16, 8, 4)},
    "straddling": {2: (6, 3, 3), 4: (12, 6, 3), 6: (24, 8, 4), 7: (28, 4, 4), 16: (48, 6, 6)},
}


@pytest.mark.parametrize("step", ["aligned", "straddling"])
@pytest.mark.parametrize("kind", ["plain", "window"])
@pytest.mark.parametrize("kv_group", [2, 4, 6, 7, 16])
def test_grouped_queries_read_their_kv_head_in_place(kv_group, kind, step) -> None:
    """The plain and the window kernels in interpret mode with k and v a KV
    head each, against the same kernels at the same heads a step fed k and v
    repeated to a head for every query head: out, lse, dq, dk and dv (a query
    head each) bit for bit; `group_sum` of dk and dv is the float32 sum of a
    group rounded once.  An aligned step holds whole groups (a group of 2 or
    4 in 8 heads) or lies inside one; a straddling step's heads belong to two
    KV heads, as four of smallthinker's group of seven do."""
    from torchft_tpu.ops import attention as fa

    bh, fwd_heads, bwd_heads = IN_PLACE_CASES[step][kv_group]
    for heads in (fwd_heads, bwd_heads):
        assert fa._straddles(heads, kv_group) == (step == "straddling"), heads
    seq, window = 1024, 600 if kind == "window" else None
    ks = jax.random.split(jax.random.PRNGKey(16 * kv_group + (window or 0)), 4)
    q = jax.random.normal(ks[0], (bh, seq, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (bh // kv_group, seq, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (bh // kv_group, seq, 128), jnp.bfloat16)
    g = jax.random.normal(ks[3], (bh, seq, 128), jnp.bfloat16)
    k_all, v_all = jnp.repeat(k, kv_group, axis=0), jnp.repeat(v, kv_group, axis=0)
    kw = dict(scale=0.088, causal=True, interpret=True, window=window)
    o, lse = forms.fwd(q, k, v, kv_group=kv_group, heads_per_step=fwd_heads, **kw)
    want_o, want_lse = forms.fwd(q, k_all, v_all, heads_per_step=fwd_heads, **kw)
    assert bool(jnp.array_equal(o, want_o)) and bool(jnp.array_equal(lse, want_lse))
    assert float(jnp.abs(o.astype(jnp.float32)).max()) > 0.01
    got = forms.bwd(q, k, v, o, lse, g, kv_group=kv_group, heads_per_step=bwd_heads, **kw)
    want = forms.bwd(q, k_all, v_all, o, lse, g, heads_per_step=bwd_heads, **kw)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == (bh, seq, 128) and bool(jnp.array_equal(a, b)), name
    for name, a in zip(("dk", "dv"), got[1:]):
        summed = np.asarray(a, np.float32).reshape(bh // kv_group, kv_group, seq, 128).sum(axis=1)
        folded = forms.heads(fa.group_sum(forms.rows(a), bh // kv_group, kv_group), bh // kv_group)
        assert folded.shape == k.shape and folded.dtype == k.dtype
        assert bool(jnp.array_equal(folded, jnp.asarray(summed).astype(jnp.bfloat16))), name


@pytest.mark.parametrize("seq", [4096, 8192, 32768])
def test_the_attention_grids_at_the_cells_lengths(seq) -> None:
    """The traced `pallas_call`s at the cells' three lengths (no kernel
    runs): causal and masked calls have a step for each of the n (n + 1) / 2
    tiles of the lower triangle, a non-causal call the whole square."""
    from torchft_tpu.ops import attention as fa

    bh, n = 8, seq // 512
    tiles = n * (n + 1) // 2
    qkv = jax.ShapeDtypeStruct((bh, seq, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((bh // 8, seq, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32)
    mask = jax.ShapeDtypeStruct((1, tiles, 512, 512), jnp.int8)

    def fwd(causal, **more):
        return functools.partial(forms.fwd, scale=0.088, causal=causal, **more)

    def bwd(causal, **more):
        return functools.partial(forms.bwd, scale=0.088, causal=causal, **more)

    # eight heads a step forward; backward as many as their dq rows (seq x 128 x 8 bytes a head) and
    # tiles leave room for in VMEM: four, four and two
    f, b = bh // fa._heads_per_step(bh), bh // fa._bwd_heads_per_step(bh, fa._row_vmem_bytes(seq, 128, 2))
    assert (f, b) == (1, {4096: 2, 8192: 2, 32768: 4}[seq])
    assert pallas_call_grids(fwd(True), qkv, qkv, qkv) == {"tpuft_fa_fwd": (f, tiles)}
    assert pallas_call_grids(bwd(True), qkv, qkv, qkv, qkv, lse, qkv) == {"tpuft_fa_bwd_dkdv_dq": (b, tiles)}
    assert pallas_call_grids(lambda q, k, v, m: fwd(True, kv_group=8)(q, k, v, mask=m), qkv, kv, kv, mask) == {
        "tpuft_dsa_attn_fwd": (f, tiles)}
    assert pallas_call_grids(lambda q, k, v, o, l, g, m: bwd(True, kv_group=8)(q, k, v, o, l, g, mask=m),
                             qkv, kv, kv, qkv, lse, qkv, mask) == {"tpuft_dsa_attn_bwd_dkdv_dq": (b, tiles)}
    assert pallas_call_grids(fwd(False), qkv, qkv, qkv) == {"tpuft_fa_fwd": (f, n, n)}
    assert pallas_call_grids(bwd(False), qkv, qkv, qkv, qkv, lse, qkv) == {"tpuft_fa_bwd_dkdv_dq": (b, n, n)}
    # the walk's tables: the forward's row by row (step t is the packed
    # mask's tile t), the backward's column by column
    rows, cols = (np.asarray(t) for t in fa._Walk(True, seq, seq, 512, 512).tables)
    assert [(int(i), int(j)) for i, j in zip(rows[:4], cols[:4])] == [(0, 0), (1, 0), (1, 1), (2, 0)]
    assert (np.asarray(fa._tri(rows, cols)) == np.arange(tiles)).all() and (cols <= rows).all()
    rows, cols = (np.asarray(t) for t in fa._Walk(True, seq, seq, 512, 512, kv_major=True).tables)
    assert (cols[:n] == 0).all() and (rows[:n] == np.arange(n)).all() and (rows[n], cols[n]) == (1, 1)
    assert len(rows) == tiles and (cols <= rows).all() and (np.diff(cols) >= 0).all()


def _dense_window_attention(q, k, v, g, scale, window):
    """Windowed causal attention written out with a dense mask built from
    positions, in plain `jax.numpy`: (o, dq, dk, dv) for the cotangent g."""
    def out(q, k, v):
        t = jnp.arange(q.shape[1])
        d = t[:, None] - t[None, :]
        s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
        p = jax.nn.softmax(jnp.where((d >= 0) & (d < window), s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, v)

    o, vjp = jax.vjp(out, q, k, v)
    return (o,) + vjp(g)


# seq 2048 under 512 x 512 tiles: a window of one tile, one that divides no tile, one narrower than a
# tile, one of two tiles and a bit, and the last position short of the sequence
@pytest.mark.parametrize("window", [512, 300, 37, 1100, 2047])
def test_windowed_flash_kernels_match_a_dense_mask(window) -> None:
    """The band-walk kernels in interpret mode, forward and all three
    gradients, against attention over a dense mask; and the XLA fallback
    against the same."""
    from torchft_tpu.ops import attention as fa

    seq, scale = 2048, 0.088
    rng = np.random.default_rng(window)
    q, k, v, g = (jnp.asarray(rng.standard_normal((2, seq, 128)), dtype=jnp.float32) for _ in range(4))
    want = _dense_window_attention(q, k, v, g, scale, window)
    o, lse = forms.fwd(q, k, v, scale, True, interpret=True, window=window)
    bwd = functools.partial(forms.bwd, scale=scale, causal=True, interpret=True, window=window)
    assert pallas_call_names(bwd, q, k, v, o, lse, g) == ["tpuft_swa_bwd_dkdv_dq"]
    got = (o,) + tuple(bwd(q, k, v, o, lse, g))
    o_x, lse_x = fa._fa_reference(q, k, v, scale, True, window)
    got_xla = (o_x,) + tuple(fa._fa_bwd_xla(q, k, v, o_x, lse_x, g, scale, True, window))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_x), rtol=1e-5, atol=1e-5)
    for a, x, b, name in zip(got, got_xla, want, ("o", "dq", "dk", "dv")):
        # float32 operands; the kernels accumulate tile by tile, the mask at once
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=name)
        np.testing.assert_allclose(np.asarray(x), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=name + " (xla)")


def test_windowed_two_pass_backward_matches_a_dense_mask(monkeypatch) -> None:
    """A dq row over the budget takes the two windowed kernels."""
    from torchft_tpu.ops import attention as fa

    seq, scale, window = 2048, 0.088, 700
    monkeypatch.setattr(fa, "_DQ_ROW_VMEM_BUDGET", seq * 128 * 4 - 1)
    rng = np.random.default_rng(3)
    q, k, v, g = (jnp.asarray(rng.standard_normal((1, seq, 128)), dtype=jnp.float32) for _ in range(4))
    want = _dense_window_attention(q, k, v, g, scale, window)
    o, lse = forms.fwd(q, k, v, scale, True, interpret=True, window=window)
    bwd = functools.partial(forms.bwd, scale=scale, causal=True, interpret=True, window=window)
    assert pallas_call_names(bwd, q, k, v, o, lse, g) == ["tpuft_swa_bwd_dkdv", "tpuft_swa_bwd_dq"]
    for a, b, name in zip((o,) + tuple(bwd(q, k, v, o, lse, g)), want, ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("window", [1024, 5000])
def test_a_window_that_covers_the_sequence_is_the_causal_call(window) -> None:
    """`flash_attention(window >= seq)`: the same jaxpr as the causal call
    (so the same kernel, un-windowed) and the same bits, output and
    gradients."""
    from torchft_tpu.ops import flash_attention

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 1024, 4, 64)), dtype=jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 1024, 2, 64)), dtype=jnp.float32) for _ in range(2))

    def loss(window):
        return lambda q, k, v: jnp.sum(jnp.square(flash_attention(q, k, v, causal=True, window=window)))

    assert str(jax.make_jaxpr(jax.grad(loss(window), argnums=(0, 1, 2)))(q, k, v)) == str(
        jax.make_jaxpr(jax.grad(loss(None), argnums=(0, 1, 2)))(q, k, v))
    got = jax.value_and_grad(loss(window), argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(None), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_windowed_flash_attention_differs_from_causal_and_matches_a_dense_mask() -> None:
    """The public call with grouped queries and a window under the sequence,
    through autodiff (the XLA formulation off-TPU)."""
    from torchft_tpu.ops import flash_attention

    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), dtype=jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 64)), dtype=jnp.float32) for _ in range(2))
    g = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), dtype=jnp.float32)
    o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, window=32), q, k, v)
    major = lambda t: t[0].transpose(1, 0, 2)  # noqa: E731 — [1, S, heads, d] -> [heads, S, d]
    rep = lambda t: jnp.repeat(major(t), 2, axis=0)  # noqa: E731
    want = _dense_window_attention(major(q), rep(k), rep(v), major(g), 64 ** -0.5, 32)
    np.testing.assert_allclose(np.asarray(major(o)), np.asarray(want[0]), rtol=1e-4, atol=1e-5)
    dq, dk, dv = vjp(g)
    np.testing.assert_allclose(np.asarray(major(dq)), np.asarray(want[1]), rtol=1e-4, atol=1e-5)
    for got, ref in ((dk, want[2]), (dv, want[3])):  # a kv head's gradient is its two query heads' summed
        np.testing.assert_allclose(np.asarray(major(got)), np.asarray(ref).reshape(2, 2, 256, 64).sum(1), rtol=1e-4, atol=1e-4)
    assert not np.allclose(np.asarray(o), np.asarray(flash_attention(q, k, v)), atol=1e-3)


@pytest.mark.parametrize("seq", [4096, 8192, 16384])
@pytest.mark.parametrize("block", [512, 256])
def test_the_band_walk_at_the_window_cells_lengths(seq, block) -> None:
    """A window of 512: the walk's tables hold every tile with a visible
    pair and no other, row by row and column by column — 2n - 1 tiles of
    512 x 512 (two a row but the first), 3n - 3 of 256 x 256 — and the
    traced `pallas_call`s at the program's blocks have that many steps."""
    from torchft_tpu.ops import attention as fa

    window, n = 512, seq // block
    t = np.arange(seq)
    d = t[:, None] - t[None, :]
    holds_a_pair = ((d >= 0) & (d < window)).reshape(n, block, n, block).any(axis=(1, 3))
    tiles = int(holds_a_pair.sum())
    assert tiles == (2 * n - 1 if block == 512 else 3 * n - 3)
    for kv_major in (False, True):
        walk = fa._Walk(True, seq, seq, block, block, kv_major=kv_major, window=window)
        rows, cols = (np.asarray(x) for x in walk.tables)
        visited = np.zeros((n, n), bool)
        visited[rows, cols] = True
        assert len(rows) == tiles and (visited == holds_a_pair).all()
        major, minor = (cols, rows) if kv_major else (rows, cols)
        assert (np.diff(major) >= 0).all() and (np.diff(minor)[np.diff(major) == 0] == 1).all()
        # the ends the kernels start, assign and emit at are the tables' own
        for i in range(n):
            in_row, in_col = cols[rows == i], rows[cols == i]
            assert (int(walk.first_k(i)), int(walk.last_k(i))) == (in_row.min(), in_row.max())
            assert (int(walk.first_q(i)), int(walk.last_q(i))) == (in_col.min(), in_col.max())
    if fa._block_sizes(seq, seq) == (block, block):
        bh = 8
        qkv = jax.ShapeDtypeStruct((bh, seq, 128), jnp.bfloat16)
        lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32)
        fwd = functools.partial(forms.fwd, scale=0.088, causal=True, window=window)
        bwd = functools.partial(forms.bwd, scale=0.088, causal=True, window=window)
        f, b = bh // fa._heads_per_step(bh), bh // fa._bwd_heads_per_step(bh, fa._row_vmem_bytes(seq, 128, 2))
        assert f == 1 and b < bh  # the band's tiles, H heads a step
        assert pallas_call_grids(fwd, qkv, qkv, qkv) == {"tpuft_swa_fwd": (f, tiles)}
        assert pallas_call_grids(bwd, qkv, qkv, qkv, qkv, lse, qkv) == {"tpuft_swa_bwd_dkdv_dq": (b, tiles)}


def _vmapped_bwd_step(keep, qi, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *, walk, scale, kv_group, q_heads, dkdv, dq):
    """The backward step as it stood before PR 65, kept here as the forward's
    above: the step's heads' slices stacked and `_bwd_tile` over them under
    one `jax.vmap`, every product one batched product with the heads leading."""
    from jax.experimental import pallas as pl
    from torchft_tpu.ops import attention as fa

    heads = lse_ref.shape[0]
    rows = pl.ds(qi * walk.block_q, walk.block_q)
    stacked = lambda head: jnp.stack([head(h) for h in range(heads)])  # noqa: E731
    tiles = jax.vmap(lambda *o: fa._bwd_tile(*o, keep, scale=scale, dkdv=dkdv, dq=dq))(
        stacked(lambda h: fa._head(q_ref, h, heads)), stacked(lambda h: fa._kv_head(k_ref, h, heads, kv_group, q_heads)),
        stacked(lambda h: fa._kv_head(v_ref, h, heads, kv_group, q_heads)), stacked(lambda h: fa._head(do_ref, h, heads)),
        lse_ref[:, :, rows], delta_ref[:, :, rows])
    return [tuple(t[h] for t in tiles) for h in range(heads)]


def _head_major_oracle(monkeypatch, q, k, v, g, *, scale, kv_group=1, window=None, mask=None):
    """out, lse, dq, dk, dv of head-major operands [heads, S, d] as the
    kernels of before PR 65 computed them: every head an entry of ONE head
    ([heads, S, d]: a block is a head's (rows, d) whole and nothing is sliced
    out of a row of heads), one entry a grid step, and the steps the ones kept
    above — the tile functions under `jax.vmap`.  k and v are repeated to a
    head a query head, the values a KV head read in place gives every head of
    its group, and a mask's tiles to a copy an entry."""
    from torchft_tpu.ops import attention as fa

    k, v = jnp.repeat(k, kv_group, axis=0), jnp.repeat(v, kv_group, axis=0)
    more = {"q_heads": 1, "window": window, "interpret": True, "heads_per_step": 1,
            "mask": None if mask is None else jnp.broadcast_to(mask, q.shape[:1] + mask.shape[1:])}
    with monkeypatch.context() as before:
        before.setattr(fa, "_fwd_step", _vmapped_fwd_step)
        before.setattr(fa, "_bwd_step", _vmapped_bwd_step)
        out, lse = fa._fa_pallas_call(q, k, v, scale, True, **more)
        return (out, lse) + tuple(fa._fa_bwd_pallas(q, k, v, out, lse, g, scale, True, **more))


# kind: (heads, positions, query and key width, query heads a KV head, window, heads a step forward and backward)
POSITION_MAJOR_CASES = {
    "plain": (8, 1024, 128, 1, None, None, None),
    "kv_group_7_straddling": (28, 1024, 128, 7, None, 4, 4),
    "window_1024": (8, 2048, 128, 1, 1024, None, None),
    "masked_kv_group_8": (8, 1024, 128, 8, None, None, None),
    "unequal_widths": (4, 1024, 256, 1, None, None, None),
}


def _rowsum_delta(g, o, q_heads):
    """`ops.attention._row_delta` as a sum over a head's columns: the same
    float32 additions whatever the heads of a batch entry, which the product
    with the heads' indicator (its place in the program: the sum would have
    XLA re-tile g * o whole on the TPU) does not promise."""
    b, seq, width = g.shape
    prod = (g.astype(jnp.float32) * o.astype(jnp.float32)).reshape(b, seq, q_heads, width // q_heads)
    return jnp.sum(prod, axis=-1).transpose(0, 2, 1).reshape(b * q_heads, 1, seq)


@pytest.mark.parametrize("kind", sorted(POSITION_MAJOR_CASES))
def test_position_major_kernels_are_bitwise_the_head_major_tiles(kind, monkeypatch) -> None:
    """The kernels read q, k, v and g and write out, dq, dk and dv where the
    projections leave them, [1, S, heads * d] with a head a lane-aligned column
    block (PR 65).  In interpret mode their out, lse, dq, dk and dv are bit for
    bit the head-major oracle's above, turned: over the triangle, with a group
    of seven query heads a KV head read in place by steps of four heads that
    straddle two KV heads (an element-placed block along the columns, a head's
    own by a scalar index times the width), over the band under a window of
    1,024, under a packed mask whose eight query heads read one KV head, and
    with query and key 256 wide beside a value of 128.  The backward's delta
    = rowsum(g * o) is given to both as the same sum (`_rowsum_delta`); the
    program's own, a product with the heads' indicator, is that sum to
    float32's rounding."""
    import attention_forms as forms
    from torchft_tpu.ops import attention as fa
    from torchft_tpu.ops import sparse_attention as sa

    heads, seq, d, kv_group, window, fwd_heads, bwd_heads = POSITION_MAJOR_CASES[kind]
    more = {"kv_group": kv_group, "window": window}
    if kind.startswith("masked"):
        keep = jax.random.bernoulli(jax.random.PRNGKey(65), 0.3, (seq, seq)) | jnp.eye(seq, dtype=bool)
        more["mask"] = sa.packed_lower_triangle((keep & jnp.tril(jnp.ones_like(keep)))[None]).astype(jnp.int8)
    assert fa._straddles(fwd_heads or fa._heads_per_step(heads), kv_group) == (kind == "kv_group_7_straddling")
    ks = jax.random.split(jax.random.PRNGKey(len(kind)), 4)
    q = jax.random.normal(ks[0], (heads, seq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (heads // kv_group, seq, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (heads // kv_group, seq, 128), jnp.bfloat16)
    g = jax.random.normal(ks[3], (heads, seq, 128), jnp.bfloat16)
    rows = forms.rows
    np.testing.assert_allclose(np.asarray(fa._row_delta(rows(g), rows(q[..., :128]), heads)),
                               np.asarray(_rowsum_delta(rows(g), rows(q[..., :128]), heads)), rtol=1e-5, atol=1e-5)
    monkeypatch.setattr(fa, "_row_delta", _rowsum_delta)
    want = _head_major_oracle(monkeypatch, q, k, v, g, scale=0.07, **more)
    o, lse = forms.fwd(q, k, v, 0.07, True, interpret=True, heads_per_step=fwd_heads, **more)
    got = (o, lse) + forms.bwd(q, k, v, o, lse, g, 0.07, True, interpret=True, heads_per_step=bwd_heads, **more)
    assert all(float(jnp.abs(x.astype(jnp.float32)).max()) > 0.01 for x in want)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape and bool(jnp.array_equal(a, b)), name


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "masked"])
def test_entries_of_one_head_share_a_step_and_a_packed_mask_keeps_its_entry(masked) -> None:
    """Entries of ONE head ([B, S, 1 * d]: `flash_attention` folds heads that
    are no lane multiple wide into the batch) have no second column block, so
    a grid step takes adjacent entries — all four here forward, the backward's
    rule's — but under a packed mask, whose tile is one entry's: then a step
    is one entry and every entry is held to its OWN mask (four different
    ones), forward and backward, against dense masked attention and its
    autodiff."""
    from torchft_tpu.ops import attention as fa
    from torchft_tpu.ops import sparse_attention as sa

    batch, seq, d = 4, 1024, 128
    ks = jax.random.split(jax.random.PRNGKey(65 + masked), 5)
    q, k, v, g = (jax.random.normal(key, (batch, seq, d), jnp.float32) for key in ks[:4])
    keep = jnp.broadcast_to(jnp.tril(jnp.ones((seq, seq), bool)), (batch, seq, seq))
    more = {"q_heads": 1, "interpret": True}
    if masked:
        keep = (jax.random.bernoulli(ks[4], 0.3, (batch, seq, seq)) | jnp.eye(seq, dtype=bool)) & keep
        more["mask"] = sa.packed_lower_triangle(keep).astype(jnp.int8)
        assert not bool(jnp.array_equal(keep[0], keep[1]))
    (want_o, want_lse), vjp = jax.vjp(lambda *qkv: _masked_reference(*qkv, keep, d ** -0.5), q, k, v)
    fwd = functools.partial(fa._fa_pallas_call, scale=d ** -0.5, causal=True, **more)
    bwd = functools.partial(fa._fa_bwd_pallas, scale=d ** -0.5, causal=True, **more)
    o, lse = fwd(q, k, v)
    steps = batch if masked else 1
    assert pallas_call_grids(fwd, q, k, v).popitem()[1][0] == steps
    assert pallas_call_grids(bwd, q, k, v, o, lse, g).popitem()[1][0] == (batch if masked else batch // fa._bwd_heads_per_step(
        batch, fa._row_vmem_bytes(seq, d, 4)))
    got = (o, lse) + tuple(bwd(q, k, v, o, lse, g))
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, (want_o, want_lse) + vjp((g, jnp.zeros_like(want_lse)))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=name)
    # entries that share a step keep the kernels' form of before PR 65 — lse lane-padded out of the forward kernel, the
    # backward's heads one batched product: bit for bit one entry a step (lse a row, `_bwd_tile` a head)
    one = fwd(q, k, v, heads_per_step=1) + tuple(bwd(q, k, v, o, lse, g, heads_per_step=1))
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(got, one))


def test_flash_attention_folds_padded_heads_into_the_batch_with_their_kv_heads_repeated(monkeypatch) -> None:
    """Heads 192 wide reach the kernels padded to 256 and folded into the
    batch, [B * heads, S, 256] — with grouped queries too (no cell: latent
    attention has a KV head a head), k and v repeated to a head a query head
    first, and dk, dv come back in k's and v's shapes."""
    from torchft_tpu.ops import attention as fa

    monkeypatch.setattr(fa._pallas_util, "kernels_apply", lambda mesh=None: True)
    b, seq = 2, 1024
    for hq, hkv in ((4, 4), (4, 2)):
        q = jax.ShapeDtypeStruct((b, seq, hq, 192), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((b, seq, hkv, 192), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((b, seq, hkv, 128), jnp.bfloat16)
        grads = jax.grad(lambda q_, k_, v_: jnp.sum(fa.flash_attention(q_, k_, v_).astype(jnp.float32)), argnums=(0, 1, 2))
        assert [a.shape for a in jax.eval_shape(grads, q, k, v)] == [q.shape, k.shape, v.shape]
        found = pallas_call_operands(grads, q, k, v)
        assert sorted(found) == ["tpuft_fa_bwd_dkdv_dq", "tpuft_fa_fwd"]
        for operands in found.values():
            assert operands[:3] == [(b * hq, seq, 256), (b * hq, seq, 256), (b * hq, seq, 128)]
