"""Ops correctness: flash attention (reference + pallas-interpret), RMSNorm,
ring attention vs full attention on the virtual CPU mesh."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _naive_attention(q, k, v, causal):
    # Straightforward softmax attention in f64 for a trustworthy oracle.
    qf, kf, vf = (np.asarray(t, dtype=np.float64) for t in (q, k, v))
    b, h, s, d = qf.shape
    out = np.zeros_like(qf)
    for bi in range(b):
        for hi in range(h):
            s_mat = qf[bi, hi] @ kf[bi, hi].T / np.sqrt(d)
            if causal:
                mask = np.tril(np.ones((s, s), dtype=bool))
                s_mat = np.where(mask, s_mat, -np.inf)
            p = np.exp(s_mat - s_mat.max(axis=-1, keepdims=True))
            p /= p.sum(axis=-1, keepdims=True)
            out[bi, hi] = p @ vf[bi, hi]
    return out


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_reference_path(causal) -> None:
    from torchft_tpu.ops import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 3, 64, 32)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 3, 64, 32)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 3, 64, 32)), dtype=jnp.float32)
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), _naive_attention(q, k, v, causal), rtol=1e-4, atol=1e-4
    )


def test_flash_attention_gqa_broadcast() -> None:
    from torchft_tpu.ops import flash_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 4, 32, 16)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), dtype=jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    kr = jnp.repeat(k, 2, axis=1)
    vr = jnp.repeat(v, 2, axis=1)
    np.testing.assert_allclose(
        np.asarray(out), _naive_attention(q, kr, vr, True), rtol=1e-4, atol=1e-4
    )


def test_flash_attention_grads_match_reference() -> None:
    from torchft_tpu.ops import flash_attention

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), dtype=jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_naive(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
        mask = jnp.tril(jnp.ones(s.shape[-2:], dtype=bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for gf, gn in zip(g_flash, g_naive):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gn), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_pallas_interpret_matches(causal) -> None:
    """Runs the actual TPU kernel in pallas interpret mode on CPU."""
    from torchft_tpu.ops.attention import _fa_pallas_call, _fa_reference

    rng = np.random.default_rng(3)
    # seq 1024 -> two 512-blocks in both q and kv; d=128 lane-aligned.
    q = jnp.asarray(rng.standard_normal((2, 1024, 128)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 1024, 128)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 1024, 128)), dtype=jnp.float32)
    o_pl, lse_pl = _fa_pallas_call(q, k, v, 0.088, causal, interpret=True)
    o_ref, lse_ref = _fa_reference(q, k, v, 0.088, causal)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(lse_pl), np.asarray(lse_ref), rtol=2e-3, atol=2e-3)


def pallas_call_names(fn, *args) -> list:
    """The `name=` of every `pallas_call` in `fn`'s jaxpr, in order."""
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return [e.params["name"] for e in eqns if e.primitive.name == "pallas_call"]


ONE_PASS = ["tpuft_fa_bwd_dkdv_dq"]
TWO_PASS = ["tpuft_fa_bwd_dkdv", "tpuft_fa_bwd_dq"]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", [1024, 4096])
@pytest.mark.parametrize("over_budget", [False, True], ids=["one_pass", "two_pass_fallback"])
def test_flash_attention_bwd_pallas_interpret_matches(causal, seq, over_budget, monkeypatch) -> None:
    """The backward pallas kernels vs the XLA flash backward, in interpret
    mode on CPU — same pattern as the forward kernel test.  Both lengths
    (2 and 8 kv blocks) take the one-pass kernel, whose dq accumulates in a
    VMEM-resident row; with the row's budget cut under them (the only way
    in: the choice reads shapes alone) they take the two-pass form that a
    longer row than any here would."""
    from torchft_tpu.ops import attention as fa

    bh = 2 if seq == 1024 else 1
    if over_budget:
        monkeypatch.setattr(fa, "_DQ_ROW_VMEM_BUDGET", seq * 128 * 4 - 1)
    assert fa._dq_row_resident(seq, 128) != over_budget

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((bh, seq, 128)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, seq, 128)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, seq, 128)), dtype=jnp.float32)
    g = jnp.asarray(rng.standard_normal((bh, seq, 128)), dtype=jnp.float32)
    scale = 0.088
    o, lse = fa._fa_reference(q, k, v, scale, causal)
    # _fa_bwd_xla explicitly, NOT _flash_bwd: on a TPU backend the latter
    # dispatches to the pallas kernels, making the comparison vacuous.
    d_ref = fa._fa_bwd_xla(q, k, v, o, lse, g, scale, causal)
    bwd = functools.partial(fa._fa_bwd_pallas, scale=scale, causal=causal, interpret=True)
    assert pallas_call_names(bwd, q, k, v, o, lse, g) == (TWO_PASS if over_budget else ONE_PASS)
    d_pl = bwd(q, k, v, o, lse, g)
    for a, b, name in zip(d_pl, d_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=name
        )


def test_flash_attention_bwd_is_one_pallas_call_at_the_cells_shape() -> None:
    """`[2, 4096, 128]` bf16, the dense cells' sequence: the backward's jaxpr
    holds exactly one `pallas_call`, whose outputs are dk, dv and a dq of
    the operand's dtype — no f32 dq, no partials, nothing for XLA to sum."""
    from torchft_tpu.ops import attention as fa

    qkv = jax.ShapeDtypeStruct((2, 4096, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((2, 4096), jnp.float32)
    bwd = functools.partial(fa._fa_bwd_pallas, scale=0.088, causal=True)
    jaxpr = jax.make_jaxpr(bwd)(qkv, qkv, qkv, qkv, lse, qkv)
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [call.params["name"]] == ONE_PASS
    assert [(v.aval.shape, v.aval.dtype) for v in call.outvars] == [((2, 4096, 128), jnp.bfloat16)] * 3
    assert {id(v) for v in jaxpr.jaxpr.outvars} == {id(v) for v in call.outvars}
    # the row's size is the only thing the choice reads: 65,536 at 128 wide
    # is the longest resident row, one block more is not
    assert fa._dq_row_resident(65536, 128) and not fa._dq_row_resident(65536 + 512, 128)
    assert fa._dq_row_resident(32768, 256) and not fa._dq_row_resident(65536, 256)


def pallas_call_grids(fn, *args) -> dict:
    """{name: grid} of every `pallas_call` in `fn`'s jaxpr."""
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return {e.params["name"]: tuple(e.params["grid_mapping"].grid) for e in eqns if e.primitive.name == "pallas_call"}


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
    fwd = functools.partial(fa._fa_pallas_call, scale=scale, causal=True, interpret=True, **more)
    bwd = functools.partial(fa._fa_bwd_pallas, scale=scale, causal=True, interpret=True, **more)
    got_o, got_lse = fwd(q, k, v)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got_lse), np.asarray(want_lse), rtol=2e-3, atol=2e-3)
    got = bwd(q, k, v, got_o, got_lse, g)
    assert [a.shape for a in got] == [q.shape, (heads, seq, d_qk), (heads, seq, d_v)]
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=name)
    # H heads a grid step, H read from the shapes: all of these heads (one KV head's, or a batch
    # entry's) forward; backward as many of them as their dq rows leave room for
    tiles, share = n * (n + 1) // 2, fa._heads_share(heads, more.get("mask"), kv_group)
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
    o, lse = fa._fa_pallas_call(q, k, v, 0.088, True, interpret=True, mask=mask)
    dq, _, _ = fa._fa_bwd_pallas(q, k, v, o, lse, g, 0.088, True, interpret=True, mask=mask)
    want, _, _ = fa._fa_bwd_xla(q, k, v, o, lse, g, 0.088, True)
    dq, want = np.asarray(dq, np.float32), np.asarray(want, np.float32)
    for qi in range(3):
        rows = slice(512 * qi, 512 * (qi + 1))
        assert np.abs(dq[:, rows]).max() > 0.01, f"q tile {qi}: nothing was written"
        assert np.linalg.norm(dq[:, rows] - want[:, rows]) < 0.01 * np.linalg.norm(want[:, rows]), f"q tile {qi}"


@pytest.mark.parametrize("heads_per_step", [2, 4])
@pytest.mark.parametrize("kind", ["causal", "rectangle", "window", "masked_kv_group_8", "unequal_widths"])
def test_heads_a_grid_step_are_bitwise_one_head_a_step(kind, heads_per_step) -> None:
    """out, lse, dq, dk, dv with H heads a grid step — every block and scratch
    leading with the heads, the tile's arithmetic under `jax.vmap` — are bit
    for bit those of one head a step: over the triangle, a rectangle (queries
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
        fwd = functools.partial(fa._fa_pallas_call, scale=0.07, causal=causal, interpret=True, heads_per_step=heads, **more)
        bwd = functools.partial(fa._fa_bwd_pallas, scale=0.07, causal=causal, interpret=True, heads_per_step=heads, **more)
        o, lse = fwd(q, k, v)
        grids = {**pallas_call_grids(fwd, q, k, v), **pallas_call_grids(bwd, q, k, v, o, lse, g)}
        assert len(grids) == 2 and {grid[0] for grid in grids.values()} == {bh // heads}, grids
        return (o, lse) + tuple(bwd(q, k, v, o, lse, g))

    want = kernels(1)
    assert all(float(jnp.abs(x.astype(jnp.float32)).max()) > 0.01 for x in want)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), kernels(heads_per_step), want):
        assert a.dtype == b.dtype and a.shape == b.shape and bool(jnp.array_equal(a, b)), name


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
    assert pallas_call_grids(functools.partial(fa._fa_pallas_call, scale=0.088, causal=True), qkv, qkv, qkv) == {
        "tpuft_fa_fwd": (1, n * (n + 1) // 2)}
    assert pallas_call_grids(functools.partial(fa._fa_bwd_pallas, scale=0.088, causal=True), qkv, qkv, qkv, qkv, lse, qkv) == {
        "tpuft_fa_bwd_dkdv_dq": (2, n * (n + 1) // 2)}
    longer = jax.ShapeDtypeStruct((bh, seq + 512, 128), jnp.bfloat16)
    assert {grid[0] for grid in pallas_call_grids(
        functools.partial(fa._fa_bwd_pallas, scale=0.088, causal=True), longer, longer, longer, longer,
        jax.ShapeDtypeStruct((bh, seq + 512), jnp.float32), longer).values()} == {1}


# (batch * heads, positions, query and key width, value width, window, query heads a KV head under a mask): the forward's
# and the backward's heads a grid step
CELL_SHAPES = {
    "dense_16_heads": ((32, 4096, 128, 128, None, None), (8, 4)),
    "dense_32_heads": ((64, 4096, 128, 128, None, None), (8, 4)),
    "moonlight": ((32, 8192, 256, 128, None, None), (8, 4)),
    "keye_masked": ((32, 32768, 128, 128, None, 8), (8, 2)),
    "laguna_full": ((48, 16384, 128, 128, None, None), (8, 4)),
    "laguna_window": ((64, 16384, 128, 128, 512, None), (8, 4)),
    "zaya": ((8, 16384, 128, 128, None, None), (8, 4)),
    "kimi": ((32, 16384, 256, 128, None, None), (8, 2)),
    "smallthinker_full": ((28, 16384, 128, 128, None, None), (7, 4)),
    "smallthinker_window": ((28, 16384, 128, 128, 4096, None), (7, 4)),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_heads_a_grid_step_at_the_cells_shapes(cell) -> None:
    """The traced `pallas_call`s at every cell's attention shape (no kernel
    runs): grid (batch * heads / H, tiles) with more than one head a step in
    both directions."""
    from torchft_tpu.ops import attention as fa

    (bh, seq, d, dv, window, kv_group), (fwd_heads, bwd_heads) = CELL_SHAPES[cell]
    n = seq // 512
    tiles = len(fa._Walk(True, seq, seq, 512, 512, window=window).tables[0])
    assert tiles == (n * (n + 1) // 2 if window is None else {512: 2 * n - 1, 4096: 252}[window])
    q = jax.ShapeDtypeStruct((bh, seq, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((bh // (kv_group or 1), seq, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((bh // (kv_group or 1), seq, dv), jnp.bfloat16)
    o = jax.ShapeDtypeStruct((bh, seq, dv), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32)
    mask = jax.ShapeDtypeStruct((1, tiles, 512, 512), jnp.int8) if kv_group else None
    more = {"kv_group": kv_group} if kv_group else {"window": window}
    family = "tpuft_dsa_attn" if kv_group else "tpuft_fa" if window is None else "tpuft_swa"
    assert min(fwd_heads, bwd_heads) > 1
    assert pallas_call_grids(lambda q_, k_, v_, m_: fa._fa_pallas_call(q_, k_, v_, 0.088, True, mask=m_, **more),
                             q, k, v, mask) == {family + "_fwd": (bh // fwd_heads, tiles)}
    assert pallas_call_grids(lambda q_, k_, v_, o_, l_, g_, m_: fa._fa_bwd_pallas(q_, k_, v_, o_, l_, g_, 0.088, True, mask=m_, **more),
                             q, k, v, o, lse, o, mask) == {family + "_bwd_dkdv_dq": (bh // bwd_heads, tiles)}


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
        return functools.partial(fa._fa_pallas_call, scale=0.088, causal=causal, **more)

    def bwd(causal, **more):
        return functools.partial(fa._fa_bwd_pallas, scale=0.088, causal=causal, **more)

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
    o, lse = fa._fa_pallas_call(q, k, v, scale, True, interpret=True, window=window)
    bwd = functools.partial(fa._fa_bwd_pallas, scale=scale, causal=True, interpret=True, window=window)
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
    o, lse = fa._fa_pallas_call(q, k, v, scale, True, interpret=True, window=window)
    bwd = functools.partial(fa._fa_bwd_pallas, scale=scale, causal=True, interpret=True, window=window)
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
    q = jnp.asarray(rng.standard_normal((1, 4, 1024, 64)), dtype=jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, 1024, 64)), dtype=jnp.float32) for _ in range(2))

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
    q = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), dtype=jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, 256, 64)), dtype=jnp.float32) for _ in range(2))
    g = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), dtype=jnp.float32)
    o, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, window=32), q, k, v)
    rep = lambda t: jnp.repeat(t, 2, axis=1).reshape(4, 256, 64)  # noqa: E731
    want = _dense_window_attention(q.reshape(4, 256, 64), rep(k), rep(v), g.reshape(4, 256, 64), 64 ** -0.5, 32)
    np.testing.assert_allclose(np.asarray(o).reshape(4, 256, 64), np.asarray(want[0]), rtol=1e-4, atol=1e-5)
    dq, dk, dv = vjp(g)
    np.testing.assert_allclose(np.asarray(dq).reshape(4, 256, 64), np.asarray(want[1]), rtol=1e-4, atol=1e-5)
    for got, ref in ((dk, want[2]), (dv, want[3])):  # a kv head's gradient is its two query heads' summed
        np.testing.assert_allclose(np.asarray(got)[0], np.asarray(ref).reshape(2, 2, 256, 64).sum(1), rtol=1e-4, atol=1e-4)
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
        fwd = functools.partial(fa._fa_pallas_call, scale=0.088, causal=True, window=window)
        bwd = functools.partial(fa._fa_bwd_pallas, scale=0.088, causal=True, window=window)
        f, b = bh // fa._heads_per_step(bh), bh // fa._bwd_heads_per_step(bh, fa._row_vmem_bytes(seq, 128, 2))
        assert f == 1 and b < bh  # the band's tiles, H heads a step
        assert pallas_call_grids(fwd, qkv, qkv, qkv) == {"tpuft_swa_fwd": (f, tiles)}
        assert pallas_call_grids(bwd, qkv, qkv, qkv, qkv, lse, qkv) == {"tpuft_swa_bwd_dkdv_dq": (b, tiles)}


def test_fused_cross_entropy_matches_and_grads() -> None:
    """The fused lm-head CE op (XLA fallback path) vs the straightforward
    materialized formulation: values and grads."""
    from torchft_tpu.ops import fused_linear_cross_entropy

    rng = np.random.default_rng(11)
    n, e, v = 64, 32, 256
    x = jnp.asarray(rng.standard_normal((n, e)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, v)) * 0.1, dtype=jnp.float32)
    t = jnp.asarray(rng.integers(0, v, n), dtype=jnp.int32)

    def ref(x, w):
        logits = x @ w
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - tl)

    np.testing.assert_allclose(
        float(fused_linear_cross_entropy(x, w, t)), float(ref(x, w)),
        rtol=1e-5,
    )
    g_f = jax.grad(fused_linear_cross_entropy, argnums=(0, 1))(x, w, t)
    g_r = jax.grad(ref, argnums=(0, 1))(x, w)
    for a, b, name in zip(g_f, g_r, ("dx", "dw")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5, err_msg=name
        )


def test_fused_cross_entropy_pallas_interpret_matches() -> None:
    """The pallas CE kernels (fwd online-logsumexp + bwd dlogits) in
    interpret mode vs a numpy oracle, at a shape that tiles (several row
    and vocab blocks)."""
    from torchft_tpu.ops.cross_entropy import (
        _ce_dlogits_pallas,
        _ce_lse_pallas,
        _target_logit,
    )

    rng = np.random.default_rng(12)
    n, e, v = 256, 128, 512
    x = jnp.asarray(rng.standard_normal((n, e)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, v)) * 0.1, dtype=jnp.float32)
    t = jnp.asarray(rng.integers(0, v, n), dtype=jnp.int32)

    logits = np.asarray(x) @ np.asarray(w)
    lse_ref = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + logits.max(-1)
    tl_ref = logits[np.arange(n), np.asarray(t)]

    lse = _ce_lse_pallas(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(lse), lse_ref, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(_target_logit(x, w, t)), tl_ref, rtol=1e-5, atol=1e-5
    )

    scale = 0.37
    p = np.exp(logits - lse_ref[:, None])
    p[np.arange(n), np.asarray(t)] -= 1.0
    dl = _ce_dlogits_pallas(
        x, w, t, jnp.asarray(lse_ref, jnp.float32), scale, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(dl), p * scale, rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("j,width,real", [(0, 512, 512), (1, 512, 512), (2, 256, 76)],
                         ids=["first_slab", "a_slab_with_targets_on_both_sides", "last_slab_partly_padding"])
def test_a_slab_of_dlogits_is_its_columns_of_the_whole(j, width, real) -> None:
    """The `tpuft_ce_dlogits` kernel (interpret mode) over one slab of a
    head's columns, as `_ce_rows_bwd` calls it: the WHOLE weight, read from
    the slab's first tile on through the index map; the targets and
    ``valid_v`` in the head's own column numbers, which the kernel's are
    once it adds that tile; the whole head's log-sum-exp (1,100 columns
    padded to 1,280, slabs of 512: the last one 256 wide, in tiles of 256).
    Against the slab's columns of the materialized (softmax - onehot) *
    scale; the padding's are exact zeros."""
    from torchft_tpu.ops.cross_entropy import _ce_dlogits_pallas

    rng = np.random.default_rng(15)
    n, e, v, vp, slab = 256, 128, 1100, 1280, 512
    col = j * slab
    x = jnp.asarray(rng.standard_normal((n, e)), dtype=jnp.float32)
    w = np.zeros((e, vp), np.float32)
    w[:, :v] = rng.standard_normal((e, v)) * 0.1
    t = rng.integers(0, v, n)
    t[:8] = [0, 511, 512, 1023, 1024, 1099, 1050, 600]   # both edges of every slab
    logits = (np.asarray(x) @ w)[:, :v]
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + logits.max(-1)
    want = np.zeros((n, vp), np.float32)
    want[:, :v] = np.exp(logits - lse[:, None])
    want[np.arange(n), t] -= 1.0
    got = jax.jit(lambda j: _ce_dlogits_pallas(  # j traced, as the loop's is
        x, jnp.asarray(w), jnp.asarray(t, jnp.int32), jnp.asarray(lse, jnp.float32), 0.37,
        interpret=True, valid_v=None if real == width else v, cols=(j, slab, width)))(j)
    assert got.shape == (n, width)
    np.testing.assert_allclose(np.asarray(got), want[:, col:col + width] * 0.37, rtol=1e-4, atol=1e-5)
    assert not np.any(np.asarray(got)[:, real:]) and ((t >= col) & (t < col + real)).sum() >= 2


@pytest.mark.parametrize("vocab_major", [False, True], ids=["head_leaf", "tied_embedding"])
@pytest.mark.parametrize("block,v,slab,tail_targets", [
    (96, 1000, 1024, False),
    (48, 1000, 512, False),
    (40, 2100, 1024, False),
    (40, 2100, 1024, True),
], ids=["one_block_one_slab", "two_blocks_two_slabs", "a_block_that_does_not_divide_a_narrower_last_slab",
        "targets_in_the_partly_padded_last_slab"])
def test_a_head_over_blocks_of_rows_is_the_one_block_head(block, v, slab, tail_targets, vocab_major, monkeypatch) -> None:
    """`fused_linear_cross_entropy_rows` (XLA fallback path) against the
    padded one-block op at widths no tile divides (1,000 -> 1,024 columns, one
    slab or two; 2,100 -> 2,560, two slabs of 1,024 and a last one of 512 with
    52 real columns; the slab is what `head_slab` makes of a budget of 96 rows
    of it): the loss to the rounding of a sum of 96 float32 terms taken in
    another order, dx to 1e-5 of its largest entry (the slabs' parts of dx
    are summed in float32 in another order), dw to 1e-6 (each element is one
    product over the same 96 rows, written once); the weight as the tree
    holds it, [E, V] or the embedding's [V, E], and its gradient in that
    layout.  With every target in the last slab's real columns a slab that
    compared its own columns with the head's would subtract no one-hot at all."""
    from torchft_tpu.ops import cross_entropy
    from torchft_tpu.ops.cross_entropy import (
        fused_linear_cross_entropy_padded, fused_linear_cross_entropy_rows, head_slab, padded_vocab)

    rng = np.random.default_rng(13)
    n, e = 96, 32
    monkeypatch.setattr(cross_entropy, "_DLOGITS_BYTES", 0)
    monkeypatch.setattr(cross_entropy, "_DLOGITS_BLOCK_BYTES", n * slab * 2)
    assert head_slab(n, padded_vocab(v)) == slab
    last = (padded_vocab(v) - 1) // slab * slab
    assert padded_vocab(v) - last in (slab, 512) and (not tail_targets or last < v < padded_vocab(v))
    x = jnp.asarray(rng.standard_normal((n, e)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, v)) * 0.1, dtype=jnp.float32)
    t = jnp.asarray(rng.integers(last if tail_targets else 0, v, n), dtype=jnp.int32)
    held = w.T if vocab_major else w

    def rows(x, held):
        return fused_linear_cross_entropy_rows(x, held, t, block, vocab_major)

    want, (dx_want, dw_want) = jax.value_and_grad(fused_linear_cross_entropy_padded, argnums=(0, 1))(x, w, t)
    got, (dx, dw) = jax.jit(jax.value_and_grad(rows, argnums=(0, 1)))(x, held)
    assert dw.shape == held.shape and dx.shape == x.shape
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_want), rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(dx_want))))
    dw = dw.T if vocab_major else dw
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_want), rtol=1e-5, atol=1e-6 * float(jnp.max(jnp.abs(dw_want))))


@pytest.mark.parametrize("n,v,block,slab", [
    # the tied 131,136-row head, padded: 4.3 GB whole; 16 blocks of 0.27 GB, 8 slabs of 0.54 and one of 512 columns
    (16_384, 131_584, 1024, 16_384),
    (8_192, 92_544, None, 92_544),    # the widest head the benchmark had: 1.5 GB, whole
    (16_384, 32_000, None, 32_000),
    (32_768, 131_584, 1024, 8_192),
    (16_384, 262_656, 512, 16_384),   # the unsliced vocabulary: half the rows a block, the same slab
])
def test_which_heads_run_over_blocks_of_rows(n, v, block, slab) -> None:
    import math

    from torchft_tpu.ops.cross_entropy import _DLOGITS_BLOCK_BYTES, _block_rows, _block_v, head_row_block, head_slab

    assert (head_row_block(n, v), head_slab(n, v)) == (block, slab)
    if block:  # a block is a whole number of the kernels' row tiles at the widths the heads have
        assert block * v * 2 <= _DLOGITS_BLOCK_BYTES < 2 * block * v * 2
        assert _block_rows(block, 2048) is not None
        # a slab's dlogits stay within the same budget, one twice as wide would not; the kernels tile it, and the
        # narrower one after the last in tiles that divide its first column
        assert n * slab * 2 <= _DLOGITS_BLOCK_BYTES < 2 * n * slab * 2 and slab % 512 == 0
        assert _block_v(slab, 2048) == 512 and _block_v(math.gcd(v - (v - 1) // slab * slab, slab), 2048) == 512


def test_rms_norm_matches_and_grads() -> None:
    from torchft_tpu.ops import rms_norm

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((4, 8, 64)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((64,)), dtype=jnp.float32)

    def ref(x, w):
        inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
        return x * inv * w

    np.testing.assert_allclose(
        np.asarray(rms_norm(x, w)), np.asarray(ref(x, w)), rtol=1e-5, atol=1e-5
    )
    g1 = jax.grad(lambda x, w: jnp.sum(rms_norm(x, w) ** 2), argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: jnp.sum(ref(x, w) ** 2), argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)

    # The pallas-kernel variant (custom VJP; XLA fallback off-TPU) must
    # agree with both, values and grads.
    from torchft_tpu.ops import rms_norm_pallas

    np.testing.assert_allclose(
        np.asarray(rms_norm_pallas(x, w)), np.asarray(ref(x, w)),
        rtol=1e-5, atol=1e-5,
    )
    g3 = jax.grad(lambda x, w: jnp.sum(rms_norm_pallas(x, w) ** 2), argnums=(0, 1))(x, w)
    for a, b in zip(g3, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_rms_norm_pallas_kernel_interpret_matches() -> None:
    """The pallas KERNEL body (not just the off-TPU fallback) vs reference,
    via interpret mode — same pattern as the flash-attention kernel test."""
    from torchft_tpu.ops.rmsnorm import _rms_pallas, rms_norm

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((96, 64)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((64,)), dtype=jnp.float32)
    out = _rms_pallas(x, w, eps=1e-6, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(rms_norm(x, w)), rtol=1e-5, atol=1e-5
    )


def test_ring_attention_grads_match_full() -> None:
    """Autodiff through the ring (cond-skipped blocks, lse merge) must
    match grads of dense attention on the same data."""
    from jax.sharding import Mesh

    from torchft_tpu.ops.ring_attention import ring_attention_sharded

    devices = np.array(jax.devices()[:4]).reshape(1, 4)
    mesh = Mesh(devices, ("data", "sequence"))

    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), dtype=jnp.float32)

    def ring_loss(q, k, v):
        out = ring_attention_sharded(
            mesh, q, k, v, causal=True, batch_axis="data", head_axis=None
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def dense_loss(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
        mask = jnp.tril(jnp.ones(s.shape[-2:], dtype=bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_dense, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3, err_msg=name
        )


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal) -> None:
    """Ring over a 4-way sequence axis == full attention on the same data."""
    from jax.sharding import Mesh

    from torchft_tpu.ops.ring_attention import ring_attention_sharded

    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devices, ("data", "sequence"))

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, 2, 64, 16)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 64, 16)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 64, 16)), dtype=jnp.float32)

    out = ring_attention_sharded(
        mesh, q, k, v, causal=causal, batch_axis="data", head_axis=None,
    )
    np.testing.assert_allclose(
        np.asarray(out), _naive_attention(q, k, v, causal), rtol=1e-4, atol=1e-4
    )


def test_zigzag_permutation_roundtrip() -> None:
    from torchft_tpu.ops.ring_attention import (
        from_zigzag,
        inverse_zigzag_permutation,
        to_zigzag,
        zigzag_permutation,
    )

    perm = zigzag_permutation(16, 4)
    # Device i's shard (4 rows) is original chunks (i, 2N-1-i), chunk = 2.
    assert perm.tolist() == [0, 1, 14, 15, 2, 3, 12, 13, 4, 5, 10, 11, 6, 7, 8, 9]
    inv = inverse_zigzag_permutation(16, 4)
    assert perm[inv].tolist() == list(range(16))

    x = jnp.arange(2 * 16 * 3).reshape(2, 16, 3)
    np.testing.assert_array_equal(
        np.asarray(from_zigzag(to_zigzag(x, 4, axis=1), 4, axis=1)), np.asarray(x)
    )

    with pytest.raises(ValueError):
        zigzag_permutation(12, 4)  # not divisible by 2N


def test_zigzag_ring_attention_matches_full() -> None:
    """Zigzag-layout ring == dense causal attention: permute in, ring over a
    4-way sequence axis, un-permute out."""
    from jax.sharding import Mesh

    from torchft_tpu.ops.ring_attention import (
        from_zigzag,
        ring_attention_sharded,
        to_zigzag,
    )

    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devices, ("data", "sequence"))

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, 2, 64, 16)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 64, 16)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 64, 16)), dtype=jnp.float32)

    n = 4
    out_z = ring_attention_sharded(
        mesh,
        to_zigzag(q, n, axis=2),
        to_zigzag(k, n, axis=2),
        to_zigzag(v, n, axis=2),
        causal=True,
        batch_axis="data",
        head_axis=None,
        layout="zigzag",
    )
    out = from_zigzag(out_z, n, axis=2)
    np.testing.assert_allclose(
        np.asarray(out), _naive_attention(q, k, v, causal=True), rtol=1e-4, atol=1e-4
    )


def test_zigzag_ring_attention_grads_match_full() -> None:
    """Autodiff through the zigzag schedule (device-varying cond branches,
    padded merges) must match dense-attention grads."""
    from jax.sharding import Mesh

    from torchft_tpu.ops.ring_attention import (
        from_zigzag,
        ring_attention_sharded,
        to_zigzag,
    )

    devices = np.array(jax.devices()[:4]).reshape(1, 4)
    mesh = Mesh(devices, ("data", "sequence"))
    n = 4

    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), dtype=jnp.float32)

    def ring_loss(q, k, v):
        out_z = ring_attention_sharded(
            mesh,
            to_zigzag(q, n, axis=2),
            to_zigzag(k, n, axis=2),
            to_zigzag(v, n, axis=2),
            causal=True,
            batch_axis="data",
            head_axis=None,
            layout="zigzag",
        )
        return jnp.sum(from_zigzag(out_z, n, axis=2).astype(jnp.float32) ** 2)

    def dense_loss(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d))
        mask = jnp.tril(jnp.ones(s.shape[-2:], dtype=bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_dense, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3, err_msg=name
        )
