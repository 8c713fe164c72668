"""The five `tpuft_dsa_*` kernels (``ops/sparse_attention.py``) in interpret
mode against the XLA formulation, their grids at the cells' lengths, and the
kernels' path as one `custom_vjp`.  (`tests/test_dsa_kernels.py` holds the
selection kernels against a NumPy order statistic; the architecture that runs
them is `tests/test_dsa_moe.py`'s.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import sparse_attention as sa


def _kernel_operands(seed=0, batch=1, heads=4, kv=2, seq=1024, d=128, j=3, di=64, ties=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (batch, seq, heads, d), bf)  # position-major, as the kernels read them
    k = jax.random.normal(ks[1], (batch, seq, kv, d), bf)
    v = jax.random.normal(ks[2], (batch, seq, kv, d), bf)
    a = jax.random.normal(ks[3], (batch, j, seq, di), bf)
    b = jax.random.normal(ks[4], (batch, seq, di), bf)
    if ties:
        b = b.at[:, 100:140].set(b[:, 100:101])
    w = jax.random.normal(ks[5], (batch, seq, j), jnp.float32) * (j * di) ** -0.5
    g = jax.random.normal(ks[6], (batch, seq, heads, d), bf)
    return q, k, v, a, b.transpose(0, 2, 1), w, g


def _unpacked(mask, seq):
    """The packed lower triangle of (512, 512) tiles as a dense [B, S, S]."""
    tile, full, t = min(512, seq), np.zeros((mask.shape[0], seq, seq), np.int8), 0
    for i in range(seq // tile):
        for jj in range(i + 1):
            full[:, i * tile:(i + 1) * tile, jj * tile:(jj + 1) * tile] = np.asarray(mask[:, t])
            t += 1
    return full


@pytest.fixture(scope="module")
def kernel_run():
    """Every kernel once, in interpret mode, at 1,024 positions and topk 200
    with forty tied keys, and the XLA formulation beside it."""
    q, k, v, a, bt, w, g = _kernel_operands()
    topk, scale = 200, 128 ** -0.5
    tau, cut, z = sa._select_pallas(a, bt, w, topk, interpret=True)
    mask = sa._mask_pallas(a, bt, w, tau, cut, interpret=True)
    out, lse = sa._masked_flash_fwd(q, k, v, mask, scale, interpret=True)
    kl, da, dbt, dw = sa._index_loss_pallas(q, k, lse, a, bt, w, z, mask, scale, interpret=True)
    dq, dk, dv = sa._masked_flash_bwd(q, k, v, out, lse, g, mask, scale, interpret=True)
    xla_out, xla_loss, xla_selected = sa._dsa_xla(q, k, v, a, bt, w, topk, scale)
    return dict(locals())


def test_select_and_mask_kernels_give_lax_top_k_s_selection(kernel_run) -> None:
    r = kernel_run
    scores = sa.index_scores(r["a"], r["bt"], r["w"])
    want = np.asarray(sa.selection_mask(scores, r["topk"]))
    got = _unpacked(r["mask"], 1024) != 0
    assert np.array_equal(got, want)
    assert (got.sum(-1)[0] == np.minimum(np.arange(1024) + 1, r["topk"])).all()
    assert int(jnp.sum(r["mask"], dtype=jnp.int32)) == int(r["xla_selected"])
    z = jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(r["z"][..., 0]), np.asarray(z), atol=1e-5)


def test_masked_attention_kernels_against_the_xla_formulation(kernel_run) -> None:
    r = kernel_run
    np.testing.assert_allclose(np.asarray(r["out"], np.float32), np.asarray(r["xla_out"], np.float32), atol=0.03)
    g = r["g"].astype(jnp.float32)
    want = jax.grad(lambda q, k, v: jnp.sum(
        sa._dsa_xla(q, k, v, r["a"], r["bt"], r["w"], r["topk"], r["scale"])[0].astype(jnp.float32) * g),
        argnums=(0, 1, 2))(r["q"], r["k"], r["v"])
    for name, got, ref in zip("qkv", (r["dq"], r["dk"], r["dv"]), want):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.linalg.norm(got - ref) < 0.01 * np.linalg.norm(ref), name


def test_index_loss_kernel_gives_the_loss_and_its_gradient_in_one_pass(kernel_run) -> None:
    r = kernel_run
    assert abs(float(jnp.sum(r["kl"]) / 1024) - float(r["xla_loss"])) < 1e-5
    want = jax.grad(lambda a, bt, w: sa._dsa_xla(r["q"], r["k"], r["v"], a, bt, w, r["topk"], r["scale"])[1],
                    argnums=(0, 1, 2))(r["a"], r["bt"], r["w"])
    for name, got, ref in zip(("a", "bt", "w"), (r["da"], r["dbt"], r["dw"]), want):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.linalg.norm(got - ref) < 0.01 * np.linalg.norm(ref), name


@pytest.mark.parametrize("heads,kv", [(2, 2), (8, 1)], ids=["kv_group_1", "kv_group_8"])
@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_the_five_kernels_at_n_tiles_a_side_against_the_xla_formulation(n, heads, kv) -> None:
    """512 n positions: the selection kernels' mask is `lax.top_k`'s, the
    attention kernels under it (a step for each tile of the lower triangle,
    `kv_group` query heads reading one KV head in place) give `_dsa_xla`'s
    out, dq, dk, dv, and the index-loss kernel (256 x 512 tiles, walked the
    same way) its loss and the loss's gradient."""
    seq, topk, scale = 512 * n, 200, 128 ** -0.5
    q, k, v, a, bt, w, g = _kernel_operands(seed=n, heads=heads, kv=kv, seq=seq, j=2, ties=False)
    tau, cut, z = sa._select_pallas(a, bt, w, topk, interpret=True)
    mask = sa._mask_pallas(a, bt, w, tau, cut, interpret=True)
    assert mask.shape == (1, n * (n + 1) // 2, 512, 512)
    want_mask = sa.selection_mask(sa.index_scores(a, bt, w), topk)
    assert np.array_equal(_unpacked(mask, seq) != 0, np.asarray(want_mask))
    out, lse = sa._masked_flash_fwd(q, k, v, mask, scale, interpret=True)
    dq, dk, dv = sa._masked_flash_bwd(q, k, v, out, lse, g, mask, scale, interpret=True)
    kl, da, dbt, dw = sa._index_loss_pallas(q, k, lse, a, bt, w, z, mask, scale, interpret=True)
    gf = g.astype(jnp.float32)

    def both(q, k, v, a, bt, w):
        xla_out, xla_loss, _ = sa._dsa_xla(q, k, v, a, bt, w, topk, scale)
        return jnp.sum(xla_out.astype(jnp.float32) * gf), (xla_out, xla_loss)

    (_, (xla_out, xla_loss)), want = jax.value_and_grad(both, argnums=(0, 1, 2), has_aux=True)(q, k, v, a, bt, w)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(xla_out, np.float32), atol=0.03)
    assert abs(float(jnp.sum(kl) / seq) - float(xla_loss)) < 1e-5
    want += jax.grad(lambda a, bt, w: sa._dsa_xla(q, k, v, a, bt, w, topk, scale)[1], argnums=(0, 1, 2))(a, bt, w)
    for name, got, ref in zip(("q", "k", "v", "a", "bt", "w"), (dq, dk, dv, da, dbt, dw), want):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.linalg.norm(got - ref) < 0.01 * np.linalg.norm(ref), name


@pytest.mark.parametrize("seq", [4096, 8192, 32768])
def test_the_selection_kernels_grids_at_the_cells_lengths(seq) -> None:
    """`tpuft_dsa_mask` and `tpuft_dsa_index_loss` traced at the cells'
    lengths (nothing runs): a step for each 256 x 512 tile that holds a
    visible pair — key tiles 0 .. qi // 2 under query tile qi — and the
    row's sums are emitted at the last of them."""
    from test_ops import pallas_call_grids

    n = seq // 512
    visible = sum(qi // 2 + 1 for qi in range(seq // 256))
    assert visible == n * (n + 1)
    bf, f32 = jnp.bfloat16, jnp.float32
    a, bt, w = (jax.ShapeDtypeStruct(s, t) for s, t in (((1, 16, seq, 64), bf), ((1, 64, seq), bf), ((1, seq, 16), f32)))
    row = jax.ShapeDtypeStruct((1, seq, 1), jnp.int32)
    assert pallas_call_grids(sa._mask_pallas, a, bt, w, row, row) == {"tpuft_dsa_mask": (1, visible)}
    q, k = (jax.ShapeDtypeStruct((1, seq, h, 128), bf) for h in (32, 4))
    lse, z = jax.ShapeDtypeStruct((1, 32, seq), f32), jax.ShapeDtypeStruct((1, seq, 1), f32)
    mask = jax.ShapeDtypeStruct((1, n * (n + 1) // 2, 512, 512), jnp.int8)
    assert pallas_call_grids(lambda *ops: sa._index_loss_pallas(*ops, 0.088), q, k, lse, a, bt, w, z, mask) == {
        "tpuft_dsa_index_loss": (1, visible)}
    walk = sa._walk(seq)
    rows, cols = (np.asarray(t) for t in walk.tables)
    assert (cols <= rows // 2).all() and (np.diff(rows) >= 0).all() and rows[-1] == seq // 256 - 1
    assert all(int(walk.last_k(qi)) == qi // 2 for qi in (0, 1, 2, seq // 256 - 1))


def test_the_kernels_path_is_one_custom_vjp_with_the_right_partners(monkeypatch) -> None:
    """`sparse_attention` on the kernels' path (interpret mode under the
    gate): out's cotangent reaches q, k, v alone, the loss's a, b, w alone."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(sa._pallas_util, "on_tpu", lambda: True)
    q, k, v, a, bt, w, g = _kernel_operands(seed=2, seq=512, ties=False)
    args = (q, k, v, a, bt.transpose(0, 2, 1), w)

    def out_term(*args):
        return jnp.sum(sa.sparse_attention(*args, topk=100)[0].astype(jnp.float32) * g.astype(jnp.float32))

    def loss_term(*args):
        return sa.sparse_attention(*args, topk=100)[1]

    d_out = jax.grad(out_term, argnums=tuple(range(6)))(*args)
    d_loss = jax.grad(loss_term, argnums=tuple(range(6)))(*args)
    norms = lambda t: [float(jnp.linalg.norm(x.astype(jnp.float32))) for x in t]  # noqa: E731
    assert all(n > 0 for n in norms(d_out[:3])) and norms(d_out[3:]) == [0.0, 0.0, 0.0]
    assert norms(d_loss[:3]) == [0.0, 0.0, 0.0] and all(n > 0 for n in norms(d_loss[3:]))
    monkeypatch.setattr(sa._pallas_util, "on_tpu", lambda: False)
    want = jax.grad(loss_term, argnums=(3, 4, 5))(*args)
    for got, ref in zip(d_loss[3:], want):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.linalg.norm(got - ref) < 0.02 * np.linalg.norm(ref)
