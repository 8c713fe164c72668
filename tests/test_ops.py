"""Ops correctness: flash attention (reference + pallas-interpret), the fused
cross-entropy head and RMSNorm.  The flash kernels' walks are in
`tests/test_attention_walks.py`, ring attention in `tests/test_ring_attention.py`:
a file is one worker's under the driver's `--dist loadfile`."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _major(x):
    """[B, heads, S, d] <-> [B, S, heads, d]: the oracles here are written
    head-major, `flash_attention` takes and gives position-major."""
    return x.transpose(0, 2, 1, 3)


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
    out = _major(flash_attention(_major(q), _major(k), _major(v), causal=causal))
    np.testing.assert_allclose(
        np.asarray(out), _naive_attention(q, k, v, causal), rtol=1e-4, atol=1e-4
    )


def test_flash_attention_gqa_broadcast() -> None:
    from torchft_tpu.ops import flash_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 4, 32, 16)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 32, 16)), dtype=jnp.float32)
    out = _major(flash_attention(_major(q), _major(k), _major(v), causal=True))
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
        return jnp.sum(flash_attention(_major(q), _major(k), _major(v), causal=True) ** 2)

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
    from attention_forms import fwd as _fa_pallas_call
    from torchft_tpu.ops.attention import _fa_reference

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
    import attention_forms as forms
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
    bwd = functools.partial(forms.bwd, scale=scale, causal=causal, interpret=True)
    assert pallas_call_names(bwd, q, k, v, o, lse, g) == (TWO_PASS if over_budget else ONE_PASS)
    d_pl = bwd(q, k, v, o, lse, g)
    for a, b, name in zip(d_pl, d_ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=name
        )


def test_flash_attention_bwd_is_one_pallas_call_at_the_cells_shape() -> None:
    """Two heads of 128 at 4,096 positions in bf16, the dense cells' sequence
    ([1, 4096, 2 * 128] as the kernels take them): the backward's jaxpr
    holds exactly one `pallas_call`, whose outputs are dk, dv and a dq of
    the operand's dtype — no f32 dq, no partials, nothing for XLA to sum."""
    from torchft_tpu.ops import attention as fa

    qkv = jax.ShapeDtypeStruct((1, 4096, 256), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((2, 4096), jnp.float32)
    bwd = functools.partial(fa._fa_bwd_pallas, scale=0.088, causal=True, q_heads=2)
    jaxpr = jax.make_jaxpr(bwd)(qkv, qkv, qkv, qkv, lse, qkv)
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [call.params["name"]] == ONE_PASS
    assert [(v.aval.shape, v.aval.dtype) for v in call.outvars] == [((1, 4096, 256), jnp.bfloat16)] * 3
    assert {id(v) for v in jaxpr.jaxpr.outvars} == {id(v) for v in call.outvars}
    # the row's size is the only thing the choice reads: 65,536 at 128 wide
    # is the longest resident row, one block more is not
    assert fa._dq_row_resident(65536, 128) and not fa._dq_row_resident(65536 + 512, 128)
    assert fa._dq_row_resident(32768, 256) and not fa._dq_row_resident(65536, 256)


def pallas_call_grids(fn, *args) -> dict:
    """{name: grid} of every `pallas_call` in `fn`'s jaxpr."""
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return {e.params["name"]: tuple(e.params["grid_mapping"].grid) for e in eqns if e.primitive.name == "pallas_call"}


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


@pytest.mark.parametrize("path", ["xla", "kernels_interpreted"])
def test_the_per_row_cross_entropy_is_the_mean_forms_rows(path, monkeypatch) -> None:
    """`fused_linear_cross_entropy_per_row` — the XLA form, and the `tpuft_ce_*`
    kernels in interpret mode with a scale a ROW in `tpuft_ce_dlogits` — against
    the materialized formulation: the loss of every row, and dx and dw under a
    random cotangent a row; its mean is `fused_linear_cross_entropy`, and under
    the cotangent 1 / N a row it has the mean form's gradients."""
    import functools

    from torchft_tpu.ops import _pallas_util, cross_entropy as ce

    rng = np.random.default_rng(21)
    n, e, v = 256, 128, 512
    x = jnp.asarray(rng.standard_normal((n, e)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, v)) * 0.1, dtype=jnp.float32)
    t = jnp.asarray(rng.integers(0, v, n), dtype=jnp.int32)
    g = jnp.asarray(rng.standard_normal(n), dtype=jnp.float32)
    mean_grads = jax.grad(ce.fused_linear_cross_entropy, argnums=(0, 1))(x, w, t)  # the XLA form, before any patch
    if path == "kernels_interpreted":
        monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
        lse_kernel = ce._ce_lse_pallas  # `_ce_fwd` names `interpret` itself
        monkeypatch.setattr(ce, "_ce_lse_pallas", lambda x, w, interpret=False, valid_v=None: lse_kernel(x, w, True, valid_v))
        monkeypatch.setattr(ce, "_ce_dlogits_pallas", functools.partial(ce._ce_dlogits_pallas, interpret=True))

    def rows(x, w):
        logits = x @ w
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]

    got, vjp = jax.vjp(lambda x, w: ce.fused_linear_cross_entropy_per_row(x, w, t), x, w)
    want, want_vjp = jax.vjp(rows, x, w)
    assert got.shape == (n,) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    for a, b, name in zip(vjp(g), want_vjp(g), ("dx", "dw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5, err_msg=name)
    np.testing.assert_allclose(float(jnp.mean(got)), float(jnp.mean(want)), rtol=1e-6)
    for a, b, name in zip(vjp(jnp.full((n,), 1.0 / n, jnp.float32)), mean_grads, ("dx", "dw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6, err_msg=name)
    if path == "kernels_interpreted":  # the kernel's own product, a scale a row against one number a row
        lse = jax.nn.logsumexp(x @ w, axis=-1)
        a_row = ce._ce_dlogits_pallas(x, w, t, lse, jnp.full((n,), 0.37, jnp.float32))
        one = ce._ce_dlogits_pallas(x, w, t, lse, 0.37)
        assert np.array_equal(np.asarray(a_row), np.asarray(one))


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


def test_a_head_width_no_block_divides_runs_the_padded_cross_entropy() -> None:
    """18,992 = 16 x 1,187 columns: zero columns pad the head to a multiple of
    512, the kernels take the padding's logits as -inf, and loss and gradients
    are those of the unpadded head."""
    from torchft_tpu.ops import cross_entropy as ce

    assert ce.padded_vocab(18992) == 19456 and ce._block_v(19456, 2048) == 512 and ce.padded_vocab(20480) == 20480
    n, e, v = 256, 128, 1000
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x, w = jax.random.normal(ks[0], (n, e)), jax.random.normal(ks[1], (e, v)) * 0.1
    t = jax.random.randint(ks[2], (n,), 0, v)

    def plain(x, w):
        logits = x @ w
        return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, t[:, None], -1)[:, 0])

    want, dwant = jax.value_and_grad(plain, (0, 1))(x, w)
    got, dgot = jax.value_and_grad(lambda x, w: ce.fused_linear_cross_entropy_padded(x, w, t), (0, 1))(x, w)
    assert abs(float(got) - float(want)) < 1e-6
    for a, b in zip(dgot, dwant):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    padded = jnp.pad(w, ((0, 0), (0, ce.padded_vocab(v) - v)))
    lse = ce._ce_lse_pallas(x, padded, interpret=True, valid_v=v)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(jax.nn.logsumexp(x @ w, -1)), atol=1e-5)
    dl = ce._ce_dlogits_pallas(x, padded, t, lse, 1.0, interpret=True, valid_v=v)
    np.testing.assert_allclose(np.asarray(dl[:, :v]), np.asarray(jax.nn.softmax(x @ w, -1) - jax.nn.one_hot(t, v)), atol=1e-6)
    assert float(jnp.max(jnp.abs(dl[:, v:]))) == 0.0


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
