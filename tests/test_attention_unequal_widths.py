"""The flash-attention kernels at unequal head widths (latent attention's: query
and key 256 wide, value 128) in interpret mode, and how the benchmark books
the one-pass backward by its name."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from architectures import BENCH


@pytest.mark.parametrize("seq,two_pass", [(1024, False), (2560, False), (1024, True), (2560, True)],
                         ids=["one_pass_2_blocks", "one_pass_5_blocks", "two_pass_2_blocks", "two_pass_5_blocks"])
def test_attention_kernels_at_unequal_widths_in_interpret_mode(seq, two_pass, monkeypatch) -> None:
    """Query and key 256 wide (MLA's 192 padded to a lane multiple with zero
    columns), value 128: the kernels against the XLA formulation at the
    TRUE width of 192, forward and backward — the one-pass backward that
    every such row short of 32,768 positions takes, and the two-pass form
    with the row's VMEM budget cut under it."""
    import attention_forms as forms
    from test_ops import ONE_PASS, TWO_PASS, pallas_call_names
    from torchft_tpu.ops import attention as fa

    if two_pass:
        monkeypatch.setattr(fa, "_DQ_ROW_VMEM_BUDGET", seq * 256 * 4 - 1)
    assert fa._dq_row_resident(seq, 256) != two_pass
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seq), 4)
    q, k = (jax.random.normal(kk, (2, seq, 192), jnp.float32) for kk in (k0, k1))
    v, g = (jax.random.normal(kk, (2, seq, 128), jnp.float32) for kk in (k2, k3))
    scale = 192 ** -0.5
    pad = [(0, 0), (0, 0), (0, 64)]
    qp, kp = jnp.pad(q, pad), jnp.pad(k, pad)
    want_o, want_lse = fa._fa_reference(q, k, v, scale, True)
    got_o, got_lse = forms.fwd(qp, kp, v, scale, True, interpret=True)
    assert got_o.shape == (2, seq, 128)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_lse), np.asarray(want_lse), rtol=1e-5, atol=1e-5)
    want = fa._fa_bwd_xla(q, k, v, want_o, want_lse, g, scale, True)
    bwd = functools.partial(forms.bwd, scale=scale, causal=True, interpret=True)
    assert pallas_call_names(bwd, qp, kp, v, got_o, got_lse, g) == (TWO_PASS if two_pass else ONE_PASS)
    got = bwd(qp, kp, v, got_o, got_lse, g)
    assert [a.shape for a in got] == [(2, seq, 256), (2, seq, 256), (2, seq, 128)]
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        a = np.asarray(a)
        if name != "dv":
            assert not a[..., 192:].any(), f"{name}: the padding columns carry a gradient"
            a = a[..., :192]
        np.testing.assert_allclose(a, np.asarray(b), rtol=2e-3, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "masked"])
@pytest.mark.parametrize("kv_group", [1, 8])
@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_the_kernels_walk_the_lower_triangle_at_unequal_widths(n, kv_group, masked) -> None:
    """Query and key 256 wide, value 128, n tiles a side: a grid step for
    each tile of the lower triangle, out, lse, dq, dk and dv the XLA
    formulation's (`test_attention_walks.check_the_triangular_walk`)."""
    from test_attention_walks import check_the_triangular_walk

    check_the_triangular_walk(n, 256, 128, kv_group, masked)


@pytest.mark.parametrize("program", ["dense_lm", "moe_lm", "mla_moe_lm"])
def test_the_one_pass_backward_is_booked_to_attention_by_its_name(program) -> None:
    """The benchmark attributes device time to attention by substring and
    `chip_smoke.has_kernel` by whole word: the one-pass kernel's name has to
    stay inside the first and is a name of its own to the second, and no
    `tpuft_fa_bwd_dq` is found in it (its absence from a trace is the
    evidence that the one-pass form ran)."""
    import chip_smoke

    op = "%tpuft_fa_bwd_dkdv_dq.7 = (bf16[32,8192,256]) custom-call(...), custom_call_target=\"tpu_custom_call\""
    assert BENCH.program(program).kernel_names()["attn"](op)
    assert "tpuft_fa_bwd_dq" not in op
    assert chip_smoke.has_kernel(op, "tpuft_fa_bwd_dkdv_dq") and "tpuft_fa_bwd_dkdv_dq" in chip_smoke.KERNELS
    assert not chip_smoke.has_kernel(op, "tpuft_fa_bwd_dkdv") and not chip_smoke.has_kernel(op, "tpuft_fa_bwd_dq")


def test_flash_attention_takes_a_value_width_of_its_own() -> None:
    """The public entry point off the TPU: [B, S, H, 48] queries and keys,
    [B, S, H, 32] values, the scale from the query's width, gradients of the
    operands' own shapes."""
    from torchft_tpu.ops import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k = (jax.random.normal(kk, (2, 64, 4, 48), jnp.float32) for kk in keys[:2])
    v = jax.random.normal(keys[2], (2, 64, 4, 32), jnp.float32)

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 48 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = flash_attention(q, k, v)
    assert out.shape == (2, 64, 4, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain(q, k, v)), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
