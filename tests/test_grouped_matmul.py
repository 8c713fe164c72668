"""``ops/grouped_matmul.py``: the product and both gradients against a per-expert
loop and against ``jax.lax.ragged_dot``, the three pallas kernels in interpret
mode, a width of 14.5 lane tiles padded inside the call, and what is said of a
shape the kernels do not tile."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import grouped_matmul as gmm
from torchft_tpu.ops.grouped_matmul import grouped_matmul, padded_group_sizes


def _rows(counts, tile, k, key):
    """Rows in the kernels' layout: each group padded to whole tiles (one at
    least), zeros in the padding."""
    sizes = np.asarray(padded_group_sizes(jnp.asarray(counts, jnp.int32), tile))
    total = int(sizes.sum()) + tile  # one tile past the last group
    real = np.zeros(total, bool)
    for start, count in zip(np.concatenate([[0], np.cumsum(sizes)[:-1]]), counts):
        real[start:start + count] = True
    return jax.random.normal(key, (total, k), jnp.float32) * real[:, None], jnp.asarray(sizes), real


def _per_expert_loop(lhs, rhs, sizes):
    out, start = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32), 0
    for g, size in enumerate(np.asarray(sizes)):
        out = out.at[start:start + size].set(lhs[start:start + size] @ rhs[g])
        start += size
    return out


@pytest.mark.parametrize("interpret", [False, True], ids=["ragged_dot", "kernels_interpreted"])
@pytest.mark.parametrize("k,n", [(256, 128), (128, 384)])
def test_grouped_matmul_matches_a_per_expert_loop(interpret, k, n) -> None:
    """Forward and both gradients, with a group of no rows, one of a few and
    one of several tiles; `interpret` runs the three pallas kernels."""
    counts, tile = [130, 0, 5, 300], 128
    lhs, sizes, real = _rows(counts, tile, k, jax.random.PRNGKey(0))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (4, k, n), jnp.float32) * k ** -0.5
    weight = jax.random.normal(jax.random.PRNGKey(2), (lhs.shape[0], n)) * real[:, None]

    def ours(l, r):
        return grouped_matmul(l, r, sizes, row_tile=tile, interpret=interpret)

    np.testing.assert_allclose(np.asarray(ours(lhs, rhs)), np.asarray(_per_expert_loop(lhs, rhs, sizes)), atol=2e-4)
    got = jax.grad(lambda l, r: jnp.sum(ours(l, r) * weight), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda l, r: jnp.sum(_per_expert_loop(l, r, sizes) * weight), argnums=(0, 1))(lhs, rhs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)
    assert got[1].dtype == rhs.dtype and not np.asarray(got[1])[1].any()  # the group without rows


def test_grouped_matmul_rounds_wide_matrices_once_and_returns_their_gradient_unrounded() -> None:
    """bf16 rows against f32 matrices: the product is the bf16 one, the
    matrices' gradient comes back in f32 from the f32 accumulator."""
    counts, tile = [200, 56], 128
    lhs, sizes, real = _rows(counts, tile, 128, jax.random.PRNGKey(3))
    lhs = lhs.astype(jnp.bfloat16)
    rhs = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 128), jnp.float32)
    out = grouped_matmul(lhs, rhs, sizes, row_tile=tile, interpret=True)
    assert out.dtype == jnp.bfloat16
    want = _per_expert_loop(lhs.astype(jnp.float32), rhs.astype(jnp.bfloat16).astype(jnp.float32), sizes)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), rtol=1e-2, atol=1e-1)
    drhs = jax.grad(lambda r: jnp.sum(grouped_matmul(lhs, r, sizes, row_tile=tile, interpret=True).astype(jnp.float32)))(rhs)
    assert drhs.dtype == jnp.float32
    exact = np.asarray(lhs, np.float32)[:256].T @ np.ones((256, 128), np.float32)
    np.testing.assert_allclose(np.asarray(drhs)[0], exact, rtol=1e-5, atol=1e-4)  # no bf16 rounding of the sums


@pytest.mark.parametrize("which", ["output", "dlhs", "drhs"])
@pytest.mark.parametrize("product", ["up_at_14_and_a_half_tiles", "down_at_14_and_a_half_tiles"])
def test_a_width_of_1856_takes_the_kernel_path_and_equals_ragged_dot(product, which, monkeypatch) -> None:
    """1,856 = 14.5 x 128 columns: `grouped_matmul` pads both operands with
    zeros to 1,920 inside the call and runs the `tpuft_gmm_*` kernels (interpret
    mode here; the call is counted), the result and both gradients equal
    `jax.lax.ragged_dot`'s on the leaves' own shapes."""
    k, n = (256, 1856) if product.startswith("up") else (1856, 256)
    rng = np.random.default_rng(7)
    sizes = gmm.padded_group_sizes(jnp.asarray([100, 0, 300]), 128)             # 128 + 128 + 384 rows
    lhs = jnp.asarray(rng.standard_normal((768, k)), jnp.float32)               # a tile past the last group
    rhs = jnp.asarray(rng.standard_normal((3, k, n)) * k ** -0.5, jnp.float32)
    calls = []
    real = gmm._gmm
    monkeypatch.setattr(gmm, "_gmm", lambda *a: calls.append(a[0].shape + a[1].shape) or real(*a))
    weight = jnp.asarray(rng.standard_normal((768, n)), jnp.float32).at[640:].set(0.0)
    with jax.default_matmul_precision("highest"):
        run = lambda l, r: gmm.grouped_matmul(l, r, sizes, row_tile=128, interpret=True)   # noqa: E731
        plain = lambda l, r: jax.lax.ragged_dot(l, r, sizes)                               # noqa: E731
        if which == "output":
            got, want = run(lhs, rhs), plain(lhs, rhs)
            assert got.shape == (768, n)
        else:
            arg = 0 if which == "dlhs" else 1
            got = jax.grad(lambda l, r: jnp.sum(run(l, r) * weight), argnums=arg)(lhs, rhs)
            want = jax.grad(lambda l, r: jnp.sum(plain(l, r) * weight), argnums=arg)(lhs, rhs)
            assert got.shape == (lhs, rhs)[arg].shape
    assert calls and calls[0] == (768, -(-k // 128) * 128, 3, -(-k // 128) * 128, -(-n // 128) * 128)
    np.testing.assert_allclose(np.asarray(got)[:640] if which != "drhs" else np.asarray(got),
                               np.asarray(want)[:640] if which != "drhs" else np.asarray(want), rtol=2e-5, atol=2e-5)


def test_a_shape_that_does_not_tile_is_said_once(monkeypatch, caplog) -> None:
    """Where the caller promised tiles on one TPU device and the kernels do not
    tile the product (rows that are no whole tiles), the fall to `ragged_dot`
    is logged, once a shape."""
    monkeypatch.setattr(gmm._pallas_util, "kernels_apply", lambda mesh=None: True)
    gmm._say_once.cache_clear()
    lhs, rhs = jnp.ones((200, 128)), jnp.ones((2, 128, 128))
    sizes = jnp.asarray([128, 72], jnp.int32)
    with caplog.at_level(logging.WARNING, logger=gmm.logger.name):
        for _ in range(3):
            out = gmm.grouped_matmul(lhs, rhs, sizes, row_tile=128)
    assert out.shape == (200, 128)
    said = [r for r in caplog.records if "does not tile" in r.getMessage()]
    assert len(said) == 1 and "[200, 128] x [G, 128, 128]" in said[0].getMessage()
