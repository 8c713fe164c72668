"""Ring attention, contiguous and zigzag, against full attention on the virtual
CPU mesh: values and gradients.  Every ring call is jitted: dispatched operation
by operation, a `shard_map` over four devices compiled each primitive of the
ring and of its transpose as a four-device program of its own (67 s alone and
190 s in a packed run for 64 positions of 16 columns; one program takes 3 s),
and a training step never runs it that way."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_ops import _naive_attention


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

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
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

    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        mesh, q, k, v, causal=causal, batch_axis="data", head_axis=None,
    ))(q, k, v)
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
    out = jax.jit(lambda q, k, v: from_zigzag(ring_attention_sharded(
        mesh,
        to_zigzag(q, n, axis=2),
        to_zigzag(k, n, axis=2),
        to_zigzag(v, n, axis=2),
        causal=True,
        batch_axis="data",
        head_axis=None,
        layout="zigzag",
    ), n, axis=2))(q, k, v)
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

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_dense, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3, err_msg=name
        )
