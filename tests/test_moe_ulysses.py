"""MoE expert parallelism + Ulysses sequence parallelism tests.

Both are capabilities beyond the reference (SURVEY.md §2.3/§5: EP and
sequence parallelism absent there).  Run on the virtual 8-device CPU mesh
(conftest pins the platform); numerics compare sharded execution against
single-device execution of the same function — the same criterion the
TP/SP tests use (tests/test_model_parallel.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import TransformerConfig, init_params, loss_fn
from torchft_tpu.models.moe import moe_capacity, moe_ffn
from torchft_tpu.models.transformer import param_axes
from torchft_tpu.ops import flash_attention
from torchft_tpu.ops.ulysses import ulysses_attention_sharded
from torchft_tpu.parallel import ft_init_mesh


MOE_CFG = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    max_seq=64,
    dtype=jnp.float32,
    moe_experts=4,
    moe_top_k=2,
    # Generous capacity so the dense/sparse comparison isn't confounded by
    # token dropping.
    moe_capacity_factor=4.0,
)


def _moe_weights(key, n_exp=4, E=32, F=64):
    kr, kg, ku, kd = jax.random.split(key, 4)
    s = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5
    return (
        s(kr, (E, n_exp), E),
        s(kg, (n_exp, E, F), E),
        s(ku, (n_exp, E, F), E),
        s(kd, (n_exp, F, E), F),
    )


def test_moe_capacity_static() -> None:
    assert moe_capacity(1024, 8, 2, 1.25) % 8 == 0
    assert moe_capacity(8, 64, 1, 1.0) >= 8  # floor


def test_moe_matches_manual_expert_mix() -> None:
    """With capacity ample enough that nothing drops, the MoE output equals
    the explicit per-token mixture of its top-k experts' FFNs."""
    key = jax.random.PRNGKey(0)
    router, w_gate, w_up, w_down = _moe_weights(key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32), jnp.float32)

    y, aux = moe_ffn(
        x, router, w_gate, w_up, w_down,
        top_k=2, capacity_factor=8.0, dtype=jnp.float32,
    )
    assert y.shape == x.shape and np.isfinite(float(aux))

    xf = x.reshape(-1, 32)
    probs = jax.nn.softmax(xf @ router, axis=-1)
    gv, gi = jax.lax.top_k(probs, 2)
    gv = gv / jnp.sum(gv, axis=-1, keepdims=True)

    def expert(e, t):
        h = jax.nn.silu(xf[t] @ w_gate[e]) * (xf[t] @ w_up[e])
        return h @ w_down[e]

    manual = np.stack(
        [
            sum(float(gv[t, j]) * np.asarray(expert(int(gi[t, j]), t)) for j in range(2))
            for t in range(xf.shape[0])
        ]
    )
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 32), manual, rtol=2e-4, atol=2e-5)


def test_moe_drops_tokens_at_capacity() -> None:
    """Over-capacity tokens contribute zero (their residual path carries
    them) instead of corrupting other tokens' outputs."""
    key = jax.random.PRNGKey(0)
    router, w_gate, w_up, w_down = _moe_weights(key)
    # Route everything to one expert: positive inputs + a router whose only
    # nonzero column is expert 0 make logits[:, 0] > 0 = all others.
    router = jnp.zeros_like(router).at[:, 0].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32), jnp.float32)) + 0.1
    y, _ = moe_ffn(
        x, router, w_gate, w_up, w_down,
        top_k=1, capacity_factor=0.25, dtype=jnp.float32,
    )
    # capacity = ceil-pad(64 * 1 * 0.25 / 4) -> 8 of 64 tokens kept.
    nonzero = np.count_nonzero(np.abs(np.asarray(y).reshape(64, 32)).sum(-1) > 1e-9)
    assert nonzero == 8, f"expected 8 kept tokens, got {nonzero}"


def test_moe_transformer_sharded_matches_single_device() -> None:
    """The MoE transformer over an expert x data mesh matches single-device
    execution bitwise-closely; expert weights actually carry the expert
    sharding."""
    params = init_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, size=(4, 64)), dtype=jnp.int32
    )
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    single = loss_fn(params, batch, MOE_CFG)

    ftmesh = ft_init_mesh({"data": 2, "expert": 4})
    sharded_params = ftmesh.shard_params(params, param_axes(MOE_CFG))
    wg = sharded_params["layers"]["w_gate"]
    spec = wg.sharding.spec
    assert "expert" in str(spec), f"expert axis not sharded: {spec}"
    sharded = loss_fn(
        sharded_params,
        jax.device_put(batch, ftmesh.sharding("batch", "seq")),
        MOE_CFG,
        ftmesh.mesh,
        ftmesh.rules,
    )
    np.testing.assert_allclose(float(single), float(sharded), rtol=1e-5)


def test_ulysses_matches_flash_attention() -> None:
    """Ulysses all-to-all attention over the sequence axis == single-device
    flash attention."""
    B, H, S, D = 2, 8, 64, 16
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (B, H, S, D), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    ref = flash_attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)), causal=True).transpose(0, 2, 1, 3)  # position-major

    ftmesh = ft_init_mesh({"data": 2, "sequence": 4})
    spec = ftmesh.rules.sharding(("batch", "heads", "seq", None), ftmesh.mesh)
    qs, ks, vs = (jax.device_put(t, spec) for t in (q, k, v))
    out = ulysses_attention_sharded(
        ftmesh.mesh, qs, ks, vs, causal=True,
        batch_axis="data", head_axis=None, seq_axis="sequence",
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ulysses_transformer_end_to_end() -> None:
    """The transformer runs with attention='ulysses' over a sequence-sharded
    mesh and matches the flash (single-device) loss."""
    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq=64, dtype=jnp.float32, attention="ulysses",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, size=(2, 64)), dtype=jnp.int32
    )
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    dense_cfg = TransformerConfig(**{**cfg.__dict__, "attention": "flash"})
    single = loss_fn(params, batch, dense_cfg)

    ftmesh = ft_init_mesh({"data": 2, "sequence": 4})
    sharded_params = ftmesh.shard_params(params, param_axes(cfg))
    sharded = loss_fn(
        sharded_params,
        jax.device_put(batch, ftmesh.sharding("batch", "seq")),
        cfg,
        ftmesh.mesh,
        ftmesh.rules,
    )
    np.testing.assert_allclose(float(single), float(sharded), rtol=1e-5)


def test_ulysses_gqa_compressed_kv() -> None:
    """GQA stays compressed through the all_to_all (kv heads < q heads) and
    still matches the broadcast single-device result."""
    B, Hq, Hkv, S, D = 2, 8, 4, 64, 16
    key = jax.random.PRNGKey(2)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(kk, (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(kv_, (B, Hkv, S, D), jnp.float32)
    ref = flash_attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)), causal=True).transpose(0, 2, 1, 3)  # position-major

    ftmesh = ft_init_mesh({"data": 2, "sequence": 4})
    qspec = ftmesh.rules.sharding(("batch", "heads", "seq", None), ftmesh.mesh)
    qs = jax.device_put(q, qspec)
    ks = jax.device_put(k, qspec)
    vs = jax.device_put(v, qspec)
    out = ulysses_attention_sharded(
        ftmesh.mesh, qs, ks, vs, causal=True,
        batch_axis="data", head_axis=None, seq_axis="sequence",
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_with_tp_broadcasts_when_needed() -> None:
    """TP x SP GQA config where kv heads per TP shard don't tile the
    sequence axis: the transformer must auto-broadcast K/V (per-shard
    divisibility, not global) instead of tripping the Ulysses assert."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=1, n_heads=8, n_kv_heads=2,
        d_ff=64, max_seq=32, dtype=jnp.float32, attention="ulysses",
    )
    ftmesh = ft_init_mesh({"tensor": 2, "sequence": 2})
    params = ftmesh.shard_params(init_params(jax.random.PRNGKey(0), cfg), param_axes(cfg))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, size=(2, 32)), dtype=jnp.int32
    )
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    loss = loss_fn(
        params,
        jax.device_put(batch, ftmesh.sharding("batch", "seq")),
        cfg,
        ftmesh.mesh,
        ftmesh.rules,
    )
    assert np.isfinite(float(loss))


def test_ulysses_head_divisibility_guard() -> None:
    ftmesh = ft_init_mesh({"sequence": 4})
    q = jnp.zeros((1, 2, 64, 16), jnp.float32)  # 2 heads < 4-way axis
    with pytest.raises(AssertionError, match="divisible"):
        ulysses_attention_sharded(
            ftmesh.mesh, q, q, q, batch_axis=None, head_axis=None,
        )
