"""Flagship transformer + parallel layer on the virtual 8-device CPU mesh.

Covers: forward/loss shapes, sharded vs single-device numerics, TP+DP+SP
mesh execution, FTMesh dynamic replica size reporting, TrainStep full/split
paths, and the ft_step commit gate with a mocked Manager.
"""

from unittest.mock import create_autospec

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchft_tpu.manager import Manager
from torchft_tpu.models import TransformerConfig, init_params, loss_fn
from torchft_tpu.models.transformer import forward, param_axes
from torchft_tpu.parallel import FTMesh, ShardingRules, TrainStep, ft_init_mesh
from torchft_tpu.futures import completed_future

CFG = TransformerConfig(
    vocab_size=128,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq=32,
    dtype=jnp.float32,  # exact comparisons on CPU
)


def _batch(b=4, s=16, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, CFG.vocab_size, size=(b, s)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    return {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(targets)}


def test_forward_shapes_and_loss() -> None:
    params = init_params(jax.random.PRNGKey(0), CFG)
    batch = _batch()
    logits = forward(params, batch["tokens"], CFG)
    assert logits.shape == (4, 16, CFG.vocab_size)
    loss = loss_fn(params, batch, CFG)
    assert np.isfinite(float(loss))
    # Untrained model should be near uniform: loss ~ log(vocab).
    assert abs(float(loss) - np.log(CFG.vocab_size)) < 1.0


def test_scan_unroll_matches_scan() -> None:
    """Unrolling the layer scan (the bench perf config) is a pure scheduling
    change: logits and grads must match scan_unroll=1 up to fusion-order
    rounding."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    batch = _batch()
    cfg_u = TransformerConfig(**{**CFG.__dict__, "scan_unroll": CFG.n_layers})

    ref = np.asarray(forward(params, batch["tokens"], CFG))
    got = np.asarray(forward(params, batch["tokens"], cfg_u))
    # Tight tolerance, not bitwise: full unroll is a static Python loop
    # (different op association than scan), and fusion choices differ in
    # the last ulps.
    np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-5)

    g_ref = jax.grad(lambda p: loss_fn(p, batch, CFG))(params)
    g_got = jax.grad(lambda p: loss_fn(p, batch, cfg_u))(params)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_got)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


@pytest.mark.parametrize("unroll", [1, CFG.n_layers], ids=["scan", "static_loop"])
def test_grads_finished_inside_their_layer_are_the_same_grads(unroll, monkeypatch) -> None:
    """Where the head runs in pieces (here: a budget of nothing for its
    dlogits) a layer's cotangents pass one barrier, x's and the weights'
    together: one barrier more a layer (one in a scan's body), and loss and
    gradients are those without it to the last bit."""
    from torchft_tpu.ops import cross_entropy

    cfg = TransformerConfig(**{**CFG.__dict__, "scan_unroll": unroll, "remat": True})
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch()

    def run():
        f = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg))
        return f(params), jax.jit(f).lower(params).as_text()

    (loss, grads), plain = run()
    monkeypatch.setattr(cross_entropy, "_DLOGITS_BYTES", 0)
    (loss_in, grads_in), tied = run()
    # beside the ones `jax.checkpoint` puts before a layer's recomputed forward pass
    barriers = tied.count("optimization_barrier") - plain.count("optimization_barrier")
    assert barriers == (cfg.n_layers if unroll > 1 else 1)
    assert float(loss) == float(loss_in)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_in)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_matches_single_device() -> None:
    params = init_params(jax.random.PRNGKey(0), CFG)
    batch = _batch()
    ref = np.asarray(loss_fn(params, batch, CFG))

    ftmesh = ft_init_mesh({"data": 2, "tensor": 2, "sequence": 2})
    sharded_params = ftmesh.shard_params(params, param_axes(CFG))
    got = np.asarray(
        jax.jit(lambda p, b: loss_fn(p, b, CFG, ftmesh.mesh, ftmesh.rules))(
            sharded_params, batch
        )
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_ring_attention_model_matches_flash() -> None:
    cfg_ring = TransformerConfig(**{**CFG.__dict__, "attention": "ring"})
    params = init_params(jax.random.PRNGKey(1), CFG)
    batch = _batch(b=2, s=32)
    ref = np.asarray(loss_fn(params, batch, CFG))

    ftmesh = ft_init_mesh({"data": 2, "sequence": 4})
    sharded = ftmesh.shard_params(params, param_axes(CFG))
    got = np.asarray(
        jax.jit(lambda p, b: loss_fn(p, b, cfg_ring, ftmesh.mesh, ftmesh.rules))(
            sharded, batch
        )
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_zigzag_ring_model_matches_flash() -> None:
    """Full model with ring_layout='zigzag': feeding zigzag-permuted
    tokens/targets yields the same loss as the unsharded flash model on the
    original order (mean CE is permutation-invariant; rope positions follow
    the permutation internally)."""
    from torchft_tpu.ops.ring_attention import to_zigzag

    cfg_z = TransformerConfig(
        **{**CFG.__dict__, "attention": "ring", "ring_layout": "zigzag"}
    )
    params = init_params(jax.random.PRNGKey(1), CFG)
    batch = _batch(b=2, s=32)
    ref = np.asarray(loss_fn(params, batch, CFG))

    ftmesh = ft_init_mesh({"data": 2, "sequence": 4})
    sharded = ftmesh.shard_params(params, param_axes(CFG))
    zbatch = {
        "tokens": to_zigzag(batch["tokens"], 4, axis=1),
        "targets": to_zigzag(batch["targets"], 4, axis=1),
    }
    got = np.asarray(
        jax.jit(lambda p, b: loss_fn(p, b, cfg_z, ftmesh.mesh, ftmesh.rules))(
            sharded, zbatch
        )
    )
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_ftmesh_dynamic_replica_size() -> None:
    manager = create_autospec(Manager, instance=True)
    manager.num_participants.return_value = 3
    manager.participating_rank.return_value = 1
    ftmesh = ft_init_mesh({"data": 2, "tensor": 2}, manager=manager)
    assert ftmesh.size("replica") == 3
    assert ftmesh.size("data") == 2
    assert ftmesh.size() == 12  # 3 replicas x 4 local devices
    assert ftmesh.replica_rank() == 1
    assert ftmesh.axis_names[0] == "replica"


def test_ftmesh_rejects_unknown_axis() -> None:
    with pytest.raises(ValueError, match="unknown mesh axis"):
        ft_init_mesh({"bogus": 2})


def test_train_step_full_decreases_loss() -> None:
    import optax

    params = init_params(jax.random.PRNGKey(0), CFG)
    ftmesh = ft_init_mesh({"data": 2, "tensor": 2})
    params = ftmesh.shard_params(params, param_axes(CFG))
    step = TrainStep(
        ftmesh, optax.adam(1e-2),
        lambda p, b: loss_fn(p, b, CFG, ftmesh.mesh, ftmesh.rules),
    )
    opt_state = step.init_opt_state(params)
    batch = _batch()
    losses = []
    for _ in range(5):
        params, opt_state, loss = step.full_step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_train_step_split_matches_full() -> None:
    import optax

    params = init_params(jax.random.PRNGKey(0), CFG)
    ftmesh = ft_init_mesh({"data": 2})
    step = TrainStep(ftmesh, optax.sgd(0.1), lambda p, b: loss_fn(p, b, CFG))
    opt_state = step.init_opt_state(params)
    batch = _batch()

    loss, grads = step.grads(params, batch)
    p2, _ = step.apply(
        jax.tree.map(jnp.copy, params), step.init_opt_state(params), grads
    )
    p1, _, loss_full = step.full_step(
        jax.tree.map(jnp.copy, params), opt_state, batch
    )
    np.testing.assert_allclose(float(loss), float(loss_full), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_tree_device_bytes_counts_shards_not_globals() -> None:
    """A sharded leaf costs each device only its shard; a replicated leaf
    costs the full array — the budget the auto overlap decision uses."""
    from jax.sharding import NamedSharding, PartitionSpec

    from torchft_tpu.parallel.trainer import tree_device_bytes

    ftmesh = ft_init_mesh({"data": 4})
    x = jnp.zeros((8, 16), jnp.float32)  # 512 bytes global
    sharded = jax.device_put(
        x, NamedSharding(ftmesh.mesh, PartitionSpec("data", None))
    )
    replicated = jax.device_put(
        x, NamedSharding(ftmesh.mesh, PartitionSpec(None, None))
    )
    assert tree_device_bytes({"a": sharded}) == 512 // 4
    assert tree_device_bytes({"a": replicated}) == 512
    assert tree_device_bytes({"a": sharded, "b": replicated}) == 512 + 128


def test_speculation_fits_budget_arithmetic() -> None:
    from torchft_tpu.parallel.trainer import speculation_fits

    class FakeDevice:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    # 10 GB free, 90% headroom => 9 GB budget.
    stats = {"bytes_limit": 16 << 30, "bytes_in_use": 6 << 30}
    assert speculation_fits(8 << 30, FakeDevice(stats)) is True
    assert speculation_fits(10 << 30, FakeDevice(stats)) is False
    # The allocator peak (post-step: includes activations/workspace)
    # governs when reported: 16-12=4 GB budget despite 10 GB "free" now.
    peaky = dict(stats, peak_bytes_in_use=12 << 30)
    assert speculation_fits(3 << 30, FakeDevice(peaky)) is True
    assert speculation_fits(8 << 30, FakeDevice(peaky)) is False
    # ... and so does a larger mark the caller knows of (the compiler's
    # footprint of the step's programs, where the allocator's peak reads low).
    assert speculation_fits(3 << 30, FakeDevice(peaky), floor=13 << 30) is False
    assert speculation_fits(2 << 30, FakeDevice(peaky), floor=13 << 30) is True
    # No statistics (CPU devices): undecidable.
    assert speculation_fits(1, FakeDevice(None)) is None
    assert speculation_fits(1, FakeDevice({})) is None


def test_ft_step_auto_overlap_falls_back_when_memory_tight(monkeypatch) -> None:
    """overlap_commit=None (the default) must take the donated in-place
    apply when the device reports the speculative copy won't fit."""
    from datetime import timedelta

    import optax

    import torchft_tpu.parallel.trainer as trainer_mod

    manager = create_autospec(Manager, instance=True)
    manager.num_participants.return_value = 2
    manager.timeout = timedelta(seconds=60)
    manager.allreduce.side_effect = lambda arr, should_average=True, **kw: completed_future(
        np.asarray(arr)
    )
    manager.should_commit.return_value = True

    params = init_params(jax.random.PRNGKey(0), CFG)
    ftmesh = ft_init_mesh({"data": 2}, manager=manager)
    step = TrainStep(ftmesh, optax.sgd(0.1), lambda p, b: loss_fn(p, b, CFG))
    assert step.overlap_commit is None

    monkeypatch.setattr(trainer_mod, "speculation_fits", lambda extra, dev, floor=0: False)
    opt_state = step.init_opt_state(params)
    params, opt_state, _, committed = step.ft_step(params, opt_state, batch=_batch())
    assert committed is True
    assert step._overlap_resolved is False  # donated path chosen

    # Unknown stats (None) keeps the overlap, and the choice is sticky.
    step2 = TrainStep(ftmesh, optax.sgd(0.1), lambda p, b: loss_fn(p, b, CFG))
    monkeypatch.setattr(trainer_mod, "speculation_fits", lambda extra, dev, floor=0: None)
    opt_state2 = step2.init_opt_state(params)
    step2.ft_step(params, opt_state2, batch=_batch())
    assert step2._overlap_resolved is True


def test_ft_step_commit_gate() -> None:
    from datetime import timedelta

    import optax

    manager = create_autospec(Manager, instance=True)
    manager.num_participants.return_value = 2
    manager.timeout = timedelta(seconds=60)
    manager.allreduce.side_effect = lambda arr, should_average=True, **kw: completed_future(
        np.asarray(arr)
    )

    params = init_params(jax.random.PRNGKey(0), CFG)
    ftmesh = ft_init_mesh({"data": 2}, manager=manager)
    step = TrainStep(ftmesh, optax.sgd(0.1), lambda p, b: loss_fn(p, b, CFG))
    opt_state = step.init_opt_state(params)
    batch = _batch()

    manager.should_commit.return_value = False
    p0 = jax.tree.map(jnp.copy, params)
    params, opt_state, _, committed = step.ft_step(params, opt_state, batch)
    assert committed is False
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    manager.should_commit.return_value = True
    params, opt_state, _, committed = step.ft_step(params, opt_state, batch)
    assert committed is True
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p0))
    )
    assert changed
