"""Wrapper unit tests against a mocked Manager.

Reference parity: torchft/optim_test.py and torchft/local_sgd_test.py — the
Manager is replaced with an autospec mock to verify quorum/commit call
patterns and the sync arithmetic, without any real coordination servers.
"""

from typing import Any, List
from unittest.mock import MagicMock, create_autospec

import numpy as np
import pytest

from torchft_tpu.futures import completed_future
from torchft_tpu.manager import Manager


def _mock_manager(num_participants: int = 2, commit: bool = True) -> MagicMock:
    from datetime import timedelta

    manager = create_autospec(Manager, instance=True)
    manager.num_participants.return_value = num_participants
    manager.should_commit.return_value = commit
    manager._use_async_quorum = False
    manager.timeout = timedelta(seconds=60)

    def fake_allreduce(
        arr,
        should_average: bool = True,
        allow_wire_compression: bool = True,
        donate: bool = False,
        bucket=None,
    ):
        # Pretend every participant contributed identical values: the average
        # equals the input, so averaging is an identity we can verify around.
        # Copy on donate: the real manager never returns the donated buffer
        # itself on success (normalize hands back a view of it at most), and
        # callers use identity with the input to detect the failure fallback.
        out = np.asarray(arr)
        return completed_future(out.copy() if donate and out is arr else out)

    manager.allreduce.side_effect = fake_allreduce
    return manager


# -- Optimizer ---------------------------------------------------------------


def test_optimizer_step_commit() -> None:
    import optax

    manager = _mock_manager()
    from torchft_tpu.optim import Optimizer

    params = {"w": np.ones(4, dtype=np.float32)}
    opt = Optimizer(manager, optax.sgd(0.5), params)

    opt.step_begin()
    manager.start_quorum.assert_called_once()

    grads = {"w": np.full(4, 2.0, dtype=np.float32)}
    assert opt.step(grads) is True
    manager.should_commit.assert_called_once()
    np.testing.assert_allclose(np.asarray(opt.params["w"]), np.zeros(4))


def test_optimizer_step_skipped_on_failed_commit() -> None:
    import optax

    manager = _mock_manager(commit=False)
    from torchft_tpu.optim import Optimizer

    params = {"w": np.ones(4, dtype=np.float32)}
    opt = Optimizer(manager, optax.sgd(0.5), params)
    opt.step_begin()
    before = np.array(opt.params["w"], copy=True)
    assert opt.step({"w": np.full(4, 2.0, dtype=np.float32)}) is False
    np.testing.assert_array_equal(np.asarray(opt.params["w"]), before)


# -- GradientAverager --------------------------------------------------------


def test_gradient_averager_roundtrip() -> None:
    from torchft_tpu.ddp import GradientAverager

    manager = _mock_manager()
    avg = GradientAverager(manager, bucket_bytes=64)
    grads = {
        "a": np.arange(6, dtype=np.float32).reshape(2, 3),
        "b": np.full((5,), 3.0, dtype=np.float32),
        "c": np.ones((16, 4), dtype=np.float32),
    }
    out = avg.allreduce(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(out[k]), grads[k])
    # Small bucket size must have split the leaves into multiple allreduces.
    assert manager.allreduce.call_count >= 2


def test_donated_buffer_failure_leaves_grads_intact() -> None:
    """The caller-side pin of the donate contract: the wire stage donates
    its staging buffer, so a latched collective failure — which resolves
    the future to that SAME buffer, possibly half-reduced by the op —
    must never be scattered back as gradients.  The original leaves come
    home untouched and the commit vote fails; only a successful op's
    freshly allocated result is unpacked."""
    from torchft_tpu.ddp import GradientAverager

    manager = _mock_manager()
    seen = {}

    def failing_allreduce(
        arr,
        should_average: bool = True,
        allow_wire_compression: bool = True,
        donate: bool = False,
        bucket=None,
    ):
        seen["donate"] = donate
        buf = np.asarray(arr)
        # The op owned the donated buffer and got partway through the
        # reduction before a peer died: the bytes are garbage now.
        buf[:] = 12345.0
        # Latched-failure fallback: the future resolves to the input
        # buffer ITSELF (wrap_future's default), which is how the
        # scatter-back detects failure.
        return completed_future(buf)

    manager.allreduce.side_effect = failing_allreduce
    avg = GradientAverager(manager, bucket_bytes=1 << 20)
    grads = {
        "a": np.arange(6, dtype=np.float32),
        "b": np.full((5,), 3.0, dtype=np.float32),
    }
    before = {k: v.copy() for k, v in grads.items()}
    out = avg.allreduce(grads)
    assert seen["donate"] is True, "wire stage no longer donates"
    for k in grads:
        np.testing.assert_array_equal(np.asarray(out[k]), before[k])
        np.testing.assert_array_equal(grads[k], before[k])


def test_gradient_averager_buckets_respect_dtype() -> None:
    from torchft_tpu.ddp import GradientAverager

    manager = _mock_manager()
    avg = GradientAverager(manager, bucket_bytes=1 << 20)
    grads = {
        "f32": np.ones(4, dtype=np.float32),
        "f16": np.ones(4, dtype=np.float16),
    }
    out = avg.allreduce(grads)
    assert out["f32"].dtype == np.float32
    assert out["f16"].dtype == np.float16
    assert manager.allreduce.call_count == 2  # dtype change forces a new bucket


def test_plan_buckets_groups_alternating_dtypes() -> None:
    """A tree whose leaf dtypes ALTERNATE (f64, i32, f64, i32, ...) must
    pack into one bucket per dtype, not one per leaf — the planner
    sort-stable groups by dtype before packing, preserving the original
    index mapping."""
    from torchft_tpu.ddp import plan_buckets

    metas = []
    for i in range(8):
        metas.append(((16,), np.float64) if i % 2 == 0 else ((16,), np.int32))
    buckets = plan_buckets(metas, bucket_bytes=1 << 20)

    assert len(buckets) == 2
    by_dtype = {b.dtype: b for b in buckets}
    assert set(by_dtype) == {np.dtype(np.float64), np.dtype(np.int32)}
    # Index mapping preserved, stable within each dtype run.
    assert by_dtype[np.dtype(np.float64)].indices == [0, 2, 4, 6]
    assert by_dtype[np.dtype(np.int32)].indices == [1, 3, 5, 7]
    # Byte bounds: each bucket is exactly its leaves' bytes, under the cap.
    assert by_dtype[np.dtype(np.float64)].nbytes == 4 * 16 * 8
    assert by_dtype[np.dtype(np.int32)].nbytes == 4 * 16 * 4
    assert all(b.nbytes <= 1 << 20 for b in buckets)
    # Every original leaf lands in exactly one bucket.
    assert sorted(i for b in buckets for i in b.indices) == list(range(8))


def test_plan_buckets_byte_cap_and_edges() -> None:
    from torchft_tpu.ddp import plan_buckets

    # 0 leaves -> no buckets.
    assert plan_buckets([], bucket_bytes=1 << 20) == []

    # Same-dtype leaves split on the byte cap: 6 x 40-byte f32 leaves at a
    # 100-byte cap -> ceil(240/80)=3 buckets of <=2 leaves, order kept.
    metas = [((10,), np.float32)] * 6
    buckets = plan_buckets(metas, bucket_bytes=100)
    assert [b.indices for b in buckets] == [[0, 1], [2, 3], [4, 5]]
    assert all(b.nbytes <= 100 for b in buckets)

    # A single giant leaf (> bucket_bytes) gets its own bucket, whole.
    metas = [((4,), np.float32), ((1000,), np.float32), ((4,), np.float32)]
    buckets = plan_buckets(metas, bucket_bytes=256)
    giant = next(b for b in buckets if 1 in b.indices)
    assert giant.indices == [1] and giant.nbytes == 4000
    assert sorted(i for b in buckets for i in b.indices) == [0, 1, 2]

    # Scalar (0-d) leaves count as one element, not zero.
    buckets = plan_buckets([((), np.float32)], bucket_bytes=64)
    assert len(buckets) == 1 and buckets[0].numel == 1


def test_gradient_averager_mixed_dtype_roundtrip_and_plan_cache() -> None:
    """Alternating-dtype grads coalesce into 2 allreduces per step (not one
    per leaf), values round-trip through the persistent buffers, and the
    plan is cached: a second step with the same tree signature reuses the
    same flat buffers (zero per-step allocation on the packing side)."""
    from torchft_tpu.ddp import GradientAverager

    manager = _mock_manager()
    avg = GradientAverager(manager, bucket_bytes=1 << 20)
    grads = {}
    for i in range(6):
        if i % 2 == 0:
            grads[f"l{i}"] = np.arange(i + 3, dtype=np.float64)
        else:
            grads[f"l{i}"] = np.full((2, i + 1), i, dtype=np.int32)

    out = avg.allreduce(grads)
    assert manager.allreduce.call_count == 2  # one bucket per dtype
    for k, v in grads.items():
        np.testing.assert_array_equal(np.asarray(out[k]), v)
        assert out[k].dtype == v.dtype

    buffers_before = [id(b) for b in avg._plans[next(iter(avg._plans))].buffers]
    out2 = avg.allreduce(grads)
    assert len(avg._plans) == 1  # same signature -> cached plan
    buffers_after = [id(b) for b in avg._plans[next(iter(avg._plans))].buffers]
    assert buffers_before == buffers_after  # persistent, reused buffers
    for k, v in grads.items():
        np.testing.assert_array_equal(np.asarray(out2[k]), v)


def test_per_leaf_averager() -> None:
    from torchft_tpu.ddp import PerLeafGradientAverager

    manager = _mock_manager()
    out = PerLeafGradientAverager(manager).allreduce(
        {"a": np.ones(3, dtype=np.float32), "b": np.zeros(2, dtype=np.float32)}
    )
    assert manager.allreduce.call_count == 2
    np.testing.assert_array_equal(np.asarray(out["a"]), np.ones(3))


def test_gradient_averager_jax_arrays() -> None:
    import jax.numpy as jnp

    from torchft_tpu.ddp import GradientAverager

    manager = _mock_manager()
    grads = {"w": jnp.arange(8, dtype=jnp.float32)}
    out = GradientAverager(manager).allreduce(grads)
    import jax

    assert isinstance(out["w"], jax.Array)
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(8))


# -- DistributedSampler ------------------------------------------------------


def test_sampler_partition_disjoint_and_complete() -> None:
    from torchft_tpu.data import DistributedSampler

    n, groups, ranks = 64, 2, 2
    seen: List[int] = []
    for g in range(groups):
        for r in range(ranks):
            s = DistributedSampler(
                n, replica_group=g, num_replica_groups=groups, rank=r,
                num_replicas=ranks, shuffle=False,
            )
            idx = list(s)
            assert len(idx) == n // (groups * ranks)
            seen.extend(idx)
    assert sorted(seen) == list(range(n))


def test_sampler_global_rank_composition() -> None:
    from torchft_tpu.data import DistributedSampler

    # rank + num_replicas * replica_group (torchft/data.py:62-67)
    s = DistributedSampler(16, replica_group=1, num_replica_groups=2, rank=1,
                           num_replicas=2, shuffle=False)
    assert s.global_rank == 3
    assert s.global_world_size == 4
    assert list(s) == [3, 7, 11, 15]


def test_sampler_drop_last_equal_shards() -> None:
    from torchft_tpu.data import DistributedSampler

    # 10 samples over 4 shards: every shard must match __len__ (2), or
    # lockstep replicas desync at the ragged tail.
    lens = set()
    for g in range(2):
        for r in range(2):
            s = DistributedSampler(10, g, 2, rank=r, num_replicas=2, shuffle=False)
            idx = list(s)
            assert len(idx) == len(s)
            lens.add(len(idx))
    assert lens == {2}


def test_sampler_shuffle_deterministic_per_epoch() -> None:
    from torchft_tpu.data import DistributedSampler

    s = DistributedSampler(32, 0, 2, shuffle=True, seed=7)
    s.set_epoch(0)
    a = list(s)
    s.set_epoch(0)
    assert list(s) == a
    s.set_epoch(1)
    assert list(s) != a


def test_stateful_loader_resumes_mid_epoch() -> None:
    """StatefulDataLoader parity with the reference's torchdata loader: a
    restarted worker resumes at the exact batch, not the epoch start."""
    from torchft_tpu.data import DistributedSampler, StatefulDataLoader

    def fresh():
        return StatefulDataLoader(
            DistributedSampler(64, 0, 2, shuffle=True, seed=3),
            batch_size=4,
        )

    # The uninterrupted stream over 1.5 epochs.
    ref_loader = fresh()
    ref = [b.tolist() for _ in range(2) for b in ref_loader]

    # Interrupt after 5 batches; a fresh loader restores the state dict and
    # must continue the stream identically.
    loader = fresh()
    got = []
    it = iter(loader)
    for _ in range(5):
        got.append(next(it).tolist())
    state = loader.state_dict()

    resumed = fresh()
    resumed.load_state_dict(state)
    for _ in range(2):
        for b in resumed:
            got.append(b.tolist())
    assert got == ref

    # Epoch rollover state round-trips too.
    assert resumed.state_dict()["batches_yielded"] == 0


def test_stateful_loader_epoch_boundary_state() -> None:
    """A state saved right after an epoch's LAST batch (before the
    iterator's epilogue) must restore to the next epoch, not an empty
    pass."""
    from torchft_tpu.data import DistributedSampler, StatefulDataLoader

    def fresh():
        return StatefulDataLoader(
            DistributedSampler(16, 0, 2, shuffle=True, seed=1), batch_size=4
        )

    loader = fresh()
    it = iter(loader)
    for _ in range(2):  # 8-sample shard / batch 4 = exactly 2 batches
        next(it)
    state = loader.state_dict()  # one-past-the-end of epoch 0

    resumed = fresh()
    resumed.load_state_dict(state)
    epoch1 = [b.tolist() for b in resumed]
    assert len(epoch1) == 2  # a full real epoch, not zero batches

    ref = fresh()
    ref_stream = [b.tolist() for _ in range(2) for b in ref]
    assert epoch1 == ref_stream[2:]  # identical to the uninterrupted epoch 1


def test_stateful_loader_rejects_second_live_iterator() -> None:
    from torchft_tpu.data import DistributedSampler, StatefulDataLoader
    import pytest as _pytest

    loader = StatefulDataLoader(
        DistributedSampler(32, 0, 2, shuffle=False), batch_size=4
    )
    it1 = iter(loader)
    next(it1)
    it2 = iter(loader)
    next(it2)
    with _pytest.raises(RuntimeError, match="newer iterator"):
        next(it1)


# -- LocalSGD ----------------------------------------------------------------


class _ParamBox:
    def __init__(self, params: Any) -> None:
        self.params = params

    def get(self) -> Any:
        return self.params

    def set(self, p: Any) -> None:
        self.params = p


def test_local_sgd_syncs_every_n(monkeypatch) -> None:
    from torchft_tpu.local_sgd import LocalSGD

    manager = _mock_manager()
    box = _ParamBox({"w": np.ones(4, dtype=np.float32)})
    with LocalSGD(manager, box.get, box.set, sync_every=2) as lsgd:
        lsgd.step()
        manager.start_quorum.assert_not_called()
        lsgd.step()
        manager.start_quorum.assert_called_once()
        manager.should_commit.assert_called_once()


def test_local_sgd_commit_gates_copyback() -> None:
    from torchft_tpu.local_sgd import LocalSGD

    manager = _mock_manager(commit=False)

    def fake_allreduce(
        arr, should_average=True, allow_wire_compression=True, donate=False,
        bucket=None,
    ):
        return completed_future(np.zeros_like(np.asarray(arr)))

    manager.allreduce.side_effect = fake_allreduce
    box = _ParamBox({"w": np.ones(4, dtype=np.float32)})
    with LocalSGD(manager, box.get, box.set, sync_every=1) as lsgd:
        lsgd.step()
    # Failed commit: params untouched even though allreduce returned zeros.
    np.testing.assert_array_equal(np.asarray(box.params["w"]), np.ones(4))


# -- DiLoCo ------------------------------------------------------------------


def test_diloco_requires_sync_quorum() -> None:
    import optax

    from torchft_tpu.local_sgd import DiLoCo

    manager = _mock_manager()
    manager._use_async_quorum = True
    box = _ParamBox({"w": np.ones(2, dtype=np.float32)})
    with pytest.raises(ValueError, match="synchronous quorum"):
        DiLoCo(manager, box.get, box.set, optax.sgd(0.5), sync_every=1)


def test_diloco_outer_step_moves_toward_local_progress() -> None:
    import optax

    from torchft_tpu.local_sgd import DiLoCo

    manager = _mock_manager()
    box = _ParamBox({"w": np.zeros(2, dtype=np.float32)})
    diloco = DiLoCo(manager, box.get, box.set, optax.sgd(1.0), sync_every=1)

    # Inner training moved w to 1.0; pseudograd = backup - local = -1.
    box.set({"w": np.ones(2, dtype=np.float32)})
    diloco.step()
    # Outer SGD lr=1: backup <- backup - 1 * (-1) = 1 == local progress.
    np.testing.assert_allclose(np.asarray(box.params["w"]), np.ones(2))


def test_diloco_failed_commit_restores_backup() -> None:
    import optax

    from torchft_tpu.local_sgd import DiLoCo

    manager = _mock_manager(commit=False)
    box = _ParamBox({"w": np.zeros(2, dtype=np.float32)})
    diloco = DiLoCo(manager, box.get, box.set, optax.sgd(1.0), sync_every=1)
    box.set({"w": np.ones(2, dtype=np.float32)})
    diloco.step()
    # Commit failed: local divergence rolled back to the backup.
    np.testing.assert_array_equal(np.asarray(box.params["w"]), np.zeros(2))


def test_diloco_sync_counts_reset() -> None:
    import optax

    from torchft_tpu.local_sgd import DiLoCo

    manager = _mock_manager()
    box = _ParamBox({"w": np.zeros(2, dtype=np.float32)})
    diloco = DiLoCo(manager, box.get, box.set, optax.sgd(0.5), sync_every=3)
    for _ in range(3):
        diloco.step()
    assert manager.start_quorum.call_count == 1
    for _ in range(3):
        diloco.step()
    assert manager.start_quorum.call_count == 2
