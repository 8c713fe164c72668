"""The exchange's average is taken in the buffer the ring reduced
(``Manager.allreduce``'s ``normalize``): bit for bit what the allocating
expression gives, in place only where the input shows it may be, and a
failed op still tells itself apart by identity with the caller's input."""

import dataclasses
import itertools
import threading
from unittest.mock import MagicMock

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from test_manager import FakeCollective, make_manager, make_quorum, store  # noqa: F401
from test_subspans import records

from torchft_tpu.collectives import TCPCollective, Work
from torchft_tpu.futures import completed_future, failed_future

# The store is shared by the module and a ring's rendezvous keys carry the
# quorum id: every ring of this file gets its own.
_QUORUM_IDS = itertools.count(2600)

DTYPES = {
    "float32": np.float32,
    "float64": np.float64,
    "float16": np.float16,
    "bfloat16": ml_dtypes.bfloat16,
    "int32": np.int32,
}


def payload(rank: int, dtype, n: int = 10_007) -> np.ndarray:
    rng = np.random.default_rng(100 + rank)
    if np.issubdtype(dtype, np.integer):
        return rng.integers(-1000, 1000, n).astype(dtype)
    return (rng.standard_normal(n) * 100.0).astype(dtype)


def ring_of_two(store, collectives, **manager_kwargs):  # noqa: F811
    """Two Managers in this process over one TCP ring; the commit vote is
    this group's own."""
    quorum_id = next(_QUORUM_IDS)
    managers = {}
    for rank, collective in enumerate(collectives):  # in turn: make_manager patches module names
        client = MagicMock()
        client._quorum.return_value = dataclasses.replace(
            make_quorum(quorum_id=quorum_id, replica_rank=rank, max_replica_rank=rank),
            store_address=store.address(),
        )
        client.should_commit.side_effect = lambda rank, step, vote, **kw: vote
        managers[rank], _, _ = make_manager(
            store, collective=collective, client_mock=client,
            replica_id=f"g{rank}", min_replica_size=2, **manager_kwargs,
        )
    return managers


def in_two_threads(group):
    """``group(rank)`` for ranks 0 and 1 at once; their results by rank."""
    results, errors = {}, []

    def run(rank: int) -> None:
        try:
            results[rank] = group(rank)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(r,)) for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    return results


def normalize_records(path, replica: str):
    return [
        s
        for r in records(path, "subspan")
        if r["replica_id"].startswith(replica)
        for s in r["spans"]
        if s["name"] == "normalize"
    ]


@pytest.mark.parametrize("engine", ["native", "py"])
@pytest.mark.parametrize("donate", [True, False], ids=["donate", "keep"])
@pytest.mark.parametrize("dtype", list(DTYPES.values()), ids=list(DTYPES))
def test_average_is_bitwise_the_allocating_expression(
    store, tmp_path, monkeypatch, dtype, donate, engine  # noqa: F811
) -> None:
    """Over a real two-group ring, dividing by three: the average equals
    ``(sum / num).astype(dtype, copy=False)`` of the same ring's sum bit for
    bit; floating payloads divide in place (in the donated buffer itself
    where the native engine reduced it), an integer one does not."""
    path = tmp_path / "ring.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    managers = ring_of_two(
        store, [TCPCollective(timeout=30.0, engine=engine) for _ in range(2)]
    )

    def group(rank: int):
        manager = managers[rank]
        manager.start_quorum()
        manager.wait_quorum()
        monkeypatch.setattr(manager, "num_participants", lambda: 3)
        x = payload(rank, dtype)
        total = manager.allreduce(x.copy(), should_average=False, donate=donate).result()
        mine = x.copy()
        avg = manager.allreduce(mine, donate=donate).result()
        return x, np.array(total), mine, avg, manager.collective().ring_engine

    try:
        results = in_two_threads(group)
    finally:
        for m in managers.values():
            m.shutdown()
    x, total, mine, avg, ran = results[0]
    if ran != engine:
        pytest.skip(f"the {engine} ring engine is not available here")
    assert avg.dtype == total.dtype == np.dtype(dtype)
    assert avg.tobytes() == (total / 3).astype(dtype, copy=False).tobytes()
    assert avg.tobytes() == results[1][3].tobytes()
    floating = not np.issubdtype(dtype, np.integer)
    summed, averaged = normalize_records(path, "g0")
    assert summed["in_place"] is False and averaged["in_place"] is floating
    assert averaged["bytes"] == x.nbytes
    if not donate:
        assert mine.tobytes() == x.tobytes()
    elif engine == "native" and dtype is np.float32:
        assert np.shares_memory(avg, mine) and avg is not mine


class EchoCollective(FakeCollective):
    """A swapped-in collective that hands back the caller's own arrays, or
    read-only copies of them."""

    def __init__(self, readonly: bool) -> None:
        super().__init__()
        self.readonly = readonly

    def allreduce(self, arrays, op="sum", allow_wire_compression=True, donate=False) -> Work:
        outs = list(arrays)
        if self.readonly:
            outs = [a.copy() for a in outs]
            for a in outs:
                a.setflags(write=False)
        return Work(completed_future(outs))


@pytest.mark.parametrize(
    "readonly, donate, in_place",
    [(False, False, False), (True, False, False), (True, True, False), (False, True, True)],
    ids=["own-kept", "readonly-kept", "readonly-donated", "own-donated"],
)
def test_what_the_result_shows_decides(store, tmp_path, monkeypatch, readonly, donate, in_place) -> None:  # noqa: F811
    """The caller's own array that was not donated, and a read-only result,
    take the allocating expression and stay as they were; the caller's own
    array that was donated is divided in place and still is not, by identity,
    the input that a failed op resolves to."""
    path = tmp_path / "echo.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    client = MagicMock()
    client._quorum.return_value = make_quorum(max_world_size=2)
    manager, _, _ = make_manager(store, collective=EchoCollective(readonly), client_mock=client)
    try:
        manager.start_quorum()
        x = payload(0, np.float32)
        mine = x.copy()
        avg = manager.allreduce(mine, donate=donate).result()
    finally:
        manager.shutdown()
    assert avg.tobytes() == (x / 2).astype(np.float32, copy=False).tobytes()
    assert avg is not mine
    (record,) = normalize_records(path, "testrep")
    assert record["in_place"] is in_place
    if in_place:
        assert np.shares_memory(avg, mine)
    else:
        assert mine.tobytes() == x.tobytes() and not np.shares_memory(avg, mine)


class FlakyRing(TCPCollective):
    """A TCP ring whose allreduce fails before it touches the wire while
    ``failing`` is set (on every rank alike, so no peer is left waiting)."""

    failing = False

    def allreduce(self, arrays, *args, **kwargs) -> Work:
        if self.failing:
            return Work(failed_future(RuntimeError("injected ring failure")))
        return super().allreduce(arrays, *args, **kwargs)


def grads_of(rank: int, step: int, host_leaves: bool):
    place = np.asarray if host_leaves else jnp.asarray
    base = np.float32(10 * step + rank)
    return {
        "a": place(np.arange(3000, dtype=np.float32) + base),
        "b": place(np.full((40, 50), base, np.float32)),
        "n": np.full((7,), base, np.float32),  # a host leaf in every tree
    }


def mean_of(step: int, host_leaves: bool):
    trees = [grads_of(r, step, host_leaves) for r in (0, 1)]
    return {
        k: ((np.asarray(trees[0][k]) + np.asarray(trees[1][k])) / 2).astype(np.float32)
        for k in trees[0]
    }


def test_failed_ring_op_keeps_the_leaves_and_the_next_step_averages(store) -> None:  # noqa: F811
    """A two-group ``GradientAverager.allreduce`` whose ring ops fail returns
    the original leaves and latches the error; the following good step
    averages out of the same persistent buffers."""
    from torchft_tpu.ddp import GradientAverager

    rings = [FlakyRing(timeout=30.0) for _ in range(2)]
    managers = ring_of_two(store, rings)

    def group(rank: int):
        manager = managers[rank]
        averager = GradientAverager(manager, bucket_bytes=8000)

        manager.start_quorum()
        rings[rank].failing = True
        grads = grads_of(rank, 1, host_leaves=False)
        out = averager.allreduce(grads)
        rings[rank].failing = False
        assert "injected ring failure" in str(manager.errored())
        assert out["n"] is grads["n"]
        for k in grads:
            np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(grads[k]))
        assert not manager.should_commit()
        (plan,) = averager._plans.values()
        buffers = [id(b) for b in plan.buffers]

        manager.start_quorum()
        out = averager.allreduce(grads_of(rank, 2, host_leaves=False))
        assert manager.errored() is None
        assert [id(b) for b in next(iter(averager._plans.values())).buffers] == buffers
        assert manager.should_commit()
        return {k: np.asarray(v) for k, v in out.items()}

    try:
        results = in_two_threads(group)
    finally:
        for m in managers.values():
            m.shutdown()
    want = mean_of(2, host_leaves=False)
    for rank in (0, 1):
        for k in want:
            assert results[rank][k].tobytes() == want[k].tobytes(), (rank, k)


@pytest.mark.parametrize("host_leaves", [False, True], ids=["device-leaves", "host-leaves"])
def test_a_steps_leaves_outlive_the_next_steps_rewrite(store, host_leaves) -> None:  # noqa: F811
    """The averaged leaves of one step — device arrays, and host arrays where
    the caller gave host arrays — hold no view of the plan's persistent
    buffers: the next step rewrites those and the leaves stay what they were."""
    from torchft_tpu.ddp import GradientAverager

    managers = ring_of_two(store, [TCPCollective(timeout=30.0) for _ in range(2)])

    def group(rank: int):
        manager = managers[rank]
        averager = GradientAverager(manager, bucket_bytes=8000)
        outs = []
        for step in (1, 2):
            manager.start_quorum()
            outs.append(averager.allreduce(grads_of(rank, step, host_leaves)))
            assert manager.should_commit()
        (plan,) = averager._plans.values()
        for leaf in outs[0].values():
            # The CPU backend's device_put aliases a 64-byte-aligned source.
            at = leaf.ctypes.data if isinstance(leaf, np.ndarray) else leaf.unsafe_buffer_pointer()
            assert not any(b.ctypes.data <= at < b.ctypes.data + b.nbytes for b in plan.buffers)
        return [{k: np.array(v) for k, v in out.items()} for out in outs]

    try:
        results = in_two_threads(group)
    finally:
        for m in managers.values():
            m.shutdown()
    for step, out in zip((1, 2), results[0]):
        want = mean_of(step, host_leaves)
        for k in want:
            assert out[k].tobytes() == want[k].tobytes(), (step, k)
