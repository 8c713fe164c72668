"""The streamed exchange (``GradientAverager._allreduce_streamed``): buckets
fetched largest first, one at a time and no copy started ahead, each ring op
issued as its bucket lands, and each resolved bucket sent home at once — bit
for bit what the monolithic ``pipelined=False`` path gives."""

from concurrent.futures import Future
from unittest.mock import MagicMock

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from test_manager import FakeCollective, make_manager, make_quorum, store  # noqa: F401
from test_subspans import records

from torchft_tpu.collectives import Work
from torchft_tpu.ddp import GradientAverager, plan_buckets

BUCKET_BYTES = 4000


class MirrorCollective(FakeCollective):
    """A ring of two whose peer holds the same bucket reversed: the sum is
    ``a + a[::-1]``, taken in the donated buffer itself like the native
    engine.  Ops whose index (in order of issue) is in ``hold`` resolve only
    once ``expect`` ops have been issued; those in ``fail`` fail then."""

    def __init__(self, wire_dtype=None, hold=(), fail=(), expect=0) -> None:
        super().__init__()
        self.wire_dtype = wire_dtype
        self.hold, self.fail, self.expect = set(hold) | set(fail), set(fail), expect
        self.issued = []  # nbytes, in order of issue
        self.held = []

    def allreduce(self, arrays, op="sum", allow_wire_compression=True, donate=False) -> Work:
        (a,) = arrays
        n = len(self.issued)
        self.issued.append(a.nbytes)
        out = a if donate else a.copy()
        np.add(out, a[::-1].copy(), out=out)
        fut: Future = Future()
        if n in self.hold:
            self.held.append((fut, RuntimeError("injected ring failure") if n in self.fail else [out]))
        else:
            fut.set_result([out])
        if len(self.issued) == self.expect:
            for held, outcome in self.held:
                if isinstance(outcome, Exception):
                    held.set_exception(outcome)
                else:
                    held.set_result(outcome)
        return Work(fut)


def small(shape, offset=0, dtype=np.float32):
    """Small whole numbers: exact in bfloat16 too, sums and halves included."""
    n = int(np.prod(shape)) if shape else 1
    return ((np.arange(n) + offset) % 64).astype(dtype).reshape(shape)


TREES = {
    "one-huge-leaf": lambda: {"w": jnp.asarray(small((9000,), 3)), "b": jnp.asarray(small((7,), 5))},
    "many-small-leaves": lambda: {f"l{i:02d}": jnp.asarray(small((50 + i,), i)) for i in range(40)},
    "scalar-leaves": lambda: {"w": jnp.asarray(small((3000,))), "s": jnp.asarray(np.float32(6.0)), "loss": 4.0},
    "mixed-dtypes": lambda: {
        "f32": jnp.asarray(small((1500,))), "i32": jnp.asarray(small((1200,), 1, np.int32)),
        "f16": jnp.asarray(small((900,), 2, np.float16)), "bf16": jnp.asarray(small((800,), 3, ml_dtypes.bfloat16)),
        "f32b": jnp.asarray(small((40, 50), 4)),
    },
    "host-leaves": lambda: {"a": small((3000,), 1), "b": small((40, 50), 2), "c": jnp.asarray(small((100,), 3))},
    "device-prep": lambda: {"w": jnp.asarray(small((2500,), 1)), "v": jnp.asarray(small((30, 40), 2)),
                            "s": jnp.asarray(np.float32(2.0))},
}


def a_manager(store, collective, tmp_path=None, monkeypatch=None):  # noqa: F811
    if tmp_path is not None:
        monkeypatch.setenv("TPUFT_METRICS_PATH", str(tmp_path / "m.jsonl"))
    client = MagicMock()
    client._quorum.return_value = make_quorum(max_world_size=2)
    client.should_commit.side_effect = lambda rank, step, vote, **kw: vote
    manager, _, _ = make_manager(store, collective=collective, client_mock=client)
    manager.start_quorum()
    return manager


def n_buckets(tree) -> int:
    leaves = [l if hasattr(l, "shape") else np.asarray(l) for l in jax.tree.leaves(tree)]
    return len(plan_buckets([(tuple(l.shape), l.dtype) for l in leaves], BUCKET_BYTES))


@pytest.mark.parametrize("name", list(TREES))
def test_streamed_equals_the_monolithic_path_bit_for_bit(store, name) -> None:  # noqa: F811
    prep = name == "device-prep"
    manager = a_manager(store, MirrorCollective(wire_dtype="bf16" if prep else None))
    try:
        tree = TREES[name]()
        streamed = GradientAverager(manager, BUCKET_BYTES, device_wire_prep=prep)
        got = streamed.allreduce(tree)
        want = GradientAverager(manager, BUCKET_BYTES, pipelined=False).allreduce(TREES[name]())
        assert manager.errored() is None
    finally:
        manager.shutdown()
    assert streamed.last_stats["device_buckets"] == (2 if prep else 0)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    changed = False
    for given, a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got), jax.tree.leaves(want)):
        assert isinstance(a, jax.Array) == isinstance(b, jax.Array) == isinstance(given, jax.Array)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        if isinstance(a, jax.Array):
            assert a.sharding == given.sharding
        changed |= np.asarray(a).tobytes() != np.asarray(given).tobytes()
    assert changed  # the mirror's sum is not the identity


@pytest.mark.parametrize("name", ["one-huge-leaf", "many-small-leaves", "mixed-dtypes"])
def test_fetch_order_is_descending_bytes_and_the_same_for_every_group(store, name) -> None:  # noqa: F811
    seen = []
    for _group in range(2):
        collective = MirrorCollective()
        manager = a_manager(store, collective)
        try:
            averager = GradientAverager(manager, BUCKET_BYTES)
            averager.allreduce(TREES[name]())
        finally:
            manager.shutdown()
        (plan,) = averager._plans.values()
        sizes = [b.nbytes for b in plan.buckets]
        assert plan.fetch_order == sorted(range(len(sizes)), key=lambda k: (-sizes[k], k))
        assert sorted(plan.fetch_order) == list(range(len(sizes))) and len(sizes) > 1
        # The ring saw the ops in that order: peers pair them by order of issue.
        assert collective.issued == [sizes[k] for k in plan.fetch_order]
        assert collective.issued == sorted(collective.issued, reverse=True)
        seen.append((plan.fetch_order, collective.issued))
    assert seen[0] == seen[1]


class HintedLeaf:
    """A leaf that counts ``copy_to_host_async`` hints (``hints``) and records
    the bytes of each fetch in the order they were made (``fetched``)."""

    def __init__(self, value: np.ndarray, state: dict) -> None:
        self.value, self.state = value, state
        self.shape, self.dtype, self.nbytes = value.shape, value.dtype, value.nbytes

    def copy_to_host_async(self) -> None:
        self.state["hints"] += 1

    def __array__(self, dtype=None, copy=None):
        self.state["fetched"].append(self.nbytes)
        return self.value


@pytest.mark.parametrize("n_leaves", [1, 2, 5, 12])
def test_the_stream_starts_no_copy_ahead(store, tmp_path, monkeypatch, n_leaves) -> None:  # noqa: F811
    """A second transfer on a host slows both (PERF.md section 6, PRs 28 and
    30): a fetch starts its own copy and no other is started beside it."""
    state = {"hints": 0, "fetched": []}
    values = [small((1100 + 10 * i,), i) for i in range(n_leaves)]  # one bucket each
    manager = a_manager(store, MirrorCollective(), tmp_path, monkeypatch)
    try:
        out = GradientAverager(manager, BUCKET_BYTES).allreduce([HintedLeaf(v, state) for v in values])
        assert manager.should_commit()
    finally:
        manager.shutdown()
    for v, o in zip(values, out):
        assert o.tobytes() == ((v + v[::-1]) / 2).astype(np.float32).tobytes()
    assert state["hints"] == 0
    assert state["fetched"] == sorted((v.nbytes for v in values), reverse=True)
    fetches = [r for r in records(tmp_path / "m.jsonl", "span") if r["phase"] == "allreduce_d2h"]
    assert [r["pos"] for r in fetches] == list(range(n_leaves))
    assert [r["bytes"] for r in fetches] == state["fetched"]


def stream_of(path):
    """(fetch spans, h2d spans, merge spans, sub-spans, exchange_stream) of one step's stream."""
    spans = records(path, "span")
    by = lambda phase: [r for r in spans if r["phase"] == phase]  # noqa: E731
    subs = [s for r in records(path, "subspan") for s in r["spans"]]
    (summary,) = records(path, "step_summary")
    return by("allreduce_d2h"), by("allreduce_h2d"), by("allreduce_merge"), subs, summary["exchange_stream"]


@pytest.mark.parametrize("early", ["first", "all", "none"])
def test_a_resolved_op_goes_home_before_the_last_fetch(store, tmp_path, monkeypatch, early) -> None:  # noqa: F811
    tree = TREES["many-small-leaves"]()
    n = n_buckets(tree)
    hold = {"first": range(1, n), "all": (), "none": range(n)}[early]
    manager = a_manager(store, MirrorCollective(hold=hold, expect=n), tmp_path, monkeypatch)
    try:
        averager = GradientAverager(manager, BUCKET_BYTES)
        got = averager.allreduce(tree)
        want = GradientAverager(manager, BUCKET_BYTES, pipelined=False).allreduce(tree)
        assert manager.should_commit()
    finally:
        manager.shutdown()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    fetches, h2ds, _merges, subs, stream = stream_of(tmp_path / "m.jsonl")
    fetches = [r for r in fetches if "pos" in r]  # the monolithic call's one fetch has none
    (plan,) = averager._plans.values()
    assert stream["buckets"] == n == len(fetches) and stream["tail_s"] >= 0
    assert stream["early_puts"] == averager.last_stats["early_puts"] == {"first": 1, "all": n - 1, "none": 0}[early]
    last_fetch_at = max(r["t_start_mono"] for r in fetches) * 1e9
    puts = {}  # the streamed call's, which came first
    for s in subs:
        if s["name"] == "h2d_put" and "bucket" in s:
            puts.setdefault(s["bucket"], s)
    went_early = [k for k in plan.fetch_order if puts[k]["t1_ns"] <= last_fetch_at]
    assert went_early == plan.fetch_order[: stream["early_puts"]]
    # A harvest lies between two fetches, in an allreduce_h2d span of its own.
    for k in went_early:
        assert any(h["t_start_mono"] * 1e9 - 2e3 <= puts[k]["t0_ns"]
                   and puts[k]["t1_ns"] <= (h["t_start_mono"] + h["duration_ms"] / 1e3) * 1e9 + 2e3 for h in h2ds)
        assert not any(f["t_start_mono"] * 1e9 < puts[k]["t1_ns"]
                       and puts[k]["t0_ns"] < (f["t_start_mono"] + f["duration_ms"] / 1e3) * 1e9 - 2e3 for f in fetches)


@pytest.mark.parametrize("failed", ["first", "middle", "last"])
def test_a_failed_bucket_keeps_its_leaves_and_the_rest_are_averaged(store, failed) -> None:  # noqa: F811
    tree = TREES["many-small-leaves"]()
    n = n_buckets(tree)
    at = {"first": 0, "middle": n // 2, "last": n - 1}[failed]
    manager = a_manager(store, MirrorCollective(fail=[at], expect=n))
    try:
        averager = GradientAverager(manager, BUCKET_BYTES)
        got = averager.allreduce(tree)
        assert "injected ring failure" in str(manager.errored())
        assert not manager.should_commit()
    finally:
        manager.shutdown()
    (plan,) = averager._plans.values()
    leaves, outs = jax.tree.leaves(tree), jax.tree.leaves(got)
    lost = plan.buckets[plan.fetch_order[at]]
    for k, bucket in enumerate(plan.buckets):
        flat = np.concatenate([np.asarray(leaves[i]).reshape(-1) for i in bucket.indices])
        mean = ((flat + flat[::-1]) / 2).astype(np.float32)
        for i, (_idx, want) in zip(bucket.indices, bucket.unpack(mean)):
            if bucket is lost:
                assert outs[i] is leaves[i]
            else:
                assert np.asarray(outs[i]).tobytes() == want.tobytes(), (k, i)


def test_the_fetch_spans_tie_holds_on_the_recorded_stream(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    """ready + fetch + copy <= the bucket's ``allreduce_d2h``, every ``h2d_put``
    lies inside an ``allreduce_h2d`` span, and none of those overlaps a fetch."""
    tree = TREES["mixed-dtypes"]()
    manager = a_manager(store, MirrorCollective(), tmp_path, monkeypatch)
    try:
        averager = GradientAverager(manager, BUCKET_BYTES)
        averager.allreduce(tree)
        assert manager.should_commit()
    finally:
        manager.shutdown()
    fetches, h2ds, merges, subs, stream = stream_of(tmp_path / "m.jsonl")
    edges = lambda r: (r["t_start_mono"] * 1e9, (r["t_start_mono"] + r["duration_ms"] / 1e3) * 1e9)  # noqa: E731
    assert len(fetches) == stream["buckets"] == averager.last_stats["buckets"]
    assert len(merges) == 1  # the commit's own drain: the mirror resolves at once, the stream waited for nothing
    for f in fetches:
        parts = [s for s in subs if s.get("bucket") == f["bucket"] and s["name"] in ("d2h_ready", "d2h_fetch", "d2h_copy")]
        assert sorted(s["name"] for s in parts) == ["d2h_copy", "d2h_fetch", "d2h_ready"]
        assert sum(s["t1_ns"] - s["t0_ns"] for s in parts) <= f["duration_ms"] * 1e6 + 2e3
        lo, hi = edges(f)
        assert all(lo - 2e3 <= s["t0_ns"] and s["t1_ns"] <= hi + 2e3 for s in parts)
        assert f["bytes"] == [s for s in parts if s["name"] == "d2h_fetch"][0]["bytes"]
    puts = [s for s in subs if s["name"] == "h2d_put"]
    assert sorted(s["bucket"] for s in puts if "bucket" in s) == list(range(stream["buckets"]))
    (final,) = [s for s in puts if "bucket" not in s]
    assert final["bytes"] == averager.last_stats["h2d_bytes"] == sum(h.get("bytes", 0) for h in h2ds)
    for s in puts:
        assert any(lo - 2e3 <= s["t0_ns"] and s["t1_ns"] <= hi + 2e3 for lo, hi in map(edges, h2ds))
    for h in h2ds:
        assert not any(edges(f)[0] < edges(h)[1] - 2e3 and edges(h)[0] < edges(f)[1] - 2e3 for f in fetches)


def test_the_lease_wait_lies_between_ready_and_fetch_of_its_leaf_and_is_counted(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    """Every leaf taken for an accelerator's (the CPU backend's own are not:
    tests/test_d2h_lease.py): each fetch asks the host's D2H lease between its
    ``d2h_ready`` and its ``d2h_fetch``, holds it for ``np.asarray`` alone,
    and ``exchange_stream`` carries the four counters."""
    from torchft_tpu import d2h_lease, futures

    turns = []

    class Lease(d2h_lease.D2HLease):
        def acquire(self, nbytes, max_wait_s):
            held = super().acquire(nbytes, max_wait_s)
            turns.append(("acquire", nbytes, held.outcome))
            return held

        def release(self, held):
            turns.append(("release", held.segment[1], held.outcome))
            super().release(held)

    monkeypatch.setattr(futures, "_fetch_is_d2h", lambda src: True)
    monkeypatch.setattr(d2h_lease, "_HOST_LEASE", Lease(str(tmp_path / "lease")))
    tree = TREES["mixed-dtypes"]()
    manager = a_manager(store, MirrorCollective(), tmp_path, monkeypatch)
    try:
        averager = GradientAverager(manager, BUCKET_BYTES)
        averager.allreduce(tree)
        assert manager.should_commit()
    finally:
        manager.shutdown()
    fetches, _h2ds, _merges, subs, stream = stream_of(tmp_path / "m.jsonl")
    n_leaves = len(jax.tree.leaves(tree))
    names = ["d2h_ready", "d2h_lease_wait", "d2h_fetch", "d2h_copy"]
    seen = 0
    for f in fetches:
        parts = sorted((s for s in subs if s.get("bucket") == f["bucket"] and s["name"] in names), key=lambda s: s["t0_ns"])
        assert [s["name"] for s in parts] == names * (len(parts) // 4) and parts
        assert all(a["t1_ns"] <= b["t0_ns"] for a, b in zip(parts, parts[1:]))
        assert sum(s["t1_ns"] - s["t0_ns"] for s in parts) <= f["duration_ms"] * 1e6 + 2e3
        for wait, fetch in zip(parts[1::4], parts[2::4]):
            assert wait["parent"] == "allreduce_d2h" and wait["thread"].startswith("tpuft_materialize")
            assert wait["bytes"] == fetch["bytes"] and wait["contended"] is False
        seen += len(parts) // 4
    assert seen == n_leaves
    # One at a time: released before the next is asked for (and before the copy).
    assert [t[0] for t in turns] == ["acquire", "release"] * n_leaves
    assert all(t[2] == "free" for t in turns) and sum(t[1] for t in turns[::2]) == averager.last_stats["d2h_bytes"]
    counters = {"lease_fetches": n_leaves, "lease_contended": 0, "lease_timeouts": 0, "lease_unavailable": 0}
    assert {k: stream[k] for k in counters} == counters == {k: averager.last_stats[k] for k in counters}
