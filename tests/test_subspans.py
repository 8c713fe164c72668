"""Sub-spans (obs/spans.SUBSPANS): buffered, written once with the step's
``step_summary``, never attributed, and recorded where the work happens —
the materializer thread, the ring's workers, the train thread."""

import dataclasses
import json
import re
import threading
import tracemalloc
from unittest.mock import MagicMock, create_autospec

import numpy as np
import pytest

from test_manager import make_manager, make_quorum, store  # noqa: F401

from torchft_tpu.metrics import EVENTS, MetricsLogger
from torchft_tpu.obs import report
from torchft_tpu.obs.spans import SUBSPANS, SpanTracker


class CountingFile:
    """Stands in for the logger's raw file: counts ``write()`` calls."""

    def __init__(self, raw):
        self.raw, self.writes = raw, 0

    def write(self, data):
        self.writes += 1
        return self.raw.write(data)

    def close(self):
        self.raw.close()


def counted(path):
    logger = MetricsLogger(str(path), replica_id="r0")
    logger._file = CountingFile(logger._file)
    return logger


def records(path, event=None):
    with open(path, encoding="utf-8") as f:
        recs = [json.loads(line) for line in f]
    return [r for r in recs if event is None or r["event"] == event]


def one_step(tracker, n_buckets, with_subs):
    """The spans of one step with ``n_buckets`` buckets, as the averager and
    the Manager open them; ``with_subs`` adds the sub-spans."""
    for k in range(n_buckets):
        with tracker.span("allreduce_d2h", step=3, bytes=64, bucket=k):
            if with_subs:
                with tracker.sub("d2h_ready", step=3, bucket=k):
                    pass
                with tracker.sub("d2h_fetch", step=3, bucket=k, bytes=64):
                    pass
                with tracker.sub("d2h_copy", step=3, bucket=k, bytes=64):
                    pass
        if with_subs:
            tracker.note_sub("ring_queue", 3, 10, 20, bucket=k, bytes=64)
            tracker.note_sub("ring_run", 3, 20, 50, bucket=k, bytes=64, wire_bytes=64)
    with tracker.span("allreduce_merge", step=3):
        pass
    with tracker.span("commit_vote", step=3):
        pass


@pytest.mark.parametrize("n_buckets", [1, 7, 40])
def test_subspans_add_no_write_per_span(tmp_path, n_buckets) -> None:
    """Whatever the number of buckets, a step with sub-spans makes exactly
    the writes it makes without them: they leave in the ``step_summary``'s
    own ``write()``, as the line after it."""
    writes = {}
    for with_subs in (False, True):
        path = tmp_path / f"m{int(with_subs)}.jsonl"
        logger = counted(path)
        tracker = SpanTracker(logger)
        one_step(tracker, n_buckets, with_subs)
        before = logger._file.writes
        tracker.step_summary(3, committed=True)
        assert logger._file.writes == before + 1
        writes[with_subs] = logger._file.writes
        logger.close()
    assert writes[True] == writes[False]
    recs = records(tmp_path / "m1.jsonl")
    assert [r["event"] for r in recs[-2:]] == ["step_summary", "subspan"]
    spans = recs[-1]["spans"]
    assert len(spans) == 5 * n_buckets
    assert {s["parent"] for s in spans} == {"allreduce_d2h", "exchange"}
    assert all(s["step"] == 3 and s["t1_ns"] >= s["t0_ns"] and s["thread"] for s in spans)
    assert records(tmp_path / "m0.jsonl", "subspan") == []
    # The next step's summary carries nothing over.
    tracker2 = SpanTracker(MetricsLogger(str(tmp_path / "m1.jsonl")))
    tracker2.step_summary(4, committed=True)
    assert len(records(tmp_path / "m1.jsonl", "subspan")) == 1


def test_span_record_gains_its_start_and_keeps_its_keys(tmp_path) -> None:
    path = tmp_path / "m.jsonl"
    tracker = SpanTracker(MetricsLogger(str(path)))
    with tracker.span("allreduce_d2h", step=1, bytes=8, bucket=2, pos=0):
        pass
    (rec,) = records(path, "span")
    assert {"phase", "step", "slice_gen", "duration_ms", "bytes", "bucket", "t_start_mono"} <= set(rec)
    assert (rec["bucket"], rec["pos"]) == (2, 0)
    assert 0 <= rec["t_mono"] - rec["t_start_mono"] < 1.0
    assert rec["t_start_mono"] + rec["duration_ms"] / 1e3 <= rec["t_mono"] + 1e-6


def test_subspans_leave_attribution_unchanged(tmp_path) -> None:
    """The same step with and without sub-spans: ``phases_ms`` and
    ``ft_accounted_ms`` see the same phases, the summaries name the same
    phases, and report.py's attribution of the two streams is the same."""
    seen = {}
    for with_subs in (False, True):
        path = tmp_path / f"a{int(with_subs)}.jsonl"
        logger = MetricsLogger(str(path), replica_id="r0")
        tracker = SpanTracker(logger)
        for step in (3, 4):
            one_step(tracker, 4, with_subs)
            phases = tracker.phases_ms()
            assert set(phases) == {"allreduce_d2h", "allreduce_merge", "commit_vote"}
            assert tracker.ft_accounted_ms() == pytest.approx(sum(phases.values()))
            logger.emit("commit", step=step, committed=True)
            tracker.step_summary(step, committed=True)
            assert tracker.phases_ms() == {}
        logger.close()
        events = report.read_events([str(path)])
        seen[with_subs] = {
            "summary_phases": [sorted(r["phases"]) for r in events if r["event"] == "step_summary"],
            "span_phases": [r["phase"] for r in events if r["event"] == "span"],
            "attribution_rows": [sorted(k for k in row if k.endswith("_s") or k == "critical")
                                 for row in report.attribute(events)["steps"]],
            "totals": sorted(report.attribute(events)["totals"]),
        }
    assert seen[True] == seen[False]


def test_no_metrics_path_keeps_nothing() -> None:
    tracker = SpanTracker(MetricsLogger(None))
    assert not tracker.enabled

    def many(n):
        for k in range(n):
            with tracker.sub("d2h_fetch", step=1, bucket=k, bytes=8):
                pass
            tracker.note_sub("ring_run", 1, 5, 9, bucket=k)

    many(50)  # warm: the lazy TraceAnnotation import, interned names
    tracemalloc.start()
    try:
        before, _ = tracemalloc.get_traced_memory()
        many(2000)
        after, _ = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert tracker._subs == []
    assert after - before < 4096  # nothing retained per sub-span
    tracker.flush_subspans()  # and nothing to write, nowhere to write it
    tracker.step_summary(1, committed=True)


def test_subspan_kind_is_registered_and_documented() -> None:
    """The record kind is in the registry under a literal ``emit("subspan"``
    call site (what test_every_emit_call_site_is_registered greps), and every
    sub-span a site opens has a parent in SUBSPANS."""
    import torchft_tpu
    import os

    assert "subspan" in EVENTS
    pkg = os.path.dirname(torchft_tpu.__file__)
    with open(os.path.join(pkg, "obs", "spans.py"), encoding="utf-8") as f:
        assert re.search(r"\.emit\(\s*\"subspan\"", f.read())
    opened = set()
    for rel in ("futures.py", "ddp.py", "manager.py", os.path.join("parallel", "trainer.py")):
        with open(os.path.join(pkg, rel), encoding="utf-8") as f:
            text = f.read()
        opened |= set(re.findall(r"\bsub\(\s*\"([a-z0-9_]+)\"", text))
        opened |= set(re.findall(r"note_sub\(\s*\"([a-z0-9_]+)\"", text))
    assert opened == set(SUBSPANS)


# ---------------------------------------------------------------------------
# Where the work happens: two groups over a real ring, in one process.
# ---------------------------------------------------------------------------


def test_two_group_ring_yields_the_buckets_sub_spans(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    """``GradientAverager.allreduce`` over a two-group TCP ring: per bucket
    the three fetch sub-spans inside its ``allreduce_d2h``, one queue+run
    pair with submitted <= started <= done, one ``normalize``, byte sums equal
    to ``last_stats``; written at the vote, the rest at shutdown."""
    import jax.numpy as jnp

    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.ddp import GradientAverager

    path = tmp_path / "ring.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    grads = {
        "a": jnp.arange(3000, dtype=jnp.float32),
        "b": jnp.ones((40, 50), jnp.float32),
        "c": jnp.full((1000,), 2.0, jnp.float32),
    }
    managers, stats, errors = {}, {}, []
    for rank in (0, 1):  # one after the other: make_manager patches module names
        client = MagicMock()
        client._quorum.return_value = dataclasses.replace(
            make_quorum(quorum_id=77, replica_rank=rank, max_replica_rank=rank),
            store_address=store.address(),
        )
        client.should_commit.return_value = True
        managers[rank], _, _ = make_manager(
            store, collective=TCPCollective(timeout=30.0), client_mock=client,
            replica_id=f"g{rank}", min_replica_size=2,
        )

    def group(rank: int) -> None:
        try:
            manager = managers[rank]
            manager.start_quorum()
            averager = GradientAverager(manager, bucket_bytes=8000)
            out = averager.allreduce(grads)
            stats[rank] = dict(averager.last_stats)
            np.testing.assert_allclose(np.asarray(out["c"]), 2.0)
            assert manager.should_commit()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=group, args=(r,)) for r in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    try:
        assert not errors, errors
        assert not any(t.is_alive() for t in threads)
        mine = [r for r in records(path) if r["replica_id"].startswith("g0")]
        (written,) = [r for r in mine if r["event"] == "subspan"]
        at = [r["event"] for r in mine]
        assert at.index("subspan") == at.index("step_summary") + 1
        subs = written["spans"]
        fetch_spans = {r["bucket"]: r for r in mine if r["event"] == "span" and r["phase"] == "allreduce_d2h"}
        n_buckets = stats[0]["buckets"]
        assert n_buckets == 3 and sorted(fetch_spans) == [0, 1, 2]
        # The stream's own field: a bucket's place in the fetch order
        # (largest first).
        by_pos = sorted(fetch_spans.values(), key=lambda r: r["pos"])
        assert [r["pos"] for r in by_pos] == [0, 1, 2]
        assert [r["bytes"] for r in by_pos] == sorted((r["bytes"] for r in by_pos), reverse=True)
        assert [r["t_start_mono"] for r in by_pos] == sorted(r["t_start_mono"] for r in by_pos)
        (summary,) = [r for r in mine if r["event"] == "step_summary"]
        stream = summary["exchange_stream"]
        assert stream["buckets"] == 3 and stream["tail_s"] >= 0
        assert 0 <= stream["early_puts"] == stats[0]["early_puts"] <= 2
        # The CPU backend's leaves are in host memory: the host's D2H lease is
        # never asked, its counters are there and read nothing.
        assert [stream[k] for k in ("lease_fetches", "lease_contended", "lease_timeouts", "lease_unavailable")] == [0] * 4
        assert not [s for s in subs if s["name"] == "d2h_lease_wait"]
        assert "allreduce_lanes" in summary
        for k in range(n_buckets):
            of = lambda name: [s for s in subs if s["name"] == name and s.get("bucket") == k]  # noqa: E731
            lo = fetch_spans[k]["t_start_mono"] * 1e9
            hi = lo + fetch_spans[k]["duration_ms"] * 1e6 + 2e3  # duration_ms is rounded to the microsecond
            for name in ("d2h_ready", "d2h_fetch", "d2h_copy"):
                (s,) = of(name)
                assert s["thread"].startswith("tpuft_materialize")
                assert lo - 2e3 <= s["t0_ns"] <= s["t1_ns"] <= hi
            (queue,), (run,), (norm,) = of("ring_queue"), of("ring_run"), of("normalize")
            assert queue["t0_ns"] <= queue["t1_ns"] == run["t0_ns"] <= run["t1_ns"] <= norm["t0_ns"]
            # On the thread that resolved the future: a ring worker, or the
            # caller itself where the op had ended before `then` attached.
            assert norm["thread"]
            assert sum(s["t1_ns"] - s["t0_ns"] for n in ("d2h_ready", "d2h_fetch", "d2h_copy") for s in of(n)) \
                <= fetch_spans[k]["duration_ms"] * 1e6 + 2e3
        by = lambda name, key: sum(s[key] for s in subs if s["name"] == name)  # noqa: E731
        assert by("d2h_fetch", "bytes") == by("d2h_copy", "bytes") == stats[0]["d2h_bytes"]
        assert by("ring_run", "wire_bytes") == stats[0]["wire_bytes"]
        assert by("normalize", "bytes") == stats[0]["d2h_bytes"]
        puts = [s for s in subs if s["name"] == "h2d_put"]
        assert sorted(s["bucket"] for s in puts if "bucket" in s) == [0, 1, 2]
        (final,) = [s for s in puts if "bucket" not in s]
        assert final["bytes"] == stats[0]["h2d_bytes"]
        # A sub-span recorded after the vote leaves at shutdown.
        managers[0].spans.note_sub("ring_run", 9, 1, 2, bucket=0)
    finally:
        for m in managers.values():
            m.shutdown()
    late = [r for r in records(path, "subspan") if r["replica_id"].startswith("g0")][-1]
    assert [s["step"] for s in late["spans"]] == [9]


def test_latched_error_flushes_what_led_up_to_it(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    path = tmp_path / "err.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    manager, _, _ = make_manager(store)
    try:
        manager.spans.note_sub("ring_run", 0, 1, 2, bucket=5)
        manager.report_error(RuntimeError("boom"))
        # (the process's program builds leave on the same flush, after them)
        kinds = [r["event"] for r in records(path) if r["event"] != "program_build"]
        assert kinds[-2:] == ["error", "subspan"]
        # The Manager's own start-up sub-span has waited in the buffer too.
        assert [(s["name"], s.get("bucket")) for s in records(path, "subspan")[0]["spans"]] == [
            ("manager_start", None), ("ring_run", 5)]
    finally:
        manager.shutdown()


def test_wait_quorum_records_only_a_real_wait(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    """A quorum still forming is waited for under ``quorum_wait``; one that
    has settled records nothing, so the waits of a step are never doubled."""
    path = tmp_path / "q.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    release = threading.Event()
    client = MagicMock()

    def slow_quorum(**_):
        release.wait(10)
        return make_quorum(max_world_size=2)

    client._quorum.side_effect = slow_quorum
    client.should_commit.return_value = True
    manager, _, _ = make_manager(store, client_mock=client)
    try:
        manager.start_quorum()
        threading.Timer(0.05, release.set).start()
        manager.wait_quorum()
        manager.wait_quorum()
        manager.allreduce(np.ones(4, np.float32)).result()
        assert manager.should_commit()
        waits = [s for r in records(path, "subspan") for s in r["spans"] if s["name"] == "quorum_wait"]
        assert len(waits) == 1 and waits[0]["t1_ns"] - waits[0]["t0_ns"] > 20e6
    finally:
        manager.shutdown()


# ---------------------------------------------------------------------------
# The frame of a step.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("overlap", [True, False])
def test_ft_step_frame_names_the_update_it_dispatched(tmp_path, overlap) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    path = tmp_path / "ft.jsonl"
    tracker = SpanTracker(MetricsLogger(str(path)))
    manager = create_autospec(Manager, instance=True)
    manager.spans = tracker
    manager.current_step.return_value = 11
    manager.errored.return_value = None
    manager.is_participating.return_value = True
    manager.collective.return_value.size.return_value = 1
    manager.should_commit.return_value = True
    ftmesh = ft_init_mesh({"data": 1}, devices=jax.devices()[:1])
    ftmesh.manager = manager
    step = TrainStep(ftmesh, optax.sgd(0.1), lambda p, b: jnp.sum((p["w"] * b) ** 2), overlap_commit=overlap)
    params = {"w": jnp.ones(8)}
    out = step.ft_step(params, step.init_opt_state(params), jnp.ones(8))
    assert out[3] is True
    tracker.flush_subspans()
    (rec,) = records(path, "subspan")
    by_name = {s["name"]: s for s in rec["spans"]}
    assert set(by_name) == {"ft_step", "grads_dispatch", "apply_dispatch"}
    frame = by_name["ft_step"]
    assert frame["speculative"] is overlap and frame["committed"] is True
    assert frame["parent"] is None and frame["step"] == 11
    for name in ("grads_dispatch", "apply_dispatch"):
        assert by_name[name]["parent"] == "ft_step"
        assert frame["t0_ns"] <= by_name[name]["t0_ns"] <= by_name[name]["t1_ns"] <= frame["t1_ns"]
