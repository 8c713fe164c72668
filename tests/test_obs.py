"""Observability subsystem tests: span tracing (obs/spans.py), goodput
attribution (obs/report.py), the metrics event registry, and the
lighthouse's Prometheus ``GET /metrics`` exposition scraped during a
kill-and-heal run.
"""

import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import pytest

from torchft_tpu.metrics import EVENTS, MetricsLogger
from torchft_tpu.obs import report
from torchft_tpu.obs.spans import PHASES, SpanTracker

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


def test_span_tracker_emits_spans_and_summary(tmp_path) -> None:
    path = tmp_path / "spans.jsonl"
    tracker = SpanTracker(MetricsLogger(str(path), replica_id="r0"), slice_gen=3)
    with tracker.span("quorum", step=7) as sp:
        time.sleep(0.01)
    assert sp.duration_ms >= 5
    with tracker.span("commit_vote", step=7, extra="x"):
        pass
    tracker.step_summary(7, committed=True)
    events = [json.loads(l) for l in path.read_text().splitlines()]
    spans = [e for e in events if e["event"] == "span"]
    assert [s["phase"] for s in spans] == ["quorum", "commit_vote"]
    assert all(s["step"] == 7 and s["slice_gen"] == 3 for s in spans)
    assert spans[1]["extra"] == "x"
    summary = events[-1]
    assert summary["event"] == "step_summary" and summary["committed"] is True
    assert set(summary["phases"]) == {"quorum", "commit_vote"}
    assert summary["accounted_ms"] == pytest.approx(
        sum(s["duration_ms"] for s in spans), abs=0.01
    )
    # The accumulator reset: a second summary carries only new phases.
    with tracker.span("heal", step=8):
        pass
    tracker.step_summary(8, committed=False)
    events = [json.loads(l) for l in path.read_text().splitlines()]
    assert set(events[-1]["phases"]) == {"heal"}


def test_span_records_failure(tmp_path) -> None:
    """A phase that raises still lands in the trace, marked ok: false —
    a hung-then-failed quorum must show its real duration."""
    path = tmp_path / "spans.jsonl"
    tracker = SpanTracker(MetricsLogger(str(path)), slice_gen=0)
    with pytest.raises(RuntimeError):
        with tracker.span("quorum", step=1):
            raise RuntimeError("boom")
    ev = json.loads(path.read_text().splitlines()[-1])
    assert ev["event"] == "span" and ev["ok"] is False
    assert ev["duration_ms"] >= 0


def test_phases_registry_is_stable() -> None:
    """report.py buckets and the Manager call sites key off these names."""
    assert PHASES == (
        "quorum",
        "configure",
        "heal",
        "ec_reconstruct",
        "allreduce_d2h",
        "allreduce_h2d",
        "allreduce_merge",
        "commit_vote",
        "snapshot",
        "ec_encode",
        "outer_sync",
    )
    from torchft_tpu.obs.spans import OVERLAPPED_PHASES

    # Overlapped phases must be a subset of the registry: report.py treats
    # them as concurrent-with-compute (not charged against productive time).
    assert set(OVERLAPPED_PHASES) <= set(PHASES)
    assert OVERLAPPED_PHASES == ("snapshot", "ec_encode", "outer_sync")


# ---------------------------------------------------------------------------
# Event registry static check
# ---------------------------------------------------------------------------


def test_every_emit_call_site_is_registered() -> None:
    """Greps every ``.emit("name", ...)`` call site in the package against
    metrics.EVENTS so a new event cannot ship undocumented.
    Registered-but-unused names are allowed (consumers may predate their
    producers during a refactor)."""
    roots = [os.path.join(REPO, "torchft_tpu")]
    pat = re.compile(r"\.emit\(\s*\n?\s*\"([a-zA-Z0-9_]+)\"")
    emitted = {}
    for root in roots:
        files = []
        if os.path.isfile(root):
            files = [root]
        else:
            for dirpath, _, names in os.walk(root):
                files += [
                    os.path.join(dirpath, n) for n in names if n.endswith(".py")
                ]
        for f in files:
            with open(f, "r", encoding="utf-8") as fh:
                for name in pat.findall(fh.read()):
                    emitted.setdefault(name, []).append(os.path.relpath(f, REPO))
    assert emitted, "grep found no emit() call sites — pattern rot?"
    unregistered = {n: fs for n, fs in emitted.items() if n not in EVENTS}
    assert not unregistered, (
        f"emit() call sites using event names missing from "
        f"torchft_tpu.metrics.EVENTS: {unregistered}"
    )


# ---------------------------------------------------------------------------
# Report: attribution + CLI
# ---------------------------------------------------------------------------


def _write_jsonl(path, events) -> str:
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    return str(path)


def _synthetic_stream():
    """Two replicas, three committed steps; replica B pays a 2 s heal on
    step 2 and a long quorum wait on step 3 (1.0 s of compute per step)."""
    events = []
    for rid, start in (("0:a", 0.0), ("1:b", 0.1)):
        mono = 100.0  # distinct per-process monotonic origin
        ts = start
        for step in (1, 2, 3):
            heal_ms = 2000.0 if rid == "1:b" and step == 2 else 0.0
            quorum_ms = 600.0 if rid == "1:b" and step == 3 else 50.0
            wall = 1.0 + (heal_ms + quorum_ms) / 1e3
            mono += wall
            ts += wall
            events.append(
                {
                    "ts": ts,
                    "t_mono": mono,
                    "replica_id": rid,
                    "event": "commit",
                    "step": step,
                    "committed": True,
                    "vote_ms": 5.0,
                }
            )
            phases = {"quorum": quorum_ms, "commit_vote": 5.0}
            if heal_ms:
                phases["heal"] = heal_ms
            events.append(
                {
                    "ts": ts + 0.001,
                    "replica_id": rid,
                    "event": "step_summary",
                    "step": step,
                    "committed": True,
                    "phases": phases,
                }
            )
    return events


def test_attribute_builds_per_step_table(tmp_path) -> None:
    events = _synthetic_stream()
    result = report.attribute(events)
    rows = {r["step"]: r for r in result["steps"]}
    # Step 1 of each replica is the first commit — no interval yet; steps
    # 2 and 3 attribute.
    assert set(rows) == {2, 3}
    # Step 2's slowest replica is 1:b (heal-dominated).
    assert rows[2]["heal_s"] == pytest.approx(2.0, abs=0.05)
    assert rows[2]["critical"] == "heal"
    # Step 3's slowest replica is 1:b again, quorum-wait-dominated... but
    # productive time (1.0 s compute) still exceeds the 0.6 s wait.
    assert rows[3]["quorum_wait_s"] == pytest.approx(0.6, abs=0.05)
    assert rows[3]["critical"] == "productive"
    totals = result["totals"]
    assert totals["heal_s"] == pytest.approx(2.0, abs=0.05)
    assert totals["productive_s"] > 0
    fr = result["fractions"]
    assert fr["heal_fraction"] is not None and 0 < fr["heal_fraction"] < 1


def test_attribute_merges_retried_step_summaries() -> None:
    """A failed-then-retried commit vote summarizes the same step twice;
    the committed interval spans both attempts, so their phases must ADD
    — replacing would misattribute the first attempt's quorum wait as
    productive time."""
    events = [
        {"ts": 1.0, "t_mono": 1.0, "replica_id": "0:a", "event": "commit",
         "step": 1, "committed": True},
        {"ts": 1.1, "replica_id": "0:a", "event": "step_summary", "step": 2,
         "committed": False, "phases": {"quorum": 5000.0}},
        {"ts": 8.0, "replica_id": "0:a", "event": "step_summary", "step": 2,
         "committed": True, "phases": {"quorum": 100.0, "commit_vote": 5.0}},
        {"ts": 8.1, "t_mono": 8.1, "replica_id": "0:a", "event": "commit",
         "step": 2, "committed": True},
        # A second group so t0/t_end cover the window.
        {"ts": 1.0, "t_mono": 1.0, "replica_id": "1:b", "event": "commit",
         "step": 1, "committed": True},
        {"ts": 8.0, "t_mono": 8.0, "replica_id": "1:b", "event": "commit",
         "step": 2, "committed": True},
    ]
    result = report.attribute(events)
    row = next(r for r in result["steps"] if r["step"] == 2)
    assert row["quorum_wait_s"] == pytest.approx(5.1, abs=0.01)


def test_attribute_charges_allreduce_d2h_as_ft_not_productive() -> None:
    """The bucket pipeline's per-bucket device->host wait (allreduce_d2h)
    blocks the train thread: it must land in other_ft_s, carved OUT of
    productive time — never treated like the overlapped snapshot phase.
    Goodput accounting would otherwise report the D2H stall as compute."""
    events = [
        {"ts": 1.0, "t_mono": 1.0, "replica_id": "0:a", "event": "commit",
         "step": 1, "committed": True},
        {"ts": 4.0, "replica_id": "0:a", "event": "step_summary", "step": 2,
         "committed": True,
         "phases": {"allreduce_d2h": 1200.0, "allreduce_merge": 300.0,
                    "commit_vote": 5.0, "snapshot": 900.0}},
        {"ts": 4.0, "t_mono": 4.0, "replica_id": "0:a", "event": "commit",
         "step": 2, "committed": True},
        # A second group so t0/t_end cover the window.
        {"ts": 1.0, "t_mono": 1.0, "replica_id": "1:b", "event": "commit",
         "step": 1, "committed": True},
        {"ts": 4.0, "t_mono": 4.0, "replica_id": "1:b", "event": "commit",
         "step": 2, "committed": True},
    ]
    result = report.attribute(events)
    row = next(r for r in result["steps"] if r["step"] == 2)
    # d2h + merge + vote = 1.505 s of the 3 s wall is FT overhead...
    assert row["other_ft_s"] == pytest.approx(1.505, abs=0.01)
    assert row["productive_s"] == pytest.approx(3.0 - 1.505, abs=0.01)
    # ...while the overlapped snapshot is reported but never charged.
    assert row["snapshot_overlap_s"] == pytest.approx(0.9, abs=0.01)
    assert result["totals"]["other_ft_s"] == pytest.approx(1.505, abs=0.01)


def _commits(rid: str, times) -> list:
    return [
        {"ts": float(t), "replica_id": rid, "event": "commit", "committed": True}
        for t in times
    ]


def _fault(ts: float, group: str, kind: str = "kill", **extra) -> dict:
    return {"ts": ts, "replica_id": "driver", "event": "fault", "kind": kind,
            "group": group, **extra}


# Recorded streams and what the dead-window accounting charges for each:
# (events, {"dead_time_s", "fraction", "victims_recovered"}, commits per group).
# Group 0 commits every second from 1 to 40 throughout, so the window is
# [1, 40], span 39 s, and the victim's median step is 1 s.
_SURVIVOR = _commits("0:a", range(1, 41))
DEADWINDOW_STREAMS = {
    # Incarnation A commits 1..10 and is killed at 10.5; B's first event (a
    # quorum) is at 17.5, its heal lands at 17.9 and it commits 18..40.  The
    # one kill-containing gap (10, 18) is 8 s, charged less one median step.
    "single_kill": [(
        _SURVIVOR + _commits("1:A", range(1, 11))
        + [{"ts": 17.5, "replica_id": "1:B", "event": "quorum"},
           {"ts": 17.9, "replica_id": "1:B", "event": "heal_fetched", "heal_ms": 150.0}]
        + _commits("1:B", range(18, 41)) + [_fault(10.5, "1")],
        {"dead_time_s": 7.0, "fraction": 1 - 7.0 / 39.0, "victims_recovered": True},
        {"0": 40, "1": 33},
    )],
    # A drain notice at 10.5: the donor COMMITS THROUGH 13 (that is the point
    # of a drain) and the replacement's first commit is at 15.  The gap that
    # holds the notice, (10, 11), is one ordinary step: nothing is charged.
    # A survivor's failed commit (5.5) is no commit and changes nothing.
    "drain": [(
        [{"ts": 5.5, "replica_id": "0:a", "event": "commit", "committed": False}]
        + _SURVIVOR + _commits("1:A", range(1, 14)) + _commits("1:B", range(15, 41))
        + [_fault(10.5, "1", kind="drain")],
        {"dead_time_s": 0.0, "fraction": 1.0, "victims_recovered": True},
        {"0": 40, "1": 39},
    )],
    # The stream a kill driver leaves: the fault record rides in it with the
    # plan's name, and the report needs nothing but the JSONL.
    "headline": [(
        _SURVIVOR + _commits("1:A", range(1, 11)) + _commits("1:B", range(18, 41))
        + [_fault(10.5, "1", plan="single")],
        {"dead_time_s": 7.0, "fraction": 1 - 7.0 / 39.0, "victims_recovered": True},
        {"0": 40, "1": 33},
    )],
    # Churn: two kills of one victim charge two gaps, (10, 18) and (22, 30),
    # each less the 1 s median step; a victim that never commits again
    # invalidates the trial (no fraction).
    "double_kill_and_unrecovered": [
        (
            _SURVIVOR + _commits("1:A", range(1, 11)) + _commits("1:B", range(18, 23))
            + _commits("1:C", range(30, 41)) + [_fault(10.5, "1"), _fault(22.5, "1")],
            {"dead_time_s": 14.0, "fraction": 1 - 14.0 / 39.0, "victims_recovered": True},
            {"0": 40, "1": 26},
        ),
        (
            _SURVIVOR + _commits("1:A", range(1, 11)) + [_fault(10.5, "1")],
            {"dead_time_s": 0.0, "fraction": None, "victims_recovered": False},
            {"0": 40, "1": 10},
        ),
    ],
    # One kill, no other record of the victim's second life than its commits.
    "kill_and_rejoin": [(
        _SURVIVOR + _commits("1:A", range(1, 11)) + _commits("1:B", range(18, 41))
        + [_fault(10.5, "1")],
        {"dead_time_s": 7.0, "fraction": 1 - 7.0 / 39.0, "victims_recovered": True},
        {"0": 40, "1": 33},
    )],
}


@pytest.mark.parametrize("case", sorted(DEADWINDOW_STREAMS))
def test_deadwindow_on_recorded_streams(tmp_path, case) -> None:
    """The dead-window goodput of a recorded stream, fault records included:
    `report.deadwindow` on the stream's commit timelines and fault times,
    and the same figures through `report.attribute` (what the CLI prints)."""
    for i, (events, want, per_group) in enumerate(DEADWINDOW_STREAMS[case]):
        path = _write_jsonl(tmp_path / f"m{i}.jsonl", events)
        events = report.read_events([path])
        commits = report.commit_timelines(events)
        assert {g: len(ts) for g, ts in commits.items()} == per_group
        dw = report.deadwindow(commits, report.fault_times(events))
        assert (dw["t0"], dw["t_end"], dw["span_s"]) == (1.0, 40.0, 39.0)
        assert dw["victims_recovered"] is want["victims_recovered"]
        assert dw["dead_time_s"] == pytest.approx(want["dead_time_s"], abs=1e-6)
        assert dw["fraction"] == (
            None if want["fraction"] is None
            else pytest.approx(want["fraction"], abs=1e-6)
        )
        result = report.attribute(events)
        goodput = result["goodput"]
        assert goodput["victims_recovered"] is want["victims_recovered"]
        assert goodput["dead_time_s"] == pytest.approx(want["dead_time_s"], abs=5e-3)
        assert goodput["deadwindow_fraction"] == (
            None if want["fraction"] is None
            else pytest.approx(want["fraction"], abs=5e-5)
        )
        # The report also yields a per-step table over the same stream.
        assert result["steps"], "attribution table empty"


def test_report_cli_json_and_table(tmp_path) -> None:
    path = _write_jsonl(tmp_path / "m.jsonl", _synthetic_stream())
    out = subprocess.run(
        [sys.executable, "-m", "torchft_tpu.obs.report", path, "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    result = json.loads(out.stdout)
    assert {"steps", "totals", "fractions", "goodput"} <= set(result)
    out2 = subprocess.run(
        [sys.executable, "-m", "torchft_tpu.obs.report", path],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out2.returncode == 0, out2.stderr
    assert "critical" in out2.stdout and "goodput (dead-window)" in out2.stdout


def test_read_events_skips_and_counts_corrupt_lines(tmp_path, capsys) -> None:
    """A writer killed mid-record leaves truncated/garbage trailing lines:
    read_events must skip them WITH a count instead of raising, including
    JSON that parses to a non-dict (a torn line that happens to be a bare
    number would otherwise crash every consumer doing ev.get)."""
    path = tmp_path / "m.jsonl"
    good1 = json.dumps({"ts": 1.0, "replica_id": "0:a", "event": "commit",
                        "step": 1, "committed": True})
    good2 = json.dumps({"ts": 2.0, "replica_id": "0:a", "event": "commit",
                        "step": 2, "committed": True})
    with open(path, "wb") as f:
        f.write(good1.encode() + b"\n")
        f.write(b'{"ts": 1.5, "replica_id": "0:a", "event": "comm\n')  # torn
        f.write(b"5\n")  # parses, but not a record
        f.write(b"\x00\xffgarbage\n")
        f.write(b"\n")  # blank lines are not corruption
        f.write(good2.encode() + b"\n")
        f.write(good1.encode()[: len(good1) // 2])  # truncated final write
    stats: dict = {}
    events = report.read_events([str(path)], stats=stats)
    assert [e["step"] for e in events] == [1, 2]
    assert stats["skipped_lines"] == 4
    assert stats["skipped_by_file"] == {str(path): 4}
    assert stats["unreadable_files"] == []
    assert "skipped 4 unparseable line(s)" in capsys.readouterr().err
    # Missing files are reported, not raised.
    stats2: dict = {}
    assert report.read_events([str(tmp_path / "nope.jsonl")], stats=stats2) == []
    assert stats2["unreadable_files"] == [str(tmp_path / "nope.jsonl")]


def test_report_cli_json_reports_skipped_lines(tmp_path) -> None:
    path = tmp_path / "m.jsonl"
    with open(path, "wb") as f:
        for ev in _synthetic_stream():
            f.write((json.dumps(ev) + "\n").encode())
        f.write(b'{"truncated\n')
    out = subprocess.run(
        [sys.executable, "-m", "torchft_tpu.obs.report", str(path), "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    result = json.loads(out.stdout)
    assert result["input"]["skipped_lines"] == 1
    assert "skipped 1 unparseable line(s)" in out.stderr


# ---------------------------------------------------------------------------
# Trace export (obs/trace.py + tools/trace_export.py)
# ---------------------------------------------------------------------------


def test_trace_export_quick_smoke() -> None:
    """The tier-1 wiring of tools/trace_export.py --quick: synthetic
    2-replica stream -> export -> Chrome-trace schema validation."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_export.py"),
         "--quick"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    summary = json.loads(out.stdout)
    assert summary["ok"] is True and summary["problems"] == []
    assert summary["replicas"] == 2
    # The control-plane track (lighthouse flight-recorder view) rides in
    # the same smoke (ISSUE 7) — one synthetic lighthouse source.
    assert summary["control_plane_tracks"] == 1
    assert summary["trace_events"] > 0
    with open(summary["out"]) as f:
        trace = json.load(f)
    assert {e["ph"] for e in trace["traceEvents"]} <= {"X", "i", "M"}
    os.remove(summary["out"])


def test_trace_builder_from_real_span_stream(tmp_path) -> None:
    """End-to-end through the REAL producers: two SpanTracker/MetricsLogger
    replicas emit spans + summaries (plus a driver fault record); the built
    trace validates — one named track per replica, monotonic non-overlapping
    slices, fault instant on the global lane."""
    from torchft_tpu.obs import trace

    path = tmp_path / "m.jsonl"
    for rid in ("0:aa", "1:bb"):
        tracker = SpanTracker(MetricsLogger(str(path), replica_id=rid), slice_gen=0)
        for step in (1, 2):
            with tracker.span("quorum", step=step):
                time.sleep(0.002)
            with tracker.span("commit_vote", step=step):
                time.sleep(0.001)
            tracker.step_summary(step, committed=True)
    driver = MetricsLogger(str(path), replica_id="bench-driver")
    driver.emit("fault", kind="kill", group="1")
    driver.close()

    events = report.read_events([str(path)])
    built = trace.build_trace(events)
    problems = trace.validate_trace(built)
    assert problems == [], problems
    evs = built["traceEvents"]
    slices = [e for e in evs if e["ph"] == "X"]
    assert {s["name"] for s in slices} == {"quorum", "commit_vote"}
    assert all(s["dur"] >= 0 and s["ts"] >= 0 for s in slices)
    # One named track per replica, faults on the global pid-0 lane.
    thread_names = {
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert thread_names == {"0:aa", "1:bb"}
    fault = next(e for e in evs if e["ph"] == "i" and "fault" in e["name"])
    assert fault["pid"] == 0 and fault["s"] == "g"
    # args carry the step so Perfetto slices are self-describing.
    assert all("step" in s["args"] for s in slices)


def test_trace_export_three_replica_kill_run(tmp_path) -> None:
    """The acceptance shape: a 3-replica stream with kill fault + drain
    instants exports to valid Chrome trace JSON via the CLI — per-track
    slices non-overlapping, both instant kinds present."""
    from torchft_tpu.obs import trace

    events = trace.synthetic_stream(n_replicas=3, steps=5)
    path = tmp_path / "metrics.jsonl"
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    out_path = tmp_path / "trace.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_export.py"),
         str(path), "-o", str(out_path)],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    summary = json.loads(out.stdout)
    assert summary["ok"] is True and summary["replicas"] == 3
    with open(out_path) as f:
        built = json.load(f)
    assert trace.validate_trace(built) == []
    instants = [e["name"] for e in built["traceEvents"] if e["ph"] == "i"]
    assert any("fault:kill" in n for n in instants)
    assert "drain_notice" in instants
    # Non-overlap, re-checked directly (the validator is also under test).
    tracks: dict = {}
    for e in built["traceEvents"]:
        if e["ph"] != "X":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= tracks.get(key, -1.0) - 0.5
        tracks[key] = e["ts"] + e["dur"]


def test_trace_clock_alignment_uses_commit_barrier() -> None:
    """Replicas with skewed wall clocks align on the step_summary commit
    barrier: the skew lands in otherData.clock_offsets_s and the commit
    slices line up across tracks."""
    from torchft_tpu.obs import trace

    events = trace.synthetic_stream(n_replicas=3, steps=4)
    built = trace.build_trace(events, align=True)
    offs = built["otherData"]["clock_offsets_s"]
    # synthetic_stream injects 2 ms skew per replica index; the median
    # replica becomes the reference.
    assert offs["0:a0"] == pytest.approx(-0.002, abs=1e-6)
    assert offs["1:b1"] == pytest.approx(0.0, abs=1e-6)
    assert offs["2:c2"] == pytest.approx(0.002, abs=1e-6)
    unaligned = trace.build_trace(events, align=False)
    assert unaligned["otherData"]["clock_offsets_s"] == {}


# ---------------------------------------------------------------------------
# tools/profile_step.py --json (device-side profile, machine-readable)
# ---------------------------------------------------------------------------


def test_profile_step_json_smoke(tmp_path) -> None:
    """--json --trace parses a Chrome-trace fixture into the machine-readable
    per-op report (no TPU needed), so device-side and runtime-side profiles
    can be joined in one pipeline."""
    import gzip

    trace = {
        "traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
             "args": {"name": "XLA Ops"}},
            {"ph": "X", "pid": 1, "tid": 2, "name": "fusion.1", "dur": 4000,
             "args": {"hlo_category": "convolution fusion",
                      "bytes_accessed": 2_000_000_000}},
            {"ph": "X", "pid": 1, "tid": 2, "name": "fusion.1", "dur": 4000},
            {"ph": "X", "pid": 1, "tid": 2, "name": "copy.7", "dur": 1000,
             "args": {"hlo_category": "copy"}},
        ]
    }
    path = tmp_path / "t.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "profile_step.py"),
         "--trace", str(path), "--steps", "2", "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["schema"] == 1 and rep["steps"] == 2
    # (4000+4000+1000) us over 2 steps = 4.5 ms/step.
    assert rep["device_total_ms_per_step"] == pytest.approx(4.5)
    assert rep["ops"][0]["name"] == "fusion.1"
    assert rep["ops"][0]["ms_per_step"] == pytest.approx(4.0)
    assert rep["ops"][0]["gb_accessed"] == pytest.approx(2.0)
    assert {c["op_class"] for c in rep["by_class"]} == {"fusion", "copy"}
    # Human-readable mode still renders.
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "profile_step.py"),
         "--trace", str(path), "--steps", "2"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert out2.returncode == 0, out2.stderr
    assert "device ops total" in out2.stdout


# ---------------------------------------------------------------------------
# Lighthouse /metrics exposition (Prometheus text) under kill-and-heal
# ---------------------------------------------------------------------------


def _scrape(lighthouse) -> dict:
    port = lighthouse.http_address().rsplit(":", 1)[1]
    url = f"http://127.0.0.1:{port}/metrics"
    with urllib.request.urlopen(url, timeout=10) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    metrics = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_labels, _, value = line.rpartition(" ")
        metrics[name_labels] = float(value)
    assert metrics, f"no samples parsed from:\n{text}"
    return metrics


def test_lighthouse_metrics_during_kill_and_heal() -> None:
    """Wire-level kill-and-heal against the real lighthouse, scraping
    /metrics at each stage: healthy 2-group quorum -> one group SIGKILLed
    (supervisor evict) -> replacement incarnation rejoins behind and heals
    -> caught up.  The exposition must track quorum size, per-replica step
    lag, tombstones, and the heal gauge through the whole arc."""
    from torchft_tpu._native import LighthouseClient, LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=300,
        quorum_tick_ms=20, heartbeat_timeout_ms=5000,
    )
    try:
        client = LighthouseClient(server.address())
        client2 = LighthouseClient(server.address())

        # Healthy steady state: both groups at step 5.  Heartbeat BOTH ids
        # before joining so the split-brain guard deterministically holds
        # the first joiner until the second arrives (2 of 2 join).
        import threading

        client.heartbeat("0:bbbb", step=5, state="step")
        client.heartbeat("1:aaaa", step=5, state="step")
        results = []
        joiner = threading.Thread(
            target=lambda: results.append(
                client.quorum("1:aaaa", timeout_ms=10000, step=5)
            )
        )
        joiner.start()
        q = client2.quorum("0:bbbb", timeout_ms=10000, step=5)
        joiner.join()
        assert len(q.participants) == 2
        m = _scrape(server)
        assert m["tpuft_quorum_size"] == 2
        assert m['tpuft_replica_step{replica="1:aaaa"}'] == 5
        assert m['tpuft_replica_step_lag{replica="1:aaaa"}'] == 0
        assert m["tpuft_replicas_tombstoned"] == 0
        assert m["tpuft_heal_in_progress"] == 0

        # Kill: the supervisor reaps 1:aaaa and evicts it.
        assert client.evict("1") == 1
        m = _scrape(server)
        assert m["tpuft_replicas_tombstoned"] == 1
        assert 'tpuft_replica_step{replica="1:aaaa"}' not in m

        # Survivor advances; replacement incarnation rejoins behind, healing.
        client.heartbeat("0:bbbb", step=8, state="step")
        client.heartbeat("1:cccc", step=5, state="heal")
        t0 = time.monotonic()
        results2 = []
        joiner2 = threading.Thread(
            target=lambda: results2.append(
                client.quorum("1:cccc", timeout_ms=10000, step=5)
            )
        )
        joiner2.start()
        q2 = client2.quorum("0:bbbb", timeout_ms=10000, step=8)
        joiner2.join()
        assert time.monotonic() - t0 < 5.0, "evict must beat heartbeat timeout"
        assert len(q2.participants) == 2
        m = _scrape(server)
        assert m['tpuft_replica_step_lag{replica="1:cccc"}'] == 3
        assert m["tpuft_heal_in_progress"] == 1
        assert m["tpuft_quorum_size"] == 2

        # Healed: caught up, lag back to zero.
        client.heartbeat("1:cccc", step=8, state="step")
        m = _scrape(server)
        assert m['tpuft_replica_step_lag{replica="1:cccc"}'] == 0
        assert m["tpuft_heal_in_progress"] == 0
        # The step advance stamped a last-commit age for the healed group.
        assert (
            m['tpuft_replica_last_commit_age_seconds{replica="1:cccc"}'] < 60
        )
    finally:
        server.shutdown()


def test_manager_server_set_status_feeds_heartbeats() -> None:
    """The Python-facing half of the pipeline: ManagerServer.set_status
    rides the next heartbeat into the lighthouse's live view."""
    from torchft_tpu._native import LighthouseServer, ManagerServer

    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200, quorum_tick_ms=20
    )
    manager = None
    try:
        manager = ManagerServer(
            replica_id="g0:uuid1",
            lighthouse_addr=lighthouse.address(),
            bind="127.0.0.1:0",
            heartbeat_interval_ms=25,
        )
        manager.set_status(7, "step")
        deadline = time.monotonic() + 5.0
        m = {}
        while time.monotonic() < deadline:
            m = _scrape(lighthouse)
            if m.get('tpuft_replica_step{replica="g0:uuid1"}') == 7:
                break
            time.sleep(0.05)
        assert m.get('tpuft_replica_step{replica="g0:uuid1"}') == 7
        # /status.json mirrors the same live view.
        port = lighthouse.http_address().rsplit(":", 1)[1]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status.json", timeout=10
        ) as resp:
            status = json.loads(resp.read().decode())
        assert status["replica_step"]["g0:uuid1"] == 7
        assert status["replica_state"]["g0:uuid1"] == "step"
        # A later advance stamps last_commit_ts_ms.
        manager.set_status(8, "step")
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status.json", timeout=10
            ) as resp:
                status = json.loads(resp.read().decode())
            if status["replica_step"].get("g0:uuid1") == 8:
                break
            time.sleep(0.05)
        assert status["replica_step"]["g0:uuid1"] == 8
        assert "g0:uuid1" in status["last_commit_ts_ms"]
    finally:
        if manager is not None:
            manager.shutdown()
        lighthouse.shutdown()


def test_report_data_plane_rollup_across_topologies() -> None:
    """attribute()'s data_plane section: payload bytes sum per step (the
    wire_nbytes-based accounting, comparable across topologies), per-tier
    wire counters take each incarnation's high-water mark (lane_stats
    snapshots are cumulative — summing them would double count), and the
    active topology set is surfaced."""
    from torchft_tpu.obs import report

    def summary(rid, step, nbytes, lanes):
        return {
            "event": "step_summary", "replica_id": rid, "step": step,
            "ts": 100.0 + step, "committed": True, "phases": {},
            "allreduce_bytes": nbytes, "allreduce_lanes": lanes,
        }

    events = [
        summary("g0:u1", 1, 1000, {
            "lanes": 2, "topology": "ring2d", "sent": [10, 10],
            "tiers": {"row": {"size": 2, "sent": [300], "recv": [300]},
                      "col": {"size": 2, "sent": [100], "recv": [100]}},
        }),
        summary("g0:u1", 2, 1000, {
            "lanes": 2, "topology": "ring2d", "sent": [10, 10],
            "tiers": {"row": {"size": 2, "sent": [600], "recv": [600]},
                      "col": {"size": 2, "sent": [200], "recv": [200]}},
        }),
        summary("g1:u2", 1, 1000, {
            "lanes": 2, "topology": "ring", "sent": [500, 500],
        }),
        # A reconfigure RESET g1's counters (new quorum membership), then
        # more traffic: the rollup must bank the pre-reset epoch instead
        # of dropping it to the post-reset max.
        summary("g1:u2", 2, 1000, {
            "lanes": 2, "topology": "ring", "sent": [50, 50],
        }),
    ]
    dp = report.data_plane(events)
    assert dp["allreduce_payload_bytes"] == 4000
    assert dp["per_replica_payload_bytes"] == {"g0:u1": 2000, "g1:u2": 2000}
    # High-water mark within an epoch, not sum: g0's row tier reads 600,
    # not 900.
    assert dp["tier_wire_bytes"]["row"] == 600
    assert dp["tier_wire_bytes"]["col"] == 200
    # Flat counters: g0's 20 + g1's banked 1000 + post-reset 100.
    assert dp["tier_wire_bytes"]["flat"] == 1120
    assert dp["topologies"] == ["ring", "ring2d"]
    # And the full attribute() payload carries the section.
    out = report.attribute(events)
    assert out["data_plane"]["allreduce_payload_bytes"] == 4000


def test_ec_coverage_alert_pages_and_resolves() -> None:
    """The EC redundancy sentinel end to end: two holders reporting full
    shard coverage keep the lighthouse quiet; one holder dying drops the
    newest generation's coverage below k + 1, and after the heartbeat-
    timeout grace the lighthouse raises a cluster-scope "ec_coverage"
    alert on /alerts.json (tpuft_alerts_active pages); the holder coming
    back resolves it."""
    from torchft_tpu._native import LighthouseServer, ManagerServer

    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20, heartbeat_timeout_ms=300,
    )
    port = lighthouse.http_address().rsplit(":", 1)[1]

    def alerts() -> list:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/alerts.json", timeout=10
        ) as resp:
            return json.loads(resp.read().decode())["alerts"]

    def active_ec() -> list:
        return [
            a for a in alerts()
            if a["kind"] == "ec_coverage" and a["active"]
        ]

    def start_holder(name: str, shards: int) -> "ManagerServer":
        srv = ManagerServer(
            replica_id=name,
            lighthouse_addr=lighthouse.address(),
            bind="127.0.0.1:0",
            heartbeat_interval_ms=25,
        )
        # k=2 -> threshold k + 1 = 3; each holder serves 2 shards of the
        # step-7 generation, so both together sit at coverage 4.
        srv.set_status(7, "step", 0.0, 0.0, -1.0, shards, 7, 2)
        return srv
    holders = {n: start_holder(n, 2) for n in ("g0:ec", "g1:ec")}
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            m = _scrape(lighthouse)
            if m.get("tpuft_ec_shard_coverage") == 4:
                break
            time.sleep(0.05)
        assert m.get("tpuft_ec_shard_coverage") == 4
        assert m["tpuft_alerts_active"] == 0 and not active_ec()

        # One holder dies: coverage 2 < 3 once its heartbeats go stale.
        holders.pop("g1:ec").shutdown()
        deadline = time.monotonic() + 10.0
        fired = []
        while time.monotonic() < deadline and not fired:
            fired = active_ec()
            time.sleep(0.05)
        assert fired, "ec_coverage alert never raised"
        assert fired[0]["replica_id"] == "cluster"
        assert fired[0]["coverage"] == 2 and fired[0]["threshold"] == 3
        assert _scrape(lighthouse)["tpuft_alerts_active"] >= 1

        # The holder returns with its shards: the alert resolves.
        holders["g1:ec"] = start_holder("g1:ec", 2)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and active_ec():
            time.sleep(0.05)
        assert not active_ec()
        resolved = [a for a in alerts() if a["kind"] == "ec_coverage"]
        assert resolved and not resolved[-1]["active"]
        assert _scrape(lighthouse)["tpuft_alerts_active"] == 0
    finally:
        for srv in holders.values():
            srv.shutdown()
        lighthouse.shutdown()
