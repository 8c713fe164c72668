"""The readers of the program's sub-spans, ring counters and program names,
against what two chip runs recorded (TPU v5 lite; PR 25):

- `data/subspans_v5e_4g.*`: the traced run of `internlm2-1.8b.steady-4g`, seed
  5101 — group 0's stream over the window's three steps (`span`, `subspan` and
  `step_summary` records, one summary before the window for the counters'
  difference), the harness's step dump, and the profile as
  `program_spans.trace()` gives it (one traced step);
- `data/subspans_v5e_1g.*`: the same of `internlm2-1.8b.steady-1g`, seed 7007,
  the window's first 14 steps (six traced).

Nothing here is timed: the numbers were, on the chip; the tests pin how they
are read.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import program_spans as ps
from benchmark.spec import Benchmark
from benchmark.tests.tiny_bench import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = [
    "d2h_ready_wait_ms.4g", "d2h_fetch_gb_per_s.4g", "d2h_copy_ms.4g", "d2h_handoff_ms.4g", "ring_queue_ms.4g",
    "ring_busy_ms.4g", "ring_recv_wait_ms.4g", "ring_combine_ms.4g", "ring_sent_bytes.4g", "exchange_normalize_ms.4g",
    "quorum_wait_ms", "quorum_wait_ms.4g", "ft_step_self_ms", "ft_step_self_ms.4g", "device_grad_ms",
    "device_grad_ms.4g", "device_update_ms",
]
EXCHANGE = [n for n in NEW if n.split("_")[0] in ("d2h", "ring", "exchange")]
PAYLOAD = 2522947584  # 630,736,896 float32 gradients


def fixture(kind, monkeypatch, stream_path=None):
    """The ctx a reader gets, with the recorded stream and trace in place of a run's."""
    with open(os.path.join(DATA, f"subspans_v5e_{kind}.steps.jsonl"), encoding="utf-8") as f:
        steps = [json.loads(line) for line in f]
    with open(os.path.join(DATA, f"subspans_v5e_{kind}.trace.json"), encoding="utf-8") as f:
        trace = json.load(f)
    monkeypatch.setenv(ps.STREAM_ENV, stream_path or os.path.join(DATA, f"subspans_v5e_{kind}.stream.jsonl"))
    monkeypatch.setattr(ps, "of_traced_run", lambda: trace)
    bench = Benchmark()
    steady = [s for s in steps if not s["traced"] and not s["after_trace"]]
    return bench, {"steady_steps": steady, "steps": steps, "traffic": bench.traffic(f"steady-{kind}")}, trace


def read_all(bench, ctx, names):
    return {name: bench.reader(name).read(ctx) for name in names}


def test_every_new_entry_has_its_reader_and_the_old_ones_are_as_they_were():
    bench = Benchmark()
    by_name = {m["name"]: m for m in bench.doc["per_layer"]}
    assert [m["name"] for m in bench.doc["per_layer"][16:]] == NEW  # appended, in this order
    for name in NEW:
        reader, metric = bench.reader(name), by_name[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["moves"], metric["source"])
        assert reader.__doc__ and callable(reader.read)
    four = {m["name"] for m in bench.per_layer("internlm2-1.8b.steady-4g")}
    assert {n for n in NEW if n.endswith(".4g")} <= four
    assert four - set(NEW) == {"quorum_ms.4g", "commit_vote_ms.4g", "exchange_exposed_ms.4g", "exchange_wire_bytes",
                               "device_step_ms.4g", "alloc_peak_bytes.4g", "mfu.4g"}
    one = {m["name"] for m in bench.per_layer("mistral-7b.steady-1g")}
    assert one & set(NEW) == {"quorum_wait_ms", "ft_step_self_ms", "device_grad_ms", "device_update_ms"}
    for m in bench.doc["per_layer"][16:]:
        assert m["workloads"] and set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_four_groups_the_exchange_taken_apart(monkeypatch):
    bench, ctx, _ = fixture("4g", monkeypatch)
    got = read_all(bench, ctx, [n for n in NEW if n.endswith(".4g")])
    assert all(v is not None for v in got.values()), got
    # What the chip run printed (its result line), to the digit the readers give.
    assert got["d2h_ready_wait_ms.4g"] == pytest.approx(188.020274)
    assert got["d2h_fetch_gb_per_s.4g"] == pytest.approx(0.3138883, rel=1e-6)
    assert got["d2h_copy_ms.4g"] == pytest.approx(303.414764)
    assert got["ring_busy_ms.4g"] == pytest.approx(2058.908866)
    assert got["exchange_normalize_ms.4g"] == pytest.approx(8952.999773)
    assert got["ring_recv_wait_ms.4g"] == pytest.approx(11994.493) and got["ring_combine_ms.4g"] == pytest.approx(312.574)
    assert got["quorum_wait_ms.4g"] == 0.0
    assert got["device_grad_ms.4g"] == pytest.approx(188.638856)
    # A ring of four sends 2 (n-1)/n of the payload, and a few frame headers.
    assert 0 <= got["ring_sent_bytes.4g"] - 1.5 * PAYLOAD < 1e-5 * PAYLOAD
    # The update that the one traced step dispatched runs after the capture:
    # only the one before it is there, cut off at the capture's start.
    assert ps.program_ms(ps.of_traced_run(), ps.UPDATE_PROGRAM, 0) is None
    assert got["d2h_handoff_ms.4g"] >= 0


def test_children_lie_inside_their_fetch_and_add_up(monkeypatch):
    _, ctx, _ = fixture("4g", monkeypatch)
    data = ps.of_run()
    fetches = {(s["step"], s["bucket"]): s for s in data["spans"] if s["phase"] == "allreduce_d2h"}
    children = [s for s in data["subs"] if s["parent"] == "allreduce_d2h"]
    assert len(children) >= 3 * len(fetches) > 0
    for s in children:
        span = fetches[(s["step"], s["bucket"])]
        # `duration_ms` is rounded to the microsecond; float seconds at 1e5 s resolve 15 ns.
        assert span["t0_ns"] - 2e3 <= s["t0_ns"] <= s["t1_ns"] <= span["t1_ns"] + 2e3
    for step in ctx["steady_steps"]:
        subs, spans = ps.in_step(data["subs"], step), ps.in_step(data["spans"], step)
        parts = sum(ps.total_ms(subs, n) for n in ("d2h_ready", "d2h_fetch", "d2h_copy")) + ps.d2h_handoff_ms(subs, spans)
        assert parts == pytest.approx(ps.total_ms(spans, "allreduce_d2h"))
        # The frame is covered: the train thread's phases, its named sub-spans and the rest.
        frame = ps.total_ms(subs, "ft_step")
        named = sum(ps.total_ms(spans, p) for p in ps.TRAIN_THREAD_PHASES) + sum(
            ps.total_ms(subs, n) for n in ("quorum_wait", "grads_dispatch", "apply_dispatch"))
        assert named + ps.ft_step_self_ms(subs, spans) == pytest.approx(frame, rel=1e-3)
        for s in subs:
            if s["name"] == "ring_run":
                (queue,) = [q for q in subs if q["name"] == "ring_queue" and q["bucket"] == s["bucket"]]
                assert queue["t0_ns"] <= queue["t1_ns"] == s["t0_ns"] <= s["t1_ns"]


def test_one_group_reads_the_step_and_no_exchange(monkeypatch):
    bench, ctx, _ = fixture("1g", monkeypatch)
    got = read_all(bench, ctx, NEW)
    assert all(got[n] is None for n in EXCHANGE), got
    assert got["quorum_wait_ms"] == 0.0  # the quorum had settled before the step asked
    assert got["ft_step_self_ms"] == pytest.approx(0.655401)  # over the fixture's seven steady steps
    assert got["device_grad_ms"] == pytest.approx(188.658158) and got["device_update_ms"] == pytest.approx(27.050702)
    # The two programs are the device's step: that run's `device_step_ms` read 215.482079.
    assert got["device_grad_ms"] + got["device_update_ms"] == pytest.approx(215.482079, rel=0.02)


def test_a_program_without_sub_spans_gives_nothing(monkeypatch, tmp_path):
    """The parent of the PR that added them: `span` records without
    `t_start_mono`, no `subspan` record, and a CPU rehearsal's trace."""
    old = tmp_path / "g0.metrics.jsonl"
    with open(os.path.join(DATA, "subspans_v5e_4g.stream.jsonl"), encoding="utf-8") as f, open(old, "w") as out:
        for line in f:
            rec = json.loads(line)
            rec.pop("t_start_mono", None)
            rec.pop("allreduce_lanes", None)
            if rec["event"] != "subspan":
                out.write(json.dumps(rec) + "\n")
    bench, ctx, _ = fixture("4g", monkeypatch, stream_path=str(old))
    monkeypatch.setattr(ps, "of_traced_run", lambda: {"modules": {}, "annotations": [], "steps": [[0.0, 1.0, None]]})
    assert all(v is None for v in read_all(bench, ctx, NEW).values())
    monkeypatch.setattr(ps, "of_traced_run", lambda: None)  # no profile beside the stream at all
    assert bench.reader("device_grad_ms").read(ctx) is None
    monkeypatch.setenv(ps.STREAM_ENV, str(tmp_path / "absent.jsonl"))
    assert bench.reader("quorum_wait_ms").read(ctx) is None
    # Spans without their own start fall back to the stamp less the duration.
    spans = ps.stream(str(old))["spans"]
    assert spans and all(s["t1_ns"] == pytest.approx(s["t_mono"] * 1e9) for s in spans)


@pytest.mark.parametrize("kind,offset_ns", [("4g", -141465435142.0), ("1g", -64816370402.5)])
def test_annotations_agree_with_the_stream_through_the_measured_offset(monkeypatch, kind, offset_ns):
    """`offset_ns` is what `trace_reduce.clock_offset` measured in that run:
    every `tpuft:` annotation starts within 0.1 ms of the same span's start in
    the stream moved by it — the old placement by offset was sound."""
    _, _, trace = fixture(kind, monkeypatch)
    agreement = ps.clock_agreement(trace, ps.of_run())
    assert agreement["clock_offset_ns"] == offset_ns
    assert agreement["matched"] == len(trace["annotations"]) >= 36
    assert agreement["max_abs_ms"] < 0.1
    names = {a[0] for a in trace["annotations"]}
    assert {"ft_step", "grads_dispatch", "apply_dispatch", "commit_vote", "quorum"} <= names
    if kind == "4g":
        assert {"d2h_ready", "d2h_fetch", "d2h_copy", "normalize", "h2d_put", "allreduce_d2h"} <= names
        assert all("bucket" in a[3] for a in trace["annotations"] if a[0] == "d2h_fetch")


def test_the_bucket_table(tmp_path):
    run_dir = tmp_path / "cell.1.run"
    run_dir.mkdir()
    with open(os.path.join(DATA, "subspans_v5e_4g.stream.jsonl"), "rb") as f:
        (run_dir / "g0.metrics.jsonl").write_bytes(f.read())
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools", "buckets.py"), str(run_dir), "--json"],
                          capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    tables = [json.loads(line) for line in proc.stdout.splitlines()]
    assert len(tables) == 3
    for table in tables:
        rows = table["rows"]
        assert [r["bucket"] for r in rows] == list(range(10))
        assert sum(r["MB"] for r in rows) * 1e6 == pytest.approx(PAYLOAD)
        assert all(r["handoff"] >= -2e-3 and r["run"] > 0 and r["normalize"] > 0 for r in rows)
        assert table["sums"]["ring_busy"] <= sum(r["run"] for r in rows)
    # The embedding's fetch is where the fetches' time is.
    assert tables[-1]["rows"][0]["fetch"] > 0.9 * sum(r["fetch"] for r in tables[-1]["rows"])
    text = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools", "buckets.py"), str(run_dir), "--step", "3"],
                          capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert text.returncode == 0 and text.stdout.startswith("step 3\n") and "normalize" in text.stdout
    (run_dir / "g1.metrics.jsonl").write_text("")
    none = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools", "buckets.py"), str(run_dir), "--group", "1"],
                          capture_output=True, text=True)
    assert none.returncode == 1 and "no sub-span" in none.stderr
