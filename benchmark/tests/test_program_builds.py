"""The readers of the program's `program_build` records and `manager_start`
sub-span — the per-layer metrics that move `setup_s` (PR 70) — against what two
chip runs recorded (TPU v5 lite; PR 70, `tools/tree_pairs.py`; the one-chip run
from the committed files unpacked into `committed_tree/`):

- `data/builds_v5e_1g.*`: a traced run of `internlm2-1.8b.steady-1g`, warm
  cache — group 0's stream from its first record to the window's third
  `step_summary`, and the harness's dump of those steps;
- `data/builds_v5e_4g.*`: the same of `internlm2-1.8b.steady-4g`, a checkout's
  first run: four groups fill one empty cache, so 36 of group 0's 39 builds
  miss and three find what another group had just written; the gradient
  program is built a second time in the warm-up step (committed arguments),
  which `other_builds_s` holds.

Nothing here is timed: the numbers were, on the chip; the tests pin how they
are read.
"""

import json
import os

import pytest

from benchmark import program_builds as pb
from benchmark.spec import Benchmark

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ["grad_trace_lower_s", "grad_load_s", "update_build_s", "other_builds_s", "builds_before_window",
       "cache_misses_before_window", "manager_start_s"]
# What each fixture's run printed in its result line, to the digit the readers give.
PINNED = {
    "1g": {"grad_trace_lower_s": 5.121260642, "grad_load_s": 0.795577287, "update_build_s": 0.119159697,
           "other_builds_s": 0.1873407029999994, "builds_before_window": 24, "cache_misses_before_window": 0,
           "manager_start_s": 0.045459634},  # seed 2147487402, the second run on a cache the first filled
    "4g": {"grad_trace_lower_s": 5.70557165, "grad_load_s": 32.146768569, "update_build_s": 1.696083066,
           "other_builds_s": 39.062027635999996, "builds_before_window": 39, "cache_misses_before_window": 36,
           "manager_start_s": 0.039573166},  # seed 2147487201, four groups filling one empty cache
}


def recorded(kind, monkeypatch, stream=None):
    """The ctx a reader gets, with a recorded stream in place of a run's."""
    with open(os.path.join(DATA, f"builds_v5e_{kind}.steps.jsonl"), encoding="utf-8") as f:
        steps = [json.loads(line) for line in f]
    monkeypatch.setenv(pb.STREAM_ENV, stream or os.path.join(DATA, f"builds_v5e_{kind}.stream.jsonl"))
    return {"steps": steps, "steady_steps": steps}


def read_all(ctx):
    bench = Benchmark()
    return {name: bench.reader(name).read(ctx) for name in NEW}


def test_the_seven_entries_are_appended_with_their_readers_and_every_cell():
    bench = Benchmark()
    cells = [w["name"] for w in bench.doc["workloads"]]
    added = bench.doc["per_layer"][-len(NEW):]
    assert [m["name"] for m in added] == NEW  # at the end, in the issue's order
    moving_setup = [m["name"] for m in bench.doc["per_layer"] if m["moves"] == "setup_s"]
    assert moving_setup == NEW  # the first metrics that move it, and no other
    for metric in added:
        reader = bench.reader(metric["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], "setup_s", metric["source"])
        assert reader.__doc__ and callable(reader.read)
        assert metric["better"] == "lower" and metric["workloads"] == cells  # no `.4g` twin: a set-up is a set-up
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert {m["layer"] for m in added} == {"train step", "control plane"}
    for cell in cells:
        assert set(NEW) <= {m["name"] for m in bench.per_layer(cell)}


def build(stage, t0, t1, fun="f", program=None, outer=None, **more):
    return dict(dict(event="program_build", fun_name=fun, stage=stage, program=program, outer=outer,
                     t0_ns=t0, t1_ns=t1, step=None, thread="MainThread"), **more)


def test_unions_count_a_nested_stage_once_and_the_four_times_add_up(tmp_path, monkeypatch):
    """A hand-made stream, times in ns: the gradient program's trace with a
    kernel's trace and a whole small build inside it, its lower and backend;
    a second build of it under other arguments; the update program twice; two
    other builds, one of them on another thread under the update's trace; one
    build after the window opened."""
    grad, update = pb.GRAD_PROGRAM, pb.UPDATE_PROGRAM
    records = [
        build("trace", 110, 130, "kernel", outer="value_and_grad"),
        build("trace", 140, 150, "mask", outer="value_and_grad"),
        build("lower", 150, 160, "jit(mask)", outer="value_and_grad"),
        build("backend", 160, 190, "jit(mask)", outer="value_and_grad", cache="hit", retrieval_s=1e-8, saved_s=0.0),
        build("trace", 100, 400, "value_and_grad", program=grad),
        build("lower", 400, 500, "jit(value_and_grad)", program=grad),
        build("backend", 500, 800, "jit(value_and_grad)", program=grad, cache="miss"),
        build("trace", 900, 950, "init"),
        build("lower", 950, 960, "jit(init)"),
        build("backend", 960, 1000, "jit(init)", cache="off"),
        build("trace", 1100, 1300, "apply", program=update),
        build("backend", 1150, 1250, "jit(ring)", thread="ring", cache="miss"),  # under the update's trace, elsewhere
        build("lower", 1300, 1350, "jit(apply)", program=update),
        build("backend", 1350, 1400, "jit(apply)", program=update, cache="hit", retrieval_s=4e-8, saved_s=1.0),
        build("lower", 1500, 1600, "jit(value_and_grad)", program=grad),  # again: committed arguments, say
        build("backend", 1600, 1700, "jit(value_and_grad)", program=grad, cache="miss"),
        build("trace", 1800, 1850, "apply", program=update),
        build("backend", 1850, 1900, "jit(apply)", program=update, cache="hit", retrieval_s=4e-8, saved_s=1.0),
        build("backend", 2100, 2200, "jit(late)", cache="miss"),  # ends inside the window
    ]
    subs = {"event": "subspan", "spans": [
        {"name": "manager_start", "parent": None, "step": 0, "t0_ns": 1010, "t1_ns": 1090, "thread": "MainThread"}]}
    path = tmp_path / "stream.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in records + [subs, {"event": "step_summary", "step": 0}]))
    monkeypatch.setenv(pb.STREAM_ENV, str(path))
    got = read_all({"steps": [{"start_mono_ns": 2000, "ms": 1.0}]})
    assert got["grad_trace_lower_s"] == pytest.approx(400e-9)  # [100, 500): what is nested in it counted once
    assert got["grad_load_s"] == pytest.approx(300e-9)  # the first build's backend stage, not the second's
    assert got["update_build_s"] == pytest.approx((300 + 100) * 1e-9)  # both builds, all three stages
    # Some build was under way for 700 + 100 + 300 + 200 + 100 ns before the window; the ring's
    # program fell inside the update's trace, the late one after the window opened.
    assert got["other_builds_s"] == pytest.approx((100 + 200) * 1e-9)
    assert sum(got[n] for n in NEW[:4]) == pytest.approx(1400e-9)
    assert got["builds_before_window"] == 7 and got["cache_misses_before_window"] == 3
    assert got["manager_start_s"] == pytest.approx(80e-9)


@pytest.mark.parametrize("kind", ["1g", "4g"])
def test_a_stream_without_build_records_reads_none_not_zero(kind, monkeypatch):
    """The parent's stream (PR 25's recording): spans, sub-spans and
    summaries, no `program_build` record and no `manager_start`."""
    with open(os.path.join(DATA, f"subspans_v5e_{kind}.steps.jsonl"), encoding="utf-8") as f:
        steps = [json.loads(line) for line in f]
    monkeypatch.setenv(pb.STREAM_ENV, os.path.join(DATA, f"subspans_v5e_{kind}.stream.jsonl"))
    assert read_all({"steps": steps, "steady_steps": steps}) == dict.fromkeys(NEW)
    monkeypatch.setenv(pb.STREAM_ENV, os.path.join(DATA, "no_such_stream.jsonl"))
    assert read_all({"steps": steps, "steady_steps": steps}) == dict.fromkeys(NEW)
    monkeypatch.setenv(pb.STREAM_ENV, os.path.join(DATA, f"builds_v5e_{kind}.stream.jsonl"))
    assert read_all({"steps": []}) == dict.fromkeys(NEW)  # no window, no "before the window"


@pytest.mark.parametrize("kind", ["1g", "4g"])
def test_the_recorded_runs_read_what_they_printed(kind, monkeypatch):
    ctx = recorded(kind, monkeypatch)
    got = read_all(ctx)
    assert got == {name: pytest.approx(value, rel=1e-9) for name, value in PINNED[kind].items()}
    records = pb.before_window(ctx)
    # The four times are parts of one union; a warm run missed nothing.
    assert sum(got[n] for n in NEW[:4]) == pytest.approx(pb.seconds(records))
    stages = pb.backend_stages(ctx)
    assert got["cache_misses_before_window"] == sum(r["cache"] == "miss" for r in stages)
    assert {r["cache"] for r in stages} == ({"hit"} if kind == "1g" else {"hit", "miss"})
    # Every record sits on the spans' clock, before the window, the Manager's step beside it.
    opens = pb.window_opens_ns(ctx)
    assert all(0 < r["t0_ns"] <= r["t1_ns"] <= opens for r in records)
    grad = pb.first_build(records, pb.GRAD_PROGRAM)
    assert [r["stage"] for r in grad] == ["trace", "lower", "backend"]
    assert [r["fun_name"] for r in grad] == ["value_and_grad", "jit(value_and_grad)", "jit(value_and_grad)"]
    assert {r["step"] for r in grad} == {None}  # built before the Manager was
    again = [r for r in records if r["program"] == pb.GRAD_PROGRAM and r not in grad]
    # Four groups: the warm-up step's arguments are committed to the device, the first call's
    # were not — JAX lowers and compiles the same function again, with the Manager's step beside it.
    assert [(r["stage"], r["step"]) for r in again] == ([] if kind == "1g" else [("lower", 1), ("backend", 1)])
