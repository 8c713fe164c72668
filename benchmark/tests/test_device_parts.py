"""`benchmark/device_parts.py`: a trace's device operations booked to the parts
of the model through the program's op map — on `data/device_parts_2programs.json`,
which holds what the reader meets on the chip in small: two programs that both
have a `fusion.3`, a `while` that encloses two runs of one instruction, an
instruction the map does not know, a fusion without a name of its own, the three
directions, and a first step that is not counted.  Nothing here is timed."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import device_parts, program_spans as ps
from benchmark.spec import Benchmark
from benchmark.tests.tiny_bench import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "device_parts_2programs.json")
NEW = {
    "grad_fwd_ms": 5, "grad_bwd_ms": 5, "grad_recompute_ms": 2, "head_loss_ms": 5, "attn_proj_ms": 5, "ffn_ms": 3,
    "experts_ms": 3, "unattributed_ms": 5,
}
ONE_CHIP = ["internlm2-1.8b.steady-1g", "mistral-7b.steady-1g", "olmoe-1b-7b.steady-1g",
            "moonlight-16b-a3b.steady-1g-8k", "keye-vl-2.0-30b-a3b.steady-1g-32k"]


@pytest.fixture()
def recorded():
    with open(DATA, encoding="utf-8") as f:
        return json.load(f)


def _attributed(recorded, skip=1):
    return device_parts.attribute(recorded["ops"], recorded["modules"], recorded["op_map"],
                                  lo=recorded["steps"][skip][0])


def test_an_event_belongs_to_the_program_that_contains_it(recorded):
    found = _attributed(recorded)
    grad, update = found["jit_value_and_grad"], found["jit_apply"]
    assert grad["executions"] == update["executions"] == 2  # the first step's are before the counted steps
    # `fusion.3` is the feed-forward's product in one program and an optimizer's multiply in the other
    assert grad["instructions"]["fusion.3"]["part"] == "ffn" and grad["instructions"]["fusion.3"]["ms"] == 3.0
    assert update["instructions"]["fusion.3"]["part"] == "unattributed"
    assert update["instructions"]["fusion.3"]["ms"] == 25.0
    assert update["table"] == {} and update["unattributed_ms"] == update["device_ms"] == 28.0
    assert device_parts.attribute(recorded["ops"], recorded["modules"], recorded["op_map"])["jit_apply"]["executions"] == 3


def test_the_table_by_part_and_direction(recorded):
    grad = _attributed(recorded)["jit_value_and_grad"]
    # executions of 10 and 20 ms with the same shares: every median is 1.5 x the first's
    assert grad["program_ms"] == 15.0 and grad["device_ms"] == pytest.approx(15.0)
    assert grad["table"] == {
        "ffn": {"fwd": 3.0},
        "stack": {"fwd": 3.0},             # the `while` without the two runs of `fusion.7` it encloses
        "attn_proj": {"recompute": 3.0},   # those two runs
        "attn": {"bwd": 3.75},
        "experts": {"bwd": 1.5},           # `fusion.5` has no op_name: most of what is fused into it is the experts' backward
    }
    assert grad["by_direction"] == {"bwd": 5.25, "fwd": 6.0, "recompute": 3.0}
    assert grad["unattributed_ms"] == 0.75  # `copy.9` is not in the map
    assert grad["instructions"]["copy.9"] == {"ms": 0.75, "part": "unattributed", "direction": "fwd",
                                             "op_name": "", "opcode": None, "by": None}
    assert {n: i["by"] for n, i in grad["instructions"].items() if n != "copy.9"} == {
        "fusion.3": "name", "while.1": "name", "fusion.7": "name", "tpuft_fa_bwd_dkdv_dq.2": "name", "fusion.5": "inside"}
    assert sum(grad["by_direction"].values()) + grad["unattributed_ms"] == pytest.approx(grad["device_ms"])
    assert grad["instructions"]["fusion.3"]["straddles"] == ["ffn", "norm"]     # booked whole to its own name's part
    assert grad["instructions"]["fusion.5"]["straddles"] == ["experts", "router"]
    assert "never_ran.1" not in grad["instructions"] and "straddles" not in grad["instructions"]["fusion.7"]
    assert device_parts.median_ms(grad["per_execution"], parts=("attn", "experts")) == 5.25
    assert device_parts.median_ms(grad["per_execution"], parts=("ffn",), direction="bwd") == 0.0


def test_a_plain_map_of_op_names_reads_the_same(recorded):
    """`TrainStep.op_map()` without detail: a fusion without a name of its own is then unattributed."""
    plain = {program: {name: entry["op_name"] for name, entry in entries.items()}
             for program, entries in recorded["op_map"].items()}
    grad = device_parts.attribute(recorded["ops"], recorded["modules"], plain, lo=recorded["steps"][1][0])["jit_value_and_grad"]
    assert grad["by_direction"] == {"bwd": 3.75, "fwd": 6.0, "recompute": 3.0} and grad["unattributed_ms"] == 2.25


def _ctx(tmp_path, monkeypatch, recorded, modules=True, op_map=True):
    """A run's directory with the recorded trace in place of a profile, and
    the program's live op map as recorded (or none)."""
    run_dir = tmp_path / "cell.1.trace.run"
    run_dir.mkdir()
    with open(run_dir / "trace_events.json", "w", encoding="utf-8") as f:
        json.dump({"devices": recorded["ops"], "host": []}, f)
    monkeypatch.setenv(ps.STREAM_ENV, str(run_dir / "g0.metrics.jsonl"))
    monkeypatch.setattr(ps, "trace_path", lambda: str(run_dir / "recorded.xplane.pb"))
    monkeypatch.setattr(ps, "trace", lambda path: {"modules": recorded["modules"] if modules else {}, "annotations": [],
                                                   "steps": recorded["steps"]})
    monkeypatch.setattr(device_parts, "_live_op_map", lambda: recorded["op_map"] if op_map else None)
    monkeypatch.setattr(device_parts, "_RUNS", {})
    bench = Benchmark()
    return bench, {"cell": {"name": "cell"}, "traffic": {"trace_skip_steps": 1}}, run_dir


def test_the_readers_on_a_run(recorded, tmp_path, monkeypatch):
    bench, ctx, run_dir = _ctx(tmp_path, monkeypatch, recorded)
    got = {name: bench.reader(name).read(ctx) for name in NEW}
    assert got == {"grad_fwd_ms": 6.0, "grad_bwd_ms": 5.25, "grad_recompute_ms": 3.0, "head_loss_ms": None,
                   "attn_proj_ms": 3.0, "ffn_ms": 3.0, "experts_ms": 1.5, "unattributed_ms": 0.75}
    with open(run_dir / device_parts.FILE, encoding="utf-8") as f:
        left = json.load(f)
    assert left["cell"] == "cell" and left["op_map"] == recorded["op_map"]
    assert left["programs"]["jit_value_and_grad"]["table"]["attn"] == {"bwd": 3.75}
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tools", "parts.py"), str(run_dir), "--top", "2"],
                         capture_output=True, text=True, check=True).stdout
    assert "attn_proj" in out and "unattributed" in out and "tpuft_fa_bwd_dkdv_dq.2" in out
    assert "fusions that hold more than one part: 2, 4.500 ms" in out
    assert "[+norm]" in out and "jit(value_and_grad)/jvp(stack)/ffn/dot_general" in out
    assert "booked by what is fused into them: 1, 1.500 ms" in out and "(by what is fused into it)" in out


@pytest.mark.parametrize("missing", ["device_plane", "op_map", "trace", "counted_steps"])
def test_every_reader_gives_nothing_where_there_is_nothing_to_read(recorded, tmp_path, monkeypatch, missing):
    """A CPU rehearsal's trace has no device plane; the parent of the PR that
    added the op map has none; an untraced run has no profile; a trace may end
    before the first counted step.  None, and no file, in each."""
    bench, ctx, run_dir = _ctx(tmp_path, monkeypatch, recorded, modules=missing != "device_plane",
                               op_map=missing != "op_map")
    if missing == "trace":
        monkeypatch.setattr(ps, "trace_path", lambda: None)
    if missing == "counted_steps":
        ctx["traffic"]["trace_skip_steps"] = 3
    assert {name: bench.reader(name).read(ctx) for name in NEW} == dict.fromkeys(NEW)
    assert not (run_dir / device_parts.FILE).exists()


def test_the_program_without_an_op_map_is_met_by_an_import_error(monkeypatch):
    """What `_live_op_map` does on the parent: `torchft_tpu.obs.opmap` is not there."""
    monkeypatch.setitem(sys.modules, "torchft_tpu.obs.opmap", None)
    assert device_parts._live_op_map() is None


def test_the_new_entries_and_their_readers():
    bench = Benchmark()
    by_name = {m["name"]: m for m in bench.doc["per_layer"]}
    assert [m["name"] for m in bench.doc["per_layer"][-len(NEW):]] == list(NEW)  # appended, in this order
    for name, cells in NEW.items():
        reader, metric = bench.reader(name), by_name[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["moves"], metric["source"]) == ("model", "ms", "tokens_per_s", "device_trace")
        assert reader.__doc__ and callable(reader.read) and metric["better"] == "lower"
        assert len(metric["workloads"]) == cells and set(metric["workloads"]) <= set(ONE_CHIP)
    assert by_name["grad_recompute_ms"]["workloads"] == ONE_CHIP[3:]  # the two cells that rematerialise
    assert by_name["ffn_ms"]["workloads"] == [ONE_CHIP[0], ONE_CHIP[1], ONE_CHIP[3]]
    assert by_name["experts_ms"]["workloads"] == ONE_CHIP[2:]
    assert not set(NEW) & {m["name"] for m in bench.per_layer("internlm2-1.8b.steady-4g")}
