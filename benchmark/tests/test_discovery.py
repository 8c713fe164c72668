"""A cell, a configuration, a traffic mix, a job kind and a per-layer metric
are each added by new files and new entries alone: nothing that is there is
edited, `run.py` least of all."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.spec import Benchmark
from benchmark.tests.tiny_bench import ROOT, make_copy

FAKE_JOB = '''"""Added by the test: a job kind that runs nothing."""


def run(bench, cell, *, seed, seconds, trace, t0_wall, **_):
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    return {
        "correct": True, "attempted": traffic["n"], "failed": 0,
        "end_to_end": {"tokens_per_s": float(config["hidden_size"]), "setup_s": 1.0, "recover_s": 2.5},
        "per_layer": {"quorum_ms": 1.0, "last_loss.tiny": float(seed)},
        "device": {"platform": "none", "kind": "none", "count": cell["chips"], "memory_peak_bytes": 1},
        "breakdown": None, "checks": {}, "compiled_in_window": 0, "samples": {}, "cache": [],
    }
'''


def test_the_committed_benchmark_is_whole():
    bench = Benchmark()
    for cell in bench.doc["workloads"]:
        config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
        assert traffic["groups"] == cell["chips"]
        assert hasattr(bench.job(traffic["job"]), "run")
        assert hasattr(bench.reference(config["architecture"]), "loss_and_grads")
        assert hasattr(bench.flops(config["architecture"]), "train_flops_per_token")
        names = {m["name"] for m in bench.end_to_end(cell["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert bench.per_layer(cell["name"])
    for metric in bench.doc["per_layer"]:
        reader = bench.reader(metric["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["moves"], metric["source"])
    four = {m["name"] for m in bench.end_to_end("internlm2-1.8b.steady-4g") + bench.per_layer("internlm2-1.8b.steady-4g")}
    assert four == {"tokens_per_s.4g", "setup_s", "quorum_ms.4g", "commit_vote_ms.4g", "exchange_exposed_ms.4g",
                    "exchange_wire_bytes", "device_step_ms.4g", "alloc_peak_bytes.4g", "mfu.4g"}
    assert "exchange_wire_bytes" not in {m["name"] for m in bench.per_layer("mistral-7b.steady-1g")}


def test_added_files_and_entries_are_found_with_no_edit(tmp_path):
    root = make_copy(str(tmp_path))
    with open(os.path.join(root, "benchmark", "jobs", "fake.py"), "w", encoding="utf-8") as f:
        f.write(FAKE_JOB)
    with open(os.path.join(root, "benchmark", "traffic", "fake-mix.json"), "w", encoding="utf-8") as f:
        json.dump({"job": "fake", "n": 7}, f)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    doc["workloads"].append(dict(name="tiny.fake-mix", config="tiny", traffic="fake-mix", chips=1, why="test"))
    doc["end_to_end"].append(dict(name="recover_s", unit="s", better="lower", bound=0.05, source="host_clock",
                                  workloads=["tiny.fake-mix"]))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if metric["name"] in ("tokens_per_s", "last_loss.tiny"):
            metric["workloads"].append("tiny.fake-mix")
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    for name in ("run.py", "spec.py", "jobs/steady.py"):  # the copied code is the committed code
        with open(os.path.join(root, "benchmark", name), "rb") as a, open(os.path.join(ROOT, "benchmark", name), "rb") as b:
            assert a.read() == b.read()

    def run(trace):
        proc = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", "tiny.fake-mix", "--seed", "41", "--seconds", "1",
             "--trace", str(trace)], cwd=root, capture_output=True, text=True, env=dict(os.environ, PYTHONPATH=ROOT))
        assert proc.returncode == 0, proc.stderr
        return json.loads(proc.stdout.strip().splitlines()[-1])

    line = run(0)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["attempted"] == 7
    assert line["metrics"] == {  # the new end-to-end metric is reported with the two every cell has
        "tokens_per_s": {"value": 256.0, "unit": "tokens/s"}, "setup_s": {"value": 1.0, "unit": "s"},
        "recover_s": {"value": 2.5, "unit": "s"}}
    traced = run(1)
    assert traced["metrics"]["last_loss.tiny"] == {"value": 41.0, "unit": "nats"}
    assert traced["metrics"]["quorum_ms"]["value"] == 1.0  # a metric with no list goes to every cell that reports what it moves
    assert "attn_roofline" not in traced["metrics"]  # one that lists other cells does not


def test_an_unknown_cell_is_an_error(tmp_path):
    with pytest.raises(KeyError):
        Benchmark().cell("no-such.cell")


def test_no_result_without_a_tpu():
    """Here JAX has the CPU alone: the command fails and prints no result."""
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "internlm2-1.8b.steady-1g", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert not any(l.startswith("{") and '"correct"' in l for l in proc.stdout.splitlines())
