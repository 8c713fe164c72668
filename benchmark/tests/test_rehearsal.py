"""The `steady` job end to end at a tiny size on the CPU backend: one group,
and four groups as four processes.  Nothing is timed: a CPU run says what the
program counts and whether the control flow is right, never a device number."""

import glob
import json
import os
import time

import pytest

from benchmark import trace_reduce
from benchmark.spec import Benchmark
from benchmark.tests.tiny_bench import ROOT, make_copy


@pytest.mark.parametrize("groups", [1, 4])
def test_steady_job_on_the_cpu(tmp_path, monkeypatch, groups):
    root = make_copy(str(tmp_path), groups)
    monkeypatch.setenv("PYTHONPATH", ROOT)  # the groups find the program; the benchmark is the copy's
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    bench = Benchmark(root)
    cell = bench.cell("tiny.tiny-steady")
    job = bench.job(bench.traffic(cell["traffic"])["job"])
    seed = 2**31 + 17  # the driver's seeds pass 32 signed bits
    result = job.run(bench, cell, seed=seed, seconds=6.0, trace=True, t0_wall=time.time(), platform="cpu")
    assert result["correct"], result["checks"]
    assert all(result["checks"][f"g{g}.reference"]["ok"] for g in range(groups))
    assert result["attempted"] >= 8 and result["failed"] == 0
    assert result["compiled_in_window"] == 0, result["checks"]["compiles_in_window"]
    assert result["device"]["count"] == groups and result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    wanted = {"quorum_ms", "commit_vote_ms", "exchange_exposed_ms", "device_step_ms"}
    if groups == 1:
        wanted |= {"step_p90_ms.steady", "last_loss.tiny"}
        assert set(result["end_to_end"]) == {"tokens_per_s", "setup_s"}
    else:
        wanted = {name + ".4g" for name in wanted} | {"exchange_wire_bytes", "last_loss.tiny"}
        assert set(result["end_to_end"]) == {"tokens_per_s.4g", "setup_s"}
        assert result["checks"]["digests_identical"]["ok"] and result["checks"]["g3.avg_vs_mean"]["ok"]
    assert wanted <= set(result["per_layer"]), result["per_layer"]
    assert result["breakdown"]["device_ops"] and result["breakdown"]["idle_gaps"]
    steps_path = os.path.join(root, "benchmark", "out", f"tiny.tiny-steady.{seed}.trace.steps.jsonl")
    with open(steps_path, encoding="utf-8") as f:
        steps = [json.loads(l) for l in f]
    assert len(steps) == result["attempted"]
    assert all(s["committed"] and "quorum" in s["spans"] and "commit_vote" in s["spans"] for s in steps)
    assert sum(s["ms"] for s in steps) / 1e3 <= 6.0  # whole steps only, none past the window
    assert sum(1 for s in steps if s["traced"]) == bench.traffic("tiny-steady")["trace_steps"]
    if groups == 1:
        # This trace has host threads alone.  Read as a chip's it is refused:
        # no host event is ever reported as the device's.
        (xplane,) = glob.glob(os.path.join(root, "benchmark", "out", "*.run", "g0.trace", "plugins", "profile", "*", "*.xplane.pb"))
        assert trace_reduce.load(xplane, "cpu")["devices"]
        with pytest.raises(RuntimeError, match="no /device:TPU: plane"):
            trace_reduce.load(xplane, "tpu")
