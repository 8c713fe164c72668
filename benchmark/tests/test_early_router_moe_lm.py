"""The early-router ReGLU configuration's benchmark files on the CPU: the job
end to end on a tiny share of the experts, and the tool that counts routing
ties and ReLU's mask flips and tries the wrong programs.  (The reference
against the program leaf by leaf, the operation counts and the readers are in
`tests/test_early_router_moe.py`, which the tier-1 run counts.)  Nothing is
timed."""

import json
import os
import subprocess
import sys
import time

from benchmark.spec import Benchmark
from tiny_bench import ROOT, make_copy

BENCH = Benchmark()
CELL = "smallthinker-21b-a3b.steady-1g-16k"


def tiny(compute: str = "bfloat16"):
    """Two periods in small; experts 2-5 of the router's 8 held."""
    published = BENCH.config("smallthinker-21b-a3b")
    return dict(
        published, source="none: a test size", vocab_size=300, hidden_size=64, num_attention_heads=7,
        num_key_value_heads=1, head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=4,
        moe_num_active_primary_experts=3, sliding_window_size=64, max_position_embeddings=256,
        expert_parallel=dict(chips=2, rank=0, router_outputs=8, first_expert_held=2),
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
        program=dict(remat=True, remat_keeps_attention=True, scan_unroll=8),
        # float32: rounding only; bfloat16: rounding and, at 512 positions a layer, a top-3 choice or a unit that falls the other way
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.2),
    )


def _copy_with_a_tiny_share_cell(tmp_path, compute="bfloat16") -> str:
    root = make_copy(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-early.json"), "w", encoding="utf-8") as f:
        json.dump(tiny(compute), f)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    cell = "tiny-early.tiny-steady"
    doc["configs"].append(dict(name="tiny-early", source="none", file="benchmark/configs/tiny-early.json", reduced=[], why="test"))
    doc["workloads"].append(dict(name=cell, config="tiny-early", traffic="tiny-steady", chips=1, why="test"))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return root


def test_steady_job_with_a_tiny_share_on_the_cpu(tmp_path, monkeypatch):
    root = _copy_with_a_tiny_share_cell(tmp_path)
    cell = "tiny-early.tiny-steady"
    monkeypatch.setenv("PYTHONPATH", ROOT)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # a group owns one device (tests/conftest.py asks for eight)
    bench = Benchmark(root)
    job = bench.job(bench.traffic("tiny-steady")["job"])
    seed = 2**31 + 29
    result = job.run(bench, bench.cell(cell), seed=seed, seconds=6.0, trace=True, t0_wall=time.time(), platform="cpu")
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 8 and result["failed"] == 0 and result["compiled_in_window"] == 0
    per_layer = result["per_layer"]
    assert per_layer["moe_dropped"] == 0.0 and 1.0 <= per_layer["moe_load_max_over_mean"] < 3.0
    assert 0.4 < per_layer["reglu_active_share"] < 0.6 and 0.3 < per_layer["moe_held_share"] < 0.7
    for name in ("gmm_ms", "early_router_ms", "swa4k_attn_ms", "swa4k_attn_roofline", "full_nope_attn_ms", "full_nope_attn_roofline",
                 "gmm_reglu_roofline", "swa_pairs_share"):
        assert name not in per_layer  # no kernel runs on the CPU, and its trace books no device time to a part


def test_the_ties_tool_counts_choices_and_units_and_fails_the_wrong_programs(tmp_path):
    """`tools/routing_ties_reglu.py --wrong 1` on the tiny cell in float32: the
    program's choices are the reference's in each of the eight layers, bf16
    moves a few choices and a few units across ReLU's mask and fp8 more; each
    wrong program fails the limit that the program as published passes."""
    root = _copy_with_a_tiny_share_cell(tmp_path, "float32")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "routing_ties_reglu.py"), "--workload",
         "tiny-early.tiny-steady", "--seeds", "3,2147483999", "--wrong", "1", "--platform", "cpu"],
        capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert lines[0]["layers"] == 8 and len(lines[0]["program_vs_float32"]) == 8
    assert 0.4 < lines[0]["active_share_float32"] < 0.6
    ranges = lines[2]
    assert ranges["seeds"] == 2 and ranges["program_vs_float32"]["max"] == 0.0
    assert 0.0 < ranges["reference_bfloat16_vs_float32"]["max"] < ranges["reference_float8_vs_float32"]["max"] < 0.5
    assert 0.0 < ranges["mask_bfloat16_vs_float32"]["max"] < ranges["mask_float8_vs_float32"]["max"] < 0.5
    tried = {line["program"]: line for line in lines[3:]}
    assert list(tried) == ["as_published", "window_layers_over_the_whole_triangle", "full_layers_under_the_window",
                           "rope_on_the_full_layers", "router_on_the_experts_input", "silu_for_relu"]
    assert not tried.pop("as_published")["fails"]
    assert all(line["fails"] for line in tried.values()), tried
