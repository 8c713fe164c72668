"""The Mamba-2 / attention / un-gated-expert configuration's benchmark files on
the CPU: the cell found by files and entries alone, the job end to end on a
tiny share of the experts, and the tool that counts routing ties and leaves the
pieces out.  (The reference against the program leaf by leaf, the operation
counts and the adapter's refusals are in `tests/test_mamba2_moe.py`, which the
tier-1 run counts.)  Nothing is timed."""

import json
import os
import subprocess
import sys
import time

from benchmark.spec import Benchmark
from tiny_bench import ROOT, make_copy

BENCH = Benchmark()
CELL = "nemotron-twotower-30b-a3b.steady-1g-16k"
NEW_METRICS = {"ssm_scan_ms", "ssm_scan_roofline", "ssm_mix_ms", "ssm_decay_mean", "gmm_relu2_roofline",
               "relu2_active_share", "gqa16_attn_ms", "gqa16_attn_roofline"}


def test_the_cell_is_found_and_reports_its_metrics():
    """By files and entries alone: the configuration's file and its
    architecture's program, reference and operation counts; the traffic file the
    benchmark had; every per-layer metric that lists the cell has a reader whose
    header agrees with its entry.  A subset check: a later PR's entries do not
    break it."""
    cell = BENCH.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("nemotron-twotower-30b-a3b", "steady-1g-16k", 1)
    config, traffic = BENCH.config(cell["config"]), BENCH.traffic(cell["traffic"])
    assert config["architecture"] == "mamba2_moe_lm" and (traffic["seq_len"], traffic["sequences_per_step"]) == (16384, 1)
    for kind in ("program", "reference", "flops"):
        assert getattr(BENCH, kind)("mamba2_moe_lm") is not None
    assert {m["name"] for m in BENCH.end_to_end(CELL)} == {"tokens_per_s", "setup_s"}
    reported = {m["name"]: m for m in BENCH.per_layer(CELL)}
    assert NEW_METRICS <= set(reported)
    assert {"step_p90_ms.steady", "ce_roofline", "quorum_wait_ms", "ft_step_self_ms", "device_grad_ms", "device_update_ms",
            "gmm_ms", "moe_load_max_over_mean", "moe_dropped", "moe_held_share", "grad_fwd_ms", "grad_bwd_ms",
            "grad_recompute_ms", "head_loss_ms", "attn_proj_ms", "experts_ms", "unattributed_ms", "mfu",
            "alloc_peak_bytes"} <= set(reported)
    for name in NEW_METRICS:
        reader, entry = BENCH.reader(name), reported[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
    entry = next(c for c in BENCH.doc["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]


def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read():
    """On a tree without the new kernels, parts and counters the new readers
    give None and do not raise."""
    config = BENCH.config("nemotron-twotower-30b-a3b")
    ctx = {"trace": {"kernel_s_per_step": {}}, "peaks": {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
           "config": config, "traffic": BENCH.traffic("steady-1g-16k"), "bench": BENCH, "steady_steps": [], "steps": [],
           "cell": BENCH.cell(CELL)}
    for name in NEW_METRICS - {"ssm_mix_ms"}:
        assert BENCH.reader(name).read(ctx) is None, name
    other = dict(ctx, config=BENCH.config("kimi-linear-48b-a3b"), trace={"kernel_s_per_step": {"attn": 1.0, "gmm": 1.0}})
    for name in ("gqa16_attn_ms", "gqa16_attn_roofline", "gmm_relu2_roofline", "relu2_active_share"):
        assert BENCH.reader(name).read(other) is None, name


def tiny(compute: str = "bfloat16"):
    """The nine blocks in small; experts 2-5 of the router's 8 held."""
    published = BENCH.config("nemotron-twotower-30b-a3b")
    return dict(
        published, source="none: a test size", vocab_size=300, hidden_size=64, mamba_num_heads=4, mamba_head_dim=16,
        n_groups=2, ssm_state_size=16, chunk_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=24, moe_shared_expert_intermediate_size=48, n_routed_experts=4, num_experts_per_tok=2,
        max_position_embeddings=256,
        expert_parallel=dict(chips=2, rank=0, router_outputs=8, first_expert_held=2),
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
        program=dict(remat=True, remat_keeps_attention=True, scan_unroll=16),
        # float32: rounding only; bfloat16: rounding and, at 512 positions a block, a top-2 choice that falls the other way
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.2),
    )


def _copy_with_a_tiny_share_cell(tmp_path, compute="bfloat16") -> str:
    root = make_copy(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-mamba2.json"), "w", encoding="utf-8") as f:
        json.dump(tiny(compute), f)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    cell = "tiny-mamba2.tiny-steady"
    doc["configs"].append(dict(name="tiny-mamba2", source="none", file="benchmark/configs/tiny-mamba2.json", reduced=[], why="test"))
    doc["workloads"].append(dict(name=cell, config="tiny-mamba2", traffic="tiny-steady", chips=1, why="test"))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return root


def test_steady_job_with_a_tiny_share_on_the_cpu(tmp_path, monkeypatch):
    root = _copy_with_a_tiny_share_cell(tmp_path)
    cell = "tiny-mamba2.tiny-steady"
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
    assert 0.3 < per_layer["relu2_active_share"] < 0.7 and 0.3 < per_layer["moe_held_share"] < 0.7
    assert 0.0 < per_layer["ssm_decay_mean"] < 1.0
    for name in ("gmm_ms", "ssm_scan_ms", "ssm_scan_roofline", "ssm_mix_ms", "gqa16_attn_ms", "gqa16_attn_roofline",
                 "gmm_relu2_roofline"):
        assert name not in per_layer  # no kernel runs on the CPU, and its trace books no device time to a part


def test_the_ties_tool_counts_choices_and_fails_a_model_without_a_piece(tmp_path):
    """`tools/routing_ties_mamba2.py --left-out 1` on the tiny cell in float32:
    the program's choices are the reference's in each of the four expert
    blocks, bf16 moves a few and fp8 more; the reference without each piece
    fails the limit."""
    root = _copy_with_a_tiny_share_cell(tmp_path, "float32")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "routing_ties_mamba2.py"), "--workload",
         "tiny-mamba2.tiny-steady", "--seeds", "3,2147483999", "--left-out", "1", "--platform", "cpu"],
        capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert len(lines[0]["program_vs_float32"]) == 4 and 0.0 < lines[0]["alpha"]["mean"] < 1.0
    ranges = lines[2]
    assert ranges["seeds"] == 2 and ranges["program_vs_float32"]["max"] == 0.0
    assert 0.0 < ranges["reference_bfloat16_vs_float32"]["max"] <= ranges["reference_float8_vs_float32"]["max"] < 0.5
    left_out = {line["left_out"]: line for line in lines[3:]}
    assert list(left_out) == ["decay", "skip", "convolution", "gate", "group_norm", "square", "route_scale", "shared_expert"]
    assert all(line["fails"] for line in left_out.values()), left_out
