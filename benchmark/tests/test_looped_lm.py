"""The looped configuration's benchmark files on the CPU: the cell found by
files and entries alone, its two kernel counts against a hand count at the
cell's shapes, the reference against a second, loop-free writing of one pass,
the job end to end at a tiny size, and the tool that runs the three wrong
programs.  (The reference against the program leaf by leaf and the adapter's
refusals are in `tests/test_looped_lm.py`, which the tier-1 run counts.)
Nothing is timed."""

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmark.spec import Benchmark
from tiny_bench import ROOT, make_copy

BENCH = Benchmark()
CELL = "ouro-2.6b.steady-1g"
NEW_METRICS = {"loop_attn_ms", "loop_attn_roofline", "ce_loop_ms", "ce_loop_roofline", "exit_gate_ms",
               "loop_exit_step_mean", "loop_last_pass_loss_ratio"}


def test_the_cell_is_found_and_reports_its_metrics():
    """By files and entries alone; a subset check: a later PR's entries do not
    break it.  Not on the lists of `ce_roofline` and `attn_roofline`, whose
    counts are one pass's."""
    cell = BENCH.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro-2.6b", "steady-1g", 1)
    config, traffic = BENCH.config(cell["config"]), BENCH.traffic(cell["traffic"])
    assert config["architecture"] == "looped_lm" and (traffic["seq_len"], traffic["sequences_per_step"]) == (4096, 2)
    for kind in ("program", "reference", "flops"):
        assert getattr(BENCH, kind)("looped_lm") is not None
    assert {m["name"] for m in BENCH.end_to_end(CELL)} == {"tokens_per_s", "setup_s"}
    reported = {m["name"]: m for m in BENCH.per_layer(CELL)}
    assert NEW_METRICS <= set(reported) and not {"ce_roofline", "attn_roofline"} & set(reported)
    assert {"step_p90_ms.steady", "quorum_wait_ms", "ft_step_self_ms", "device_grad_ms", "device_update_ms", "grad_fwd_ms",
            "grad_bwd_ms", "grad_recompute_ms", "head_loss_ms", "attn_proj_ms", "ffn_ms", "unattributed_ms", "mfu",
            "alloc_peak_bytes"} <= set(reported)
    for name in NEW_METRICS:
        reader, entry = BENCH.reader(name), reported[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
    entry = next(c for c in BENCH.doc["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"] and entry["source"] == config["source"]
    assert config["published"] == {"num_hidden_layers": 48} and len(config["layer_types"]) == 48


def test_the_counts_against_a_hand_count_at_the_cells_shapes():
    """32 causal attention calls a direction at 2 x 16 heads of 128 over 4,096
    positions; four passes of the head's two kernels over 8,192 rows, 2,048
    columns in and 49,152 out, the backward with a scale a row; and the step's
    operations a token."""
    config, traffic = BENCH.config("ouro-2.6b"), BENCH.traffic("steady-1g")
    fa = BENCH.flops("tpuft_fa_loop").per_step(config, traffic)
    pairs = 4096 * 4097 // 2
    assert fa["flops"] == 32 * 32 * 6 * 2 * pairs * 128
    assert fa["bytes"] == 32 * 32 * (12 * 4096 * 128 * 2 + 3 * 4096 * 4)
    assert fa["flops"] == 4 * BENCH.flops("tpuft_fa").per_step(config, traffic)["flops"]
    ce = BENCH.flops("tpuft_ce_loop").per_step(config, traffic)
    assert ce["flops"] == 4 * 2 * 2 * 8192 * 2048 * 49152
    once = BENCH.flops("tpuft_ce").per_step(config, traffic)
    assert ce["flops"] == 4 * once["flops"] and ce["bytes"] == 4 * (once["bytes"] + 8192 * 4)
    flops = BENCH.flops("looped_lm")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert flops.layer_matmul_params(config) == layer == 51_380_224
    assert flops.matmul_params_applied(config) == 4 * (8 * layer + 2048 * 49152) + 3 * 2048
    assert flops.total_params(config) == 8 * (layer + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2049 == 612_438_017
    per_token = flops.train_flops_per_token(config, 4096)
    assert per_token == 6.0 * flops.matmul_params_applied(config) + 32 * 3 * 2 * 2 * 16 * 128 * 4097 / 2.0
    assert 13.8e9 < per_token < 14.0e9


def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read():
    """On a tree without the new part and counters, or under a configuration
    that is not looped, the new readers give None and do not raise."""
    ctx = {"trace": {"kernel_s_per_step": {}}, "peaks": {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
           "config": BENCH.config("ouro-2.6b"), "traffic": BENCH.traffic("steady-1g"), "bench": BENCH, "steady_steps": [],
           "steps": [], "cell": BENCH.cell(CELL)}
    for name in NEW_METRICS - {"exit_gate_ms"}:
        assert BENCH.reader(name).read(ctx) is None, name
    other = dict(ctx, config=BENCH.config("internlm2-1.8b"), trace={"kernel_s_per_step": {"attn": 1.0, "ce": 1.0}})
    for name in ("loop_attn_ms", "loop_attn_roofline", "ce_loop_ms", "ce_loop_roofline"):
        assert BENCH.reader(name).read(other) is None, name
    looped = dict(ctx, trace={"kernel_s_per_step": {"attn": 0.4, "ce": 0.1}})
    assert BENCH.reader("loop_attn_ms").read(looped) == 400.0 and 0 < BENCH.reader("loop_attn_roofline").read(looped) < 100
    assert BENCH.reader("ce_loop_ms").read(looped) == 100.0 and 0 < BENCH.reader("ce_loop_roofline").read(looped) < 100


def tiny(compute: str = "bfloat16"):
    """Two layers run three times, at 4 heads of 16."""
    return dict(
        BENCH.config("ouro-2.6b"), source="none: a test size", vocab_size=384, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, intermediate_size=96, num_hidden_layers=2, total_ut_steps=3,
        max_position_embeddings=256,
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
        program=dict(remat=True, remat_keeps_attention=False, scan_unroll=8, loop_scan=False),
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.05),
    )


def test_the_reference_against_a_loop_free_writing_of_one_pass():
    """One pass of the reference (`left_out="passes"`: the layers once, the
    final norm, the head, and the one pass takes every token) against the same
    mathematics written without a loop over passes or layers and without the
    reference's helpers: numpy, float64, two layers spelled out."""
    import jax

    reference = BENCH.reference("looped_lm")
    config = tiny("float32")
    s = reference.sizes_of(config)
    weights = reference.make_weights(5, config)
    rng = np.random.default_rng(5)
    weights = jax.tree.map(lambda l: l + 0.1 * np.asarray(rng.standard_normal(l.shape), np.float32), weights)
    tokens = rng.integers(0, config["vocab_size"], size=48)
    targets = np.roll(tokens, -1)
    got = float(reference.loss(weights, tokens, targets, s, "float32", "passes"))
    w = jax.tree.map(lambda l: np.asarray(l, np.float64), weights)
    rms = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True) + s["eps"]) * g  # noqa: E731
    seq, heads, dim = 48, s["heads"], s["head_dim"]
    angle = np.arange(seq)[:, None] * s["rope_theta"] ** (-np.arange(dim // 2) / (dim // 2))

    def rope(x):  # [S, H, D]
        a, b = x[..., : dim // 2], x[..., dim // 2:]
        cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]
        return np.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def layer(x, i):
        L = {k: v[i] for k, v in w["layers"].items()}
        u = rms(x, L["attn_norm"])
        q, k, v = (rope((u @ L["wq"]).reshape(seq, heads, dim)), rope((u @ L["wk"]).reshape(seq, heads, dim)),
                   (u @ L["wv"]).reshape(seq, heads, dim))
        scores = np.einsum("shd,thd->hst", q, k) / np.sqrt(dim)
        scores = np.where(np.tril(np.ones((seq, seq), bool))[None], scores, -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        a = np.einsum("hst,thd->shd", probs, v).reshape(seq, heads * dim) @ L["wo"]
        x = x + rms(a, L["attn_post_norm"])
        u = rms(x, L["mlp_norm"])
        gate = u @ L["w_gate"]
        m = (gate / (1 + np.exp(-gate)) * (u @ L["w_up"])) @ L["w_down"]
        return x + rms(m, L["mlp_post_norm"])

    h = rms(layer(layer(w["embed"][tokens], 0), 1), w["final_norm"])
    logits = h @ w["lm_head"]
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + logits.max(-1)
    want = float((lse - logits[np.arange(seq), targets]).mean())
    assert abs(got - want) < 2e-5 * abs(want), (got, want)
    # and the exit distribution sums to one a token, the last pass taking what is left
    lambdas = rng.uniform(0.05, 0.95, size=(3, 7)).astype(np.float32)
    p = np.asarray(reference.exit_distribution(list(lambdas)))
    assert p.shape == (4, 7) and np.allclose(p.sum(0), 1.0, atol=1e-6)
    assert np.allclose(p[3], np.prod(1 - lambdas, axis=0), rtol=1e-6) and np.allclose(p[1], lambdas[1] * (1 - lambdas[0]))


def _copy_with_a_tiny_cell(tmp_path, compute="bfloat16") -> str:
    root = make_copy(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-loop.json"), "w", encoding="utf-8") as f:
        json.dump(tiny(compute), f)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    cell = "tiny-loop.tiny-steady"
    doc["configs"].append(dict(name="tiny-loop", source="none", file="benchmark/configs/tiny-loop.json", reduced=[], why="test"))
    doc["workloads"].append(dict(name=cell, config="tiny-loop", traffic="tiny-steady", chips=1, why="test"))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return root


def test_steady_job_with_a_tiny_loop_on_the_cpu(tmp_path, monkeypatch):
    root = _copy_with_a_tiny_cell(tmp_path)
    cell = "tiny-loop.tiny-steady"
    monkeypatch.setenv("PYTHONPATH", ROOT)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # a group owns one device (tests/conftest.py asks for eight)
    bench = Benchmark(root)
    job = bench.job(bench.traffic("tiny-steady")["job"])
    seed = 2**31 + 63
    result = job.run(bench, bench.cell(cell), seed=seed, seconds=6.0, trace=True, t0_wall=time.time(), platform="cpu")
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 8 and result["failed"] == 0 and result["compiled_in_window"] == 0
    per_layer = result["per_layer"]
    assert 1.0 < per_layer["loop_exit_step_mean"] < 3.0  # three passes, the seed's gate: p near (1/2, 1/4, 1/4)
    assert 0.9 < per_layer["loop_last_pass_loss_ratio"] < 1.1
    for name in ("loop_attn_ms", "loop_attn_roofline", "ce_loop_ms", "ce_loop_roofline", "exit_gate_ms"):
        assert name not in per_layer  # no kernel runs on the CPU, and its trace books no device time to a part


def test_the_three_wrong_programs_fail_the_limit(tmp_path):
    """`tools/loop_wrong_programs.py` on the tiny cell in float32: the program
    as published passes, and one pass, no entropy term and the last pass's loss
    alone each fail — the first and the last also over the leaves that are not
    the gate's."""
    root = _copy_with_a_tiny_cell(tmp_path, "float32")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "loop_wrong_programs.py"), "--workload",
         "tiny-loop.tiny-steady", "--seed", "2147483999", "--platform", "cpu"],
        capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = {line.get("program", "all"): line for line in map(json.loads, proc.stdout.strip().splitlines())}
    assert list(lines) == ["as_published", "one_pass", "no_entropy_term", "last_pass_loss_alone", "all"]
    assert not lines["as_published"]["fails"] and lines["all"]["every_wrong_program_fails"]
    assert all(lines[name]["fails"] for name in ("one_pass", "no_entropy_term", "last_pass_loss_alone"))
    assert lines["one_pass"]["grad_rel_without_the_gate"] > 0.05 and lines["last_pass_loss_alone"]["grad_rel_without_the_gate"] > 0.05
    assert "exit_gate" in lines["no_entropy_term"]["grad_rel_worst_leaf"]
