"""The block-diffusion configuration's benchmark files on the CPU: the cell
found by files and entries alone, its counts against a hand count at the cell's
shapes, the reference's mask and loss against a second, loop-free writing in
numpy, the job end to end at a tiny size, and the tool that reads the routing
and runs the five wrong mechanisms.  (The reference against the program leaf by
leaf and the adapter's refusals are in `tests/test_bd_moe.py`, which the tier-1
run counts.)  Nothing is timed."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark.spec import Benchmark
from tiny_bench import ROOT, make_copy

BENCH = Benchmark()
CELL = "sdar-30b-a3b.steady-1g-16k"
NEW_METRICS = {"bd_attn_ms", "bd_attn_roofline", "bd_noise_ms", "bd_masked_share", "bd_live_pairs_share", "gmm_bd_roofline"}


def test_the_cell_is_found_and_reports_its_metrics():
    """By files and entries alone; a subset check: a later PR's entries do not
    break it.  Not on the list of any `attn` roofline: no `tpuft_fa_*` runs here."""
    cell = BENCH.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdar-30b-a3b", "steady-1g-16k", 1)
    config, traffic = BENCH.config(cell["config"]), BENCH.traffic(cell["traffic"])
    assert config["architecture"] == "bd_moe_lm" and (traffic["seq_len"], traffic["sequences_per_step"]) == (16384, 1)
    for kind in ("program", "reference", "flops"):
        assert getattr(BENCH, kind)("bd_moe_lm") is not None
    assert {m["name"] for m in BENCH.end_to_end(CELL)} == {"tokens_per_s", "setup_s"}
    reported = {m["name"]: m for m in BENCH.per_layer(CELL)}
    assert NEW_METRICS <= set(reported) and not {"attn_roofline", "dsa_attn_roofline", "ce_loop_roofline"} & set(reported)
    assert {"step_p90_ms.steady", "quorum_wait_ms", "ft_step_self_ms", "device_grad_ms", "device_update_ms", "grad_fwd_ms",
            "grad_bwd_ms", "grad_recompute_ms", "head_loss_ms", "attn_proj_ms", "experts_ms", "unattributed_ms", "gmm_ms",
            "moe_load_max_over_mean", "moe_dropped", "moe_held_share", "ce_roofline", "mfu", "alloc_peak_bytes"} <= set(reported)
    for name in NEW_METRICS:
        reader, entry = BENCH.reader(name), reported[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
    entry = next(c for c in BENCH.doc["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert config["expert_parallel"]["router_outputs"] == 128 and config["num_experts"] == 16


def test_the_published_widths_are_the_catalogs():
    """Every number of the catalog's `config` under the same key, but the three the file lists as reduced."""
    catalog = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128, hidden_act="silu", hidden_size=2048,
        intermediate_size=6144, max_position_embeddings=32768, max_window_layers=48, mlp_only_layers=[],
        model_type="sdar_moe", moe_intermediate_size=768, norm_topk_prob=True, num_attention_heads=32, num_experts=128,
        num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False, use_sliding_window=False, vocab_size=151936)
    config = BENCH.config("sdar-30b-a3b")
    differs = sorted(key for key, value in catalog.items() if config[key] != value)
    assert differs == sorted(config["reduced"])
    assert all(config["published"][key] == catalog[key] for key in config["reduced"])


def test_the_counts_against_a_hand_count_at_the_cells_shapes():
    """The live pairs of 16,384 tokens in blocks of 4, the two kernels' six
    products over them a layer at 32 heads of 128, and the step's operations a
    DATA token: two positions through every layer, one row through the head."""
    config, traffic = BENCH.config("sdar-30b-a3b"), BENCH.traffic("steady-1g-16k")
    flops = BENCH.flops("bd_moe_lm")
    pairs = flops.live_pairs(16384, 4)
    assert pairs == 16384 * 16384 + 16384 * 4 == 268_500_992
    assert abs(pairs / 32768 ** 2 - 0.2501) < 1e-4
    layers = config["num_hidden_layers"]
    bd = BENCH.flops("tpuft_bd").per_step(config, traffic)
    assert bd["flops"] == layers * 32 * 6 * 2 * pairs * 128
    tensor, stats = 32768 * 128 * 2, 32768 * 4
    assert bd["bytes"] == layers * (32 * (8 * tensor + 3 * stats) + 4 * 4 * tensor)
    attention = 2048 * 128 * (2 * 32 + 2 * 4)
    assert flops.attention_params(config) == attention == 18_874_368 and flops.expert_params(config) == 4_718_592
    assert flops.held_experts_per_position(config) == 1.0
    layer = attention + 2048 * 128 + 4_718_592
    assert flops.matmul_params_per_token(config) == 2 * layers * layer + 2048 * 18992
    per_token = flops.train_flops_per_token(config, 16384)
    assert per_token == 6.0 * (2 * layers * layer + 2048 * 18992) + layers * 6 * 2 * 128 * 32 * pairs / 16384
    whole = dict(config, num_hidden_layers=48, num_experts=128, vocab_size=151936, expert_parallel=None)
    assert flops.total_params(whole) == 30_532_122_624
    assert flops.total_params(dict(config, num_hidden_layers=4)) == 456_346_624
    assert flops.total_params(config) == 456_346_624 + (layers - 4) * 94_638_336


def test_a_reader_finds_nothing_where_the_program_has_nothing_to_read():
    """On a tree without the new kernels, part and counters, or under a
    configuration that states no block diffusion, the new readers give None and
    do not raise."""
    ctx = {"trace": {"kernel_s_per_step": {}}, "peaks": {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
           "config": BENCH.config("sdar-30b-a3b"), "traffic": BENCH.traffic("steady-1g-16k"), "bench": BENCH,
           "steady_steps": [], "steps": [], "cell": BENCH.cell(CELL)}
    for name in NEW_METRICS - {"bd_noise_ms"}:
        assert BENCH.reader(name).read(ctx) is None, name
    other = dict(ctx, config=BENCH.config("keye-vl-2.0-30b-a3b"), trace={"kernel_s_per_step": {"bd_attn": 1.0}})
    assert BENCH.reader("bd_attn_roofline").read(other) is None
    here = dict(ctx, trace={"kernel_s_per_step": {"bd_attn": 0.6}})
    assert BENCH.reader("bd_attn_ms").read(here) == 600.0 and 0 < BENCH.reader("bd_attn_roofline").read(here) < 100
    names = BENCH.program("bd_moe_lm").kernel_names()
    assert names["bd_attn"]("tpuft_bd_fwd.3") and names["bd_attn"]("tpuft_bd_bwd_dkdv_dq")
    assert not names["attn"]("tpuft_bd_fwd") and not names["bd_attn"]("tpuft_fa_fwd") and names["gmm"]("tpuft_gmm_drhs.1")


def test_the_grouped_matmuls_roofline_reads_the_held_rows_of_a_recorded_step(tmp_path, monkeypatch):
    """`gmm_bd_roofline`: the median of the steady steps' `moe_rows_held` through `flops/tpuft_gmm_wide.py` at this
    file's keys (16 held experts of 2,048 x 768 a layer) over the `tpuft_gmm_*` time; nothing under another family's
    configuration, without the kernels or without the counter."""
    stream = tmp_path / "g0.metrics.jsonl"
    stream.write_text("".join(json.dumps(dict(event="step_summary", t_mono=1.0 + i, step=i, moe_rows_held=rows, moe_assignments=1_310_720))
                              + "\n" for i, rows in enumerate((160_000, 163_840, 170_000))))
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(stream))
    config = BENCH.config("sdar-30b-a3b")
    ctx = {"trace": {"kernel_s_per_step": {"gmm": 0.06}}, "peaks": BENCH.peaks("TPU v5 lite"), "bench": BENCH, "config": config,
           "traffic": BENCH.traffic("steady-1g-16k"), "steady_steps": [{"start_mono_ns": 0.5e9, "ms": 10_000.0}]}
    need = BENCH.flops("tpuft_gmm_wide").per_step(config, 163_840)
    assert need["flops"] == 9 * 2.0 * 163_840 * 2048 * 768
    reader = BENCH.reader("gmm_bd_roofline")
    assert reader.read(ctx) == pytest.approx(100 * need["flops"] / 197e12 / 0.06) and 0 < reader.read(ctx) < 100
    assert reader.read(dict(ctx, config=BENCH.config("zaya1-8b"))) is None
    assert reader.read(dict(ctx, trace={"kernel_s_per_step": {}})) is None
    assert reader.read(dict(ctx, steady_steps=[])) is None


def tiny(compute: str = "bfloat16"):
    """Two layers at 4 heads on 2 KV heads of 32, 8 experts of which 4 are held, blocks of 4."""
    return dict(
        BENCH.config("sdar-30b-a3b"), source="none: a test size", vocab_size=384, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, moe_intermediate_size=48, num_hidden_layers=2, num_experts=4,
        num_experts_per_tok=2, max_position_embeddings=512,
        expert_parallel=dict(chips=2, rank=1, router_outputs=8, first_expert_held=4),
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
        program=dict(remat=True, remat_keeps_attention=True, scan_unroll=8),
        correct=dict(grad_rel_limit=2e-4 if compute == "float32" else 0.08),
    )


def test_the_reference_against_a_loop_free_writing():
    """The reference's loss on one sequence against the same mathematics
    written without its helpers: numpy, float64, the mask pair by pair from the
    published rule, every expert held, two layers spelled out."""
    import jax

    reference = BENCH.reference("bd_moe_lm")
    config = dict(tiny("float32"), num_experts=8, expert_parallel=None)
    s = reference.sizes_of(config)
    weights = reference.make_weights(5, config)
    rng = np.random.default_rng(5)
    weights = jax.tree.map(lambda l: l + 0.1 * np.asarray(rng.standard_normal(l.shape), np.float32), weights)
    L, b = 24, 4
    tokens = rng.integers(0, config["vocab_size"], size=L)
    got = float(reference.loss(weights, tokens, None, s, "float32"))
    m, t = (np.asarray(a) for a in reference.noise(tokens, b, s["noise_seed"]))
    assert m.shape == t.shape == (L,) and (t.reshape(-1, b) == t.reshape(-1, b)[:, :1]).all() and 0 < m.sum() < L
    w = jax.tree.map(lambda l: np.asarray(l, np.float64), weights)
    ids = np.concatenate([np.where(m, config["vocab_size"] - 1, tokens), tokens])
    sees = np.zeros((2 * L, 2 * L), bool)
    for i in range(2 * L):
        for j in range(2 * L):
            bi, bj = (i % L) // b, (j % L) // b
            if i < L:
                sees[i, j] = (bj == bi) if j < L else (bj < bi)
            else:
                sees[i, j] = j >= L and bj <= bi
    assert sees.sum() == L * L + L * b
    rms = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True) + s["eps"]) * g  # noqa: E731
    heads, kv, dim = s["heads"], s["kv_heads"], s["head_dim"]
    angle = (np.arange(2 * L) % L)[:, None] * s["rope_theta"] ** (-np.arange(dim // 2) / (dim // 2))

    def rope(x):  # [P, H, D]
        a, c = x[..., : dim // 2], x[..., dim // 2:]
        cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]
        return np.concatenate([a * cos - c * sin, a * sin + c * cos], -1)

    balance = 0.0

    def layer(x, i):
        nonlocal balance
        W = {k: v[i] for k, v in w["layers"].items()}
        u = rms(x, W["attn_norm"])
        q = rope(rms((u @ W["wq"]).reshape(2 * L, heads, dim), W["q_norm"]))
        k = rope(rms((u @ W["wk"]).reshape(2 * L, kv, dim), W["k_norm"]))
        v = (u @ W["wv"]).reshape(2 * L, kv, dim)
        k, v = np.repeat(k, heads // kv, axis=1), np.repeat(v, heads // kv, axis=1)
        scores = np.where(sees[None], np.einsum("shd,thd->hst", q, k) / np.sqrt(dim), -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        x = x + np.einsum("hst,thd->shd", probs, v).reshape(2 * L, heads * dim) @ W["wo"]
        u = rms(x, W["mlp_norm"])
        logits = u @ W["router"]
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        top = np.argsort(-p, axis=-1)[:, : s["top_k"]]
        y = np.zeros_like(x)
        for pos in range(2 * L):
            gates = p[pos, top[pos]] / p[pos, top[pos]].sum()
            for g, e in zip(gates, top[pos]):
                a = u[pos] @ W["w_gate"][e]
                y[pos] += g * ((a / (1 + np.exp(-a)) * (u[pos] @ W["w_up"][e])) @ W["w_down"][e])
        share = np.bincount(top.reshape(-1), minlength=s["experts"]) / (2 * L)
        balance += s["experts"] * (share * p.mean(0)).sum()
        return x + y

    h = rms(layer(layer(w["embed"][ids], 0), 1)[:L], w["final_norm"])
    logits = h @ w["lm_head"]
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + logits.max(-1)
    ce = lse - logits[np.arange(L), tokens]
    want = float((np.where(m, 1.0 / t, 0.0) * ce).mean() + s["aux_coef"] * balance)
    assert abs(got - want) < 2e-5 * abs(want), (got, want)


def _copy_with_a_tiny_cell(tmp_path, compute="bfloat16") -> str:
    root = make_copy(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-bd.json"), "w", encoding="utf-8") as f:
        json.dump(tiny(compute), f)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    cell = "tiny-bd.tiny-steady"
    doc["configs"].append(dict(name="tiny-bd", source="none", file="benchmark/configs/tiny-bd.json", reduced=[], why="test"))
    doc["workloads"].append(dict(name=cell, config="tiny-bd", traffic="tiny-steady", chips=1, why="test"))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return root


def test_steady_job_with_a_tiny_block_diffusion_cell_on_the_cpu(tmp_path, monkeypatch):
    root = _copy_with_a_tiny_cell(tmp_path)
    cell = "tiny-bd.tiny-steady"
    monkeypatch.setenv("PYTHONPATH", ROOT)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # a group owns one device (tests/conftest.py asks for eight)
    bench = Benchmark(root)
    job = bench.job(bench.traffic("tiny-steady")["job"])
    seed = 2**31 + 66
    result = job.run(bench, bench.cell(cell), seed=seed, seconds=6.0, trace=True, t0_wall=time.time(), platform="cpu")
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 8 and result["failed"] == 0 and result["compiled_in_window"] == 0
    per_layer = result["per_layer"]
    assert 0.3 < per_layer["bd_masked_share"] < 0.7  # 2 x 256 data tokens in 128 blocks: near a half
    assert per_layer["bd_live_pairs_share"] == np.float32((256 * 256 + 256 * 4) / 512 ** 2)
    assert per_layer["moe_dropped"] == 0 and 0 < per_layer["moe_held_share"] < 1
    for name in ("bd_attn_ms", "bd_attn_roofline", "bd_noise_ms"):
        assert name not in per_layer  # no kernel runs on the CPU, and its trace books no device time to a part
    # a committed step counts its DATA tokens, not the positions the model runs
    assert result["end_to_end"]["tokens_per_s"] > 0
    with open(os.path.join(root, "benchmark", "out", f"{cell}.{seed}.trace.run", "g0.result.json"), encoding="utf-8") as f:
        group = json.load(f)
    assert group["committed_tokens"] == group["committed"] * 2 * 256


def test_the_tool_reads_the_routing_and_the_five_wrong_mechanisms_fail_the_limit(tmp_path):
    """`tools/routing_ties_bd.py` on the tiny cell in float32: the program's
    choices are the reference's but for near-ties, nothing is dropped, and the
    reference with a causal mask over 2 L, without the clean half, without the
    1 / t weight, with the shift or with RoPE positions 0 .. 2 L - 1 each fails."""
    root = _copy_with_a_tiny_cell(tmp_path, "float32")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "routing_ties_bd.py"), "--workload",
         "tiny-bd.tiny-steady", "--seeds", "2147483999,5", "--left-out", "1", "--platform", "cpu"],
        capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    seeds, ranges, wrong = lines[:2], lines[2], lines[3:]
    for line in seeds:
        assert line["choices_a_layer"] == 2 * 2 * 256 * 2 and max(line["program_vs_float32"]) < 0.01
        assert line["dropped"] == 0 and 0 < line["rows_held_over_buffer"] < 1 and 0.3 < line["bd_masked_share"] < 0.7
        assert len(line["mask_rows_experts_held"]) == 2 and all(0 <= n <= 2 for n in line["mask_rows_experts_held"])
    assert ranges["seeds"] == 2 and ranges["dropped"] == {"min": 0, "max": 0}
    assert [line["left_out"] for line in wrong] == ["causal", "clean_half", "weight", "shift", "rope"]
    assert all(line["fails"] and line["grad_rel"] > 0.05 for line in wrong), wrong
