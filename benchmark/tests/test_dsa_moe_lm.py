"""The sparse-attention configuration's benchmark files on the CPU: the plain
reference against float64, against itself whole, and against the program at a
tiny size; the configuration against the catalog; the operation counts against
numbers worked by hand at the cell's sizes; the new readers on what they read
and on nothing; and the `steady` job end to end with a tiny Keye-shaped share.
Nothing is timed."""

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.spec import Benchmark
from benchmark.tests.tiny_bench import ROOT, make_copy

BENCH = Benchmark()
REFERENCE = BENCH.reference("dsa_moe_lm")
PROGRAM = BENCH.program("dsa_moe_lm")
SEEDS = (3, 2**31 + 5, 77)
CELL = "keye-vl-2.0-30b-a3b.steady-1g-32k"
NEW_METRICS = ("dsa_attn_ms", "dsa_attn_roofline", "dsa_index_ms", "dsa_index_roofline", "dsa_selected_share")


def tiny(compute: str = "float32", **changed):
    """Two layers; 2 of the router's 8 experts held; 32 of up to 128 keys kept."""
    config = dict(
        source="none: a test size", architecture="dsa_moe_lm", vocab_size=520, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64, intermediate_size=256, moe_intermediate_size=64,
        num_experts=2, num_local_experts=2, num_experts_per_tok=2, norm_topk_prob=True, decoder_sparse_step=1,
        mlp_only_layers=[], hidden_act="silu", attention_bias=False, tie_word_embeddings=False, sliding_window=None,
        use_sliding_window=False, rope_scaling=dict(mrope_section=[8, 12, 12], rope_type="default", type="default"),
        sa_config=dict(indexer_head_dim=32, indexer_num_heads=4, indexer_num_kv_heads=1, kv_chunk_size=512,
                       q_chunk_size=512, topk=32),
        max_position_embeddings=256, rope_theta=1e7, rms_norm_eps=1e-6, router_aux_loss_coef=0.001,
        expert_parallel=dict(chips=4, rank=1, router_outputs=8, first_expert_held=2),
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
        program=dict(remat=True, remat_keeps_attention=True, scan_unroll=4),
        # float32: rounding only; bfloat16: rounding and, at this size, a selected key or an expert or two that fall
        # the other way
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.15),
    )
    config.update(changed)
    return config


def one_step(config, seed, seq_len=128):
    weights = REFERENCE.make_weights(seed, config)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, config["vocab_size"], size=(2, seq_len)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(np.roll(tokens, -1, axis=1))}
    (loss, counters), grads = jax.jit(jax.value_and_grad(PROGRAM.loss(config), has_aux=True))(weights, batch)
    return weights, batch, loss, grads, counters


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_program_agrees_with_the_reference(seed):
    config = tiny("float32")
    weights, batch, loss, grads, counters = one_step(config, seed)
    indices = compare.sample_indices(seed, weights)
    check = compare.against_reference(REFERENCE, config, weights, batch, loss, compare.sample(grads, indices), indices)
    assert check["ok"] and check["grad_rel"] < 3e-5 and check["loss_rel"] < 2e-6, check
    assert int(counters["dsa_pairs_selected"]) == 2 * 2 * (32 * 33 // 2 + 96 * 32)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_fp8_control_is_further_from_the_reference_than_bf16(seed):
    config = tiny("bfloat16")
    weights, batch, loss, grads, _ = one_step(config, seed)
    indices = compare.sample_indices(seed, weights)
    sound = compare.against_reference(REFERENCE, config, weights, batch, loss, compare.sample(grads, indices), indices)
    closs, csample = compare.sequence_by_sequence(REFERENCE, config, weights, batch, indices, "float8")
    control = compare.against_reference(REFERENCE, config, weights, batch, closs, csample, indices)
    assert np.isfinite(sound["grad_rel"]) and sound["grad_rel"] < control["grad_rel"], (sound, control)
    assert sound["ok"], sound


def test_reference_in_float32_agrees_with_itself_in_float64():
    config = tiny("float32")
    weights, batch, *_ = one_step(config, 5, seq_len=64)
    tokens, targets = batch["tokens"][0], batch["targets"][0]
    loss32, grads32 = REFERENCE.one_sequence_fn(config)(weights, tokens, targets)
    with jax.enable_x64():
        w64 = jax.tree.map(lambda x: jnp.asarray(np.asarray(x), jnp.float64), weights)
        s = REFERENCE.sizes_of(config)
        loss64, grads64 = jax.value_and_grad(functools.partial(REFERENCE.loss, s=s))(w64, tokens, targets)
    assert abs(float(loss32) - float(loss64)) < 2e-6 * float(loss64)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads32), jax.tree.leaves(grads64)):
        a, b = np.asarray(a, np.float64), np.asarray(b)
        assert np.linalg.norm(a - b) < 3e-5 * np.linalg.norm(b), jax.tree_util.keystr(path)


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_the_block_by_block_gradient_is_the_gradient_of_the_whole_loss(precision):
    """`one_sequence_fn` writes the chain rule out over the blocks (so that
    32,768 positions fit); differentiating `loss` whole gives the same."""
    config = tiny("float32")
    weights, batch, *_ = one_step(config, 9)
    tokens, targets = batch["tokens"][1], batch["targets"][1]
    loss, grads = REFERENCE.one_sequence_fn(config, precision)(weights, tokens, targets)
    s = REFERENCE.sizes_of(config)
    whole_loss, whole = jax.value_and_grad(functools.partial(REFERENCE.loss, s=s, precision=precision))(
        weights, tokens, targets)
    assert abs(float(loss) - float(whole_loss)) < 1e-5 * float(whole_loss)
    limit = 1e-5 if precision == "float32" else 0.01  # rounded operands: a last bit that falls the other way is seen
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(whole)):
        assert float(jnp.linalg.norm(a - b)) <= limit * float(jnp.linalg.norm(b)), jax.tree_util.keystr(path)


def test_reference_attends_in_blocks_of_queries_as_it_does_whole(monkeypatch):
    config = tiny("float32")
    weights, batch, *_ = one_step(config, 4)
    tokens, targets = batch["tokens"][0], batch["targets"][0]
    s = REFERENCE.sizes_of(config)
    whole = REFERENCE.loss(weights, tokens, targets, s)
    monkeypatch.setattr(REFERENCE, "QUERY_BLOCK", 32)
    assert abs(float(REFERENCE.loss(weights, tokens, targets, s)) - float(whole)) < 1e-6 * float(whole)


def test_reference_selection_and_its_lower_precisions():
    config = tiny("float32")
    weights, batch, *_ = one_step(config, 6)
    tokens = batch["tokens"][0]
    by = {p: [np.asarray(k) for k in REFERENCE.selection(weights, tokens, config, p)]
          for p in ("float32", "bfloat16", "float8")}
    t = np.arange(128)
    for keep in by["float32"]:
        assert (keep.sum(-1) == np.minimum(t + 1, 32)).all() and not np.triu(keep, 1).any()
    moved = {p: np.mean([(a & ~b).sum() / a.sum() for a, b in zip(by["float32"], by[p])]) for p in ("bfloat16", "float8")}
    assert 0.0 < moved["bfloat16"] < moved["float8"] < 0.5, moved


def test_weights_come_from_the_seed_alone():
    config = tiny("float32")
    a, b, c = (REFERENCE.make_weights(s, config) for s in (5, 5, 6))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["layers"]["wi_q"], c["layers"]["wi_q"]))
    assert a["layers"]["w_gate"].shape == (2, 2, 128, 64) and a["layers"]["wi_q"].shape == (2, 128, 4 * 32)
    assert a["layers"]["wq"].shape == (2, 128, 4 * 64) and a["layers"]["q_norm"].shape == (2, 64)
    big = REFERENCE.make_weights(2**31 + 9, config)  # the driver's seeds pass 32 signed bits
    assert bool(jnp.all(jnp.isfinite(big["embed"])))


# -- the configuration and the counts -----------------------------------------------


def test_the_configuration_keeps_every_published_width():
    c = BENCH.config("keye-vl-2.0-30b-a3b")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not on this machine")
    with open(catalog, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k, "missing") != v)
    assert differs == sorted(c["reduced"]) == ["num_experts", "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    entry = next(e for e in BENCH.doc["configs"] if e["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    assert c["expert_parallel"]["router_outputs"] == 128 and c["expert_parallel"]["chips"] * c["num_experts"] == 128
    assert c["num_hidden_layers"] >= 4 and c["num_experts"] == 16 and c["vocab_size"] * 8 == 151_936
    for key in ("reduced_why", "stands_for"):
        assert len(c[key]) > 100
    assert {"rope", "qk_norm", "indexer", "selection", "index_loss", "router_aux_loss_coef", "learning_rate",
            "vision_tower", "weights"} <= set(c["assumed"])
    cfg = PROGRAM.transformer_config(c)
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k, cfg.moe_score, cfg.moe_norm_topk) == (
        128, (0, 16), 8, "softmax", True)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff, cfg.qk_norm_per_head) == (32, 4, 128, 768, True)
    assert (cfg.dsa_index_heads, cfg.dsa_index_dim, cfg.dsa_topk, cfg.rope_theta, cfg.rms_eps) == (16, 64, 2048, 1e7, 1e-6)
    assert cfg.remat and cfg.remat_keeps_attention


def test_keye_cut_to_one_chips_share():
    c = BENCH.config("keye-vl-2.0-30b-a3b")
    flops = BENCH.flops("dsa_moe_lm")
    layers = c["num_hidden_layers"]
    # attention: Wq 2048*4096 + Wk 2048*512 + Wv 2048*512 + Wo 4096*2048
    assert flops.attention_params(c) == 8_388_608 + 1_048_576 + 1_048_576 + 8_388_608 == 18_874_368
    # the indexer: Wiq 2048*1024 + Wik 2048*64 + Wiw 2048*16
    assert flops.indexer_params(c) == 2_097_152 + 131_072 + 32_768 == 2_260_992
    assert flops.expert_params(c) == 3 * 2048 * 768 == 4_718_592
    assert flops.held_experts_per_token(c) == 8 * 16 / 128 == 1.0
    layer = 18_874_368 + 2_260_992 + 262_144 + 4_718_592
    assert flops.matmul_params(c) == layers * layer + 2048 * 18_992
    # held on the chip: + the QK-norms (2 * 128), the LayerNorm (2 * 64), two norms (2 * 2048), 16 experts
    held_layer = 18_874_368 + 256 + 2_260_992 + 128 + 4_096 + 262_144 + 16 * 4_718_592
    assert held_layer == 96_899_456
    assert flops.total_params(c) == layers * held_layer + 2 * 2048 * 18_992 + 2048
    assert layers != 4 or flops.total_params(c) == 465_391_104
    shapes = jax.eval_shape(lambda: REFERENCE.make_weights(1, c))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == flops.total_params(c)
    # pairs at 32,768 with topk 2,048: the triangle up to 2,048, then 2,048 a query
    assert flops.selected_pairs(32768, 2048) == 2048 * 2049 // 2 + 30_720 * 2048 == 65_012_736
    assert flops.visible_pairs(32768) == 536_887_296
    assert flops.selected_pairs(1024, 2048) == flops.visible_pairs(1024)
    assert round(65_012_736 / 536_887_296, 5) == 0.12109
    per_token = flops.attention_flops_per_token(c, 32768)
    assert per_token == pytest.approx(layers * (12 * 128 * 32 * 65_012_736 + 6 * 64 * 16 * 536_887_296) / 32768)
    assert flops.train_flops_per_token(c, 32768) == pytest.approx(6 * flops.matmul_params(c) + per_token)


def test_kernel_counts_from_shapes():
    c, t = BENCH.config("keye-vl-2.0-30b-a3b"), BENCH.traffic("steady-1g-32k")
    layers = c["num_hidden_layers"]
    attn = BENCH.flops("tpuft_dsa_attn").per_step(c, t)
    # layers * 32 heads * 65,012,736 selected pairs * 6 products * 2 * 128
    assert attn["flops"] == layers * 32 * 65_012_736 * 1536.0
    row = 32768 * 128 * 2
    assert attn["bytes"] == layers * (32 * (2 * row + 32768 * 4) + 4 * 2 * row + 32 * (4 * row + 2 * 32768 * 4) + 4 * 4 * row)
    # a dense causal attention (`flops/tpuft_fa.py`'s pairs) would count 8.26 times as much
    assert 536_887_296 / 65_012_736 == pytest.approx(8.258, abs=1e-3)
    index = BENCH.flops("tpuft_dsa_index").per_step(c, t)
    assert index["flops"] == layers * 16 * 536_887_296 * 384.0
    assert index["bytes"] == layers * 2 * 32768 * (16 * 64 * 2 + 64 * 2 + 16 * 4)
    peaks = BENCH.peaks("TPU v5 lite")
    for need in (attn, index):
        assert need["flops"] / peaks["bf16_flops_per_s"] > need["bytes"] / peaks["hbm_bytes_per_s"]  # compute-bound
    # the existing counts that read this configuration's keys as they stand
    ce = BENCH.flops("tpuft_ce").per_step(c, t)
    assert ce["flops"] == 4.0 * 32768 * 2048 * 18_992


def test_the_cell_is_found_and_reports_its_metrics():
    assert BENCH.cell(CELL)["chips"] == 1 and BENCH.traffic(BENCH.cell(CELL)["traffic"])["groups"] == 1
    traffic, base = BENCH.traffic("steady-1g-32k"), BENCH.traffic("steady-1g-8k")
    differ = {k for k in traffic if traffic[k] != base.get(k)}
    assert differ == {"seq_len", "sequences_per_step", "trace_from_step", "trace_steps", "trace_why", "name"}
    assert (traffic["seq_len"], traffic["sequences_per_step"], traffic["warmup_steps"]) == (32768, 1, 3)
    assert (traffic["trace_from_step"], traffic["trace_steps"], traffic["trace_skip_steps"]) == (3, 4, 1)
    assert {m["name"] for m in BENCH.end_to_end(CELL)} == {"tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in BENCH.per_layer(CELL)}
    assert set(NEW_METRICS) | {"gmm_ms", "ce_roofline", "moe_dropped", "moe_load_max_over_mean", "mfu", "device_grad_ms",
                               "device_update_ms", "quorum_ms", "quorum_wait_ms",
                               "ft_step_self_ms", "device_step_ms", "alloc_peak_bytes"} <= per_layer
    # dense attention's pairs, every chip's rows, latent attention: not this cell's
    assert not {"attn_roofline", "gmm_roofline", "mla_attn_ms", "mla_attn_roofline"} & per_layer
    # its reader wants 20 steps outside the capture; a 48 s window of 2.9 s steps has 11
    assert "step_p90_ms.steady" not in per_layer
    for other in (w["name"] for w in BENCH.doc["workloads"] if w["name"] != CELL):
        assert not set(NEW_METRICS) & {m["name"] for m in BENCH.per_layer(other)}
    assert [m["name"] for m in BENCH.doc["per_layer"][-5:]] == list(NEW_METRICS)  # appended, in this order
    for name in NEW_METRICS:
        metric = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
        reader = BENCH.reader(name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["moves"], metric["source"])
        assert metric["workloads"] == [CELL] and reader.__doc__
    assert set(PROGRAM.kernel_names()) == {"attn", "ce", "gmm", "dsa_attn", "dsa_index", "dsa_select"}
    assert PROGRAM.kernel_names()["dsa_attn"]("%tpuft_dsa_attn_bwd_dkdv_dq.3 = custom-call")


def _ctx(tmp_path, monkeypatch, summaries, kernels, config):
    stream = tmp_path / "g0.metrics.jsonl"
    stream.write_text("".join(json.dumps(dict(event="step_summary", t_mono=1.0 + i, step=i, **s)) + "\n"
                              for i, s in enumerate(summaries)))
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(stream))
    return {"trace": {"kernel_s_per_step": kernels}, "peaks": BENCH.peaks("TPU v5 lite"), "bench": BENCH,
            "config": config, "traffic": BENCH.traffic("steady-1g-32k"),
            "steady_steps": [{"start_mono_ns": 0.5e9, "ms": 10_000.0}]}


def test_the_new_readers_on_a_recorded_step(tmp_path, monkeypatch):
    c = BENCH.config("keye-vl-2.0-30b-a3b")
    layers = c["num_hidden_layers"]
    summaries = [dict(dsa_pairs_selected=layers * 65_012_736, dsa_pairs_visible=layers * 536_887_296,
                      dsa_index_loss=1.5)] * 3
    ctx = _ctx(tmp_path, monkeypatch, summaries, {"dsa_attn": 2.0, "dsa_index": 0.5, "dsa_select": 0.4}, c)
    assert BENCH.reader("dsa_selected_share").read(ctx) == pytest.approx(0.12109, abs=5e-6)
    assert BENCH.reader("dsa_attn_ms").read(ctx) == 2000.0 and BENCH.reader("dsa_index_ms").read(ctx) == 500.0
    attn = BENCH.flops("tpuft_dsa_attn").per_step(c, ctx["traffic"])
    assert BENCH.reader("dsa_attn_roofline").read(ctx) == pytest.approx(100 * attn["flops"] / 197e12 / 2.0)
    index = BENCH.flops("tpuft_dsa_index").per_step(c, ctx["traffic"])
    assert BENCH.reader("dsa_index_roofline").read(ctx) == pytest.approx(100 * index["flops"] / 197e12 / 0.5)
    assert 0 < BENCH.reader("dsa_attn_roofline").read(ctx) < 100 and 0 < BENCH.reader("dsa_index_roofline").read(ctx) < 100


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read(tmp_path, monkeypatch):
    """A program without the counters or the kernels (the parent of the PR
    that added them), a configuration without an indexer, a trace without the
    kernels: every new reader returns None and does not raise."""
    ctx = _ctx(tmp_path, monkeypatch, [dict(moe_dropped=0)], {"attn": 0.01, "gmm": 0.01}, BENCH.config("olmoe-1b-7b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
    ctx = _ctx(tmp_path, monkeypatch, [], {}, BENCH.config("keye-vl-2.0-30b-a3b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
    ctx = _ctx(tmp_path, monkeypatch, [], {"dsa_attn": 0.1, "dsa_index": 0.1}, BENCH.config("mistral-7b"))
    assert BENCH.reader("dsa_attn_roofline").read(ctx) is None and BENCH.reader("dsa_index_roofline").read(ctx) is None


# -- the job, end to end ---------------------------------------------------------


def _copy_with_a_tiny_share_cell(tmp_path, compute="bfloat16") -> str:
    root = make_copy(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-dsa.json"), "w", encoding="utf-8") as f:
        json.dump(tiny(compute), f)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    cell = "tiny-dsa.tiny-steady"
    doc["configs"].append(dict(name="tiny-dsa", source="none", file="benchmark/configs/tiny-dsa.json", reduced=[], why="test"))
    doc["workloads"].append(dict(name=cell, config="tiny-dsa", traffic="tiny-steady", chips=1, why="test"))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return root


def test_steady_job_with_a_tiny_share_on_the_cpu(tmp_path, monkeypatch):
    root = _copy_with_a_tiny_share_cell(tmp_path)
    cell = "tiny-dsa.tiny-steady"
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
    # 256 positions, 32 keys kept: (32 * 33 / 2 + 224 * 32) / (256 * 257 / 2)
    assert per_layer["dsa_selected_share"] == pytest.approx(7696 / 32896)
    assert per_layer["moe_dropped"] == 0.0 and 1.0 <= per_layer["moe_load_max_over_mean"] < 3.0
    for name in ("gmm_ms", "dsa_attn_ms", "dsa_attn_roofline", "dsa_index_ms", "dsa_index_roofline"):
        assert name not in per_layer  # no kernel runs on the CPU


def test_selection_ties_tool_counts_the_keys_that_differ(tmp_path):
    """`tools/selection_ties.py` on the tiny cell: the float32 program selects
    the reference's keys, bf16 moves a few and fp8 more."""
    import subprocess
    import sys

    root = _copy_with_a_tiny_share_cell(tmp_path, "float32")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "selection_ties.py"), "--workload",
         "tiny-dsa.tiny-steady", "--seeds", "3,2147483999", "--platform", "cpu"], capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["seeds"] == 2 and last["program_vs_float32"]["max"] < 2e-3
    assert 0.0 < last["reference_bfloat16_vs_float32"]["max"] < last["reference_float8_vs_float32"]["max"] < 0.5
