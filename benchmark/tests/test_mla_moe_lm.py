"""The latent-attention, shared-expert configuration's benchmark files on the
CPU: the plain reference against float64 and against the program at a tiny
size, the operation counts against numbers worked by hand at the cell's
sizes, the new readers on what they read and on nothing, and the `steady` job
end to end with a tiny Moonlight-shaped share.  Nothing is timed."""

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
REFERENCE = BENCH.reference("mla_moe_lm")
PROGRAM = BENCH.program("mla_moe_lm")
SEEDS = (3, 2**31 + 5, 77)
CELL = "moonlight-16b-a3b.steady-1g-8k"
NEW_METRICS = ("mla_attn_roofline", "mla_attn_ms", "gmm_held_roofline", "moe_held_share")


def tiny(compute: str = "float32", **changed):
    """One dense and two sparse layers; 2 of the router's 8 experts held."""
    config = dict(
        source="none: a test size", architecture="mla_moe_lm", vocab_size=512, hidden_size=128, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64, q_lora_rank=None, intermediate_size=256,
        moe_intermediate_size=64, n_routed_experts=2, n_shared_experts=2, num_experts_per_tok=2, norm_topk_prob=True,
        scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1, routed_scaling_factor=2.446,
        seq_aux=True, aux_loss_alpha=0.001, max_position_embeddings=256, rope_theta=5e4, rms_norm_eps=1e-5,
        expert_parallel=dict(chips=4, rank=1, router_outputs=8, first_expert_held=2),
        router_bias=dict(seed=31, scale=0.05),
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
        program=dict(remat=True, scan_unroll=4),
        # float32: rounding only; bfloat16: rounding and, at this size, a routing choice or two that falls the other
        # way (sound 0.010 to 0.063 over five seeds; the fp8 control 0.137 to 0.176, the router its worst leaf)
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.1),
    )
    config.update(changed)
    return config


def one_step(config, seed):
    weights = REFERENCE.make_weights(seed, config)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, config["vocab_size"], size=(2, 128)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(np.roll(tokens, -1, axis=1))}
    (loss, counters), grads = jax.jit(jax.value_and_grad(PROGRAM.loss(config), has_aux=True))(weights, batch)
    return weights, batch, loss, grads, counters


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_program_agrees_with_the_reference(seed):
    config = tiny("float32")
    weights, batch, loss, grads, counters = one_step(config, seed)
    indices = compare.sample_indices(seed, weights)
    out = compare.against_reference(REFERENCE, config, weights, batch, loss, compare.sample(grads, indices), indices)
    assert out["ok"], out
    assert out["loss_rel"] < 1e-5 and out["grad_rel"] < 1e-4
    assert int(counters["moe_dropped"]) == 0 and int(counters["moe_assignments"]) == 2 * 2 * 128 * 2
    assert 0 < int(counters["moe_rows_held"]) < int(counters["moe_assignments"])


@pytest.mark.parametrize("seed", SEEDS)
def test_bf16_program_passes_and_the_fp8_control_fails(seed):
    config = tiny("bfloat16")
    weights, batch, loss, grads, _ = one_step(config, seed)
    indices = compare.sample_indices(seed, weights)
    sound = compare.against_reference(REFERENCE, config, weights, batch, loss, compare.sample(grads, indices), indices)
    assert sound["ok"], sound
    closs, cgrads = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], config, "float8")
    control = compare.against_reference(REFERENCE, config, weights, batch, closs, compare.sample(cgrads, indices), indices)
    assert not control["ok"], control
    assert control["grad_rel"] > 2 * sound["grad_rel"]  # 256 tokens: one choice that falls the other way is 0.05


def test_reference_in_float32_agrees_with_itself_in_float64():
    """The reference's own rounding: float32 at the highest precision against
    the same code in float64, loss and every gradient leaf."""
    config = tiny("float32")
    weights = REFERENCE.make_weights(5, config)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 512, size=(1, 128)).astype(np.int32))
    targets = jnp.roll(tokens, -1, axis=1)
    loss32, grads32 = REFERENCE.loss_and_grads(weights, tokens, targets, config)
    with jax.enable_x64():
        wide = jax.tree.map(lambda w: jnp.asarray(np.asarray(w), jnp.float64), weights)
        s = REFERENCE.sizes_of(config)
        loss64, grads64 = jax.value_and_grad(lambda w: REFERENCE.loss(w, tokens[0], targets[0], s))(wide)
        assert loss64.dtype == jnp.float64
        assert abs(float(loss32) - float(loss64)) / float(loss64) < 1e-6
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads32), jax.tree.leaves(grads64)):
            a, b = np.asarray(a, np.float64), np.asarray(b)
            assert np.linalg.norm(a - b) <= 2e-5 * np.linalg.norm(b), jax.tree_util.keystr(path)


def test_reference_attends_in_blocks_of_queries_as_it_does_whole(monkeypatch):
    """Four blocks of queries give what one does (the cell's sequences are
    four blocks of 2,048)."""
    config = tiny("float32")
    weights = REFERENCE.make_weights(8, config)
    tokens = jnp.asarray(np.random.default_rng(8).integers(0, 512, size=(128,)).astype(np.int32))
    s = REFERENCE.sizes_of(config)
    whole = REFERENCE.loss(weights, tokens, jnp.roll(tokens, -1), s)
    monkeypatch.setattr(REFERENCE, "QUERY_BLOCK", 32)
    blocks = REFERENCE.loss(weights, tokens, jnp.roll(tokens, -1), s)
    assert abs(float(whole) - float(blocks)) < 1e-6 * float(whole)


def test_reference_routing_and_the_bias():
    config = tiny("float32")
    weights = REFERENCE.make_weights(9, config)
    tokens = jnp.asarray(np.random.default_rng(9).integers(0, 512, size=(128,)).astype(np.int32))
    chosen = np.asarray(REFERENCE.routing(weights, tokens, config))
    assert chosen.shape == (2, 128, 2) and (chosen[..., 0] < chosen[..., 1]).all() and chosen.max() < 8
    bias = REFERENCE.router_bias(config)
    assert bias.shape == (2, 8) and bias.dtype == np.float32 and np.array_equal(bias, REFERENCE.router_bias(config))
    assert 0.01 < np.abs(bias).mean() < 0.1
    unbiased = np.asarray(REFERENCE.routing(weights, tokens, dict(config, router_bias=dict(seed=31, scale=0.0))))
    assert 0 < (unbiased != chosen).mean() < 0.5  # the bias changes choices, and not most of them


def test_weights_come_from_the_seed_alone():
    config = tiny("float32")
    a, b, c = (REFERENCE.make_weights(s, config) for s in (5, 5, 6))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["layers"]["w_up"], c["layers"]["w_up"]))
    assert a["layers"]["w_gate"].shape == (2, 2, 128, 64) and a["dense_layers"]["w_gate"].shape == (1, 128, 256)
    big = REFERENCE.make_weights(2**31 + 9, config)  # the driver's seeds pass 32 signed bits
    assert bool(jnp.all(jnp.isfinite(big["embed"])))


# -- the configuration and the counts -----------------------------------------------


def test_the_configuration_keeps_every_published_width():
    c = BENCH.config("moonlight-16b-a3b")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not on this machine")
    with open(catalog, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Moonlight-16B-A3B")
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k, "missing") != v)
    assert differs == sorted(c["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    assert c["expert_parallel"]["router_outputs"] == 64 and c["expert_parallel"]["chips"] * c["n_routed_experts"] == 64
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4 and c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    cfg = PROGRAM.transformer_config(c)
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k, cfg.moe_route_scale) == (64, (0, 8), 6, 2.446)
    assert (cfg.mla_kv_rank, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim) == (512, 128, 64, 128)
    assert (cfg.d_ff, cfg.dense_d_ff, cfg.moe_shared_experts, cfg.moe_dense_layers) == (1408, 11264, 2, 1)


def test_moonlight_cut_to_one_chips_share():
    c = BENCH.config("moonlight-16b-a3b")
    flops = BENCH.flops("mla_moe_lm")
    sparse = c["num_hidden_layers"] - 1
    # attention: Wq 2048*16*192 = 6,291,456; Wkva 2048*576 = 1,179,648; Wkvb 512*16*256 = 2,097,152; Wo 2048*2048 = 4,194,304
    assert flops.attention_params(c) == 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304 == 13_762_560
    assert flops.expert_params(c) == 3 * 2048 * 1408 == 8_650_752
    assert flops.held_experts_per_token(c) == 6 * 8 / 64 == 0.75
    # per token: the dense layer 13,762,560 + 3*2048*11,264 (69,206,016) = 82,968,576;
    #   a sparse layer 13,762,560 + router 131,072 + shared 2 * 8,650,752 + 0.75 * 8,650,752 = 37,683,200; head 2048 * 20,480
    dense_layer, sparse_layer = 82_968_576, 37_683_200
    assert flops.matmul_params(c) == dense_layer + sparse * sparse_layer + 41_943_040
    # held on the chip: + 3 norm vectors a layer (2 * 2048 + 512), all 8 held experts, the embedding, the final norm
    held_layer = 13_762_560 + 4_608 + 131_072 + 10 * 8_650_752
    assert held_layer == 100_405_760
    assert flops.total_params(c) == (82_968_576 + 4_608) + sparse * held_layer + 2 * 41_943_040 + 2048
    assert sparse != 5 or flops.total_params(c) == 668_890_112
    shapes = jax.eval_shape(lambda: REFERENCE.make_weights(1, c))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == flops.total_params(c)
    # attention at 8,192: 3 * 2 * 16 heads * (192 + 128) * 4096.5 a layer and token
    assert flops.attention_flops_per_token(c, 8192) == pytest.approx((sparse + 1) * 3 * 2 * 16 * 320 * 4096.5)
    total = flops.train_flops_per_token(c, 8192)
    assert total == pytest.approx(6 * flops.matmul_params(c) + flops.attention_flops_per_token(c, 8192))
    assert sparse != 5 or total / 1e9 == pytest.approx(2.635, abs=2e-3)


def test_kernel_counts_from_shapes():
    c, t = BENCH.config("moonlight-16b-a3b"), BENCH.traffic("steady-1g-8k")
    layers = c["num_hidden_layers"]
    fa = BENCH.flops("tpuft_fa_mla").per_step(c, t)
    # layers * (2 * 16) heads * 2 * 8192 * 8193 / 2 visible pairs * (3 * 192 + 3 * 128)
    assert fa["flops"] == pytest.approx(layers * 32 * 8192 * 8193 * 960)
    assert fa["bytes"] == layers * 32 * (6 * 8192 * 192 * 2 + 6 * 8192 * 128 * 2 + 3 * 8192 * 4)
    # counted at one head width of 192 (`flops/tpuft_fa.py` with head_dim 192) it would be 1.2 times as much
    assert layers * 32 * 6 * 8192 * 8193 * 192 / fa["flops"] == pytest.approx(1.2)
    rows = 5 * 12_288.0
    gmm = BENCH.flops("tpuft_gmm_held").per_step(dict(c, num_hidden_layers=6), rows)
    assert gmm["flops"] == 9 * 2 * rows * 2048 * 1408
    wide, narrow, matrices = rows * 2048 * 2, rows * 1408 * 2, 5 * 8 * 2048 * 1408
    assert gmm["bytes"] == 3 * (3 * (wide + narrow) + matrices * (2 + 2 + 4))
    # tokens * experts a token (`flops/tpuft_gmm.py`'s rows) would count eight chips' work
    assert 5 * 16_384 * 6 / rows == 8.0
    peaks = BENCH.peaks("TPU v5 lite")
    for need in (fa, gmm):
        assert need["flops"] / peaks["bf16_flops_per_s"] > need["bytes"] / peaks["hbm_bytes_per_s"]  # compute-bound


def test_the_cell_is_found_and_reports_its_metrics():
    assert BENCH.cell(CELL)["chips"] == 1 and BENCH.traffic(BENCH.cell(CELL)["traffic"])["groups"] == 1
    traffic, base = BENCH.traffic("steady-1g-8k"), BENCH.traffic("steady-1g")
    assert {k for k in traffic if traffic[k] != base[k]} == {"seq_len", "name"} and traffic["seq_len"] == 8192
    assert {m["name"] for m in BENCH.end_to_end(CELL)} == {"tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in BENCH.per_layer(CELL)}
    assert set(NEW_METRICS) | {"gmm_ms", "ce_roofline", "moe_dropped", "moe_load_max_over_mean", "mfu", "device_grad_ms",
                               "device_update_ms", "step_p90_ms.steady", "quorum_ms", "ft_step_self_ms"} <= per_layer
    assert not {"attn_roofline", "gmm_roofline"} & per_layer  # they count one head width, and every chip's rows
    for other in (w["name"] for w in BENCH.doc["workloads"] if w["name"] != CELL):
        assert not set(NEW_METRICS) & {m["name"] for m in BENCH.per_layer(other)}
    for name in NEW_METRICS:
        metric = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
        reader = BENCH.reader(name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["moves"], metric["source"])
        assert metric["workloads"] == [CELL]
    assert set(PROGRAM.kernel_names()) == {"attn", "ce", "gmm"}
    assert PROGRAM.kernel_names()["attn"]("%tpuft_fa_bwd_dq.3 = custom-call")


def _ctx(tmp_path, monkeypatch, summaries, kernels, config):
    stream = tmp_path / "g0.metrics.jsonl"
    stream.write_text("".join(json.dumps(dict(event="step_summary", t_mono=1.0 + i, step=i, **s)) + "\n"
                              for i, s in enumerate(summaries)))
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(stream))
    return {"trace": {"kernel_s_per_step": kernels}, "peaks": BENCH.peaks("TPU v5 lite"), "bench": BENCH,
            "config": config, "traffic": BENCH.traffic("steady-1g-8k"),
            "steady_steps": [{"start_mono_ns": 0.5e9, "ms": 10_000.0}]}


def test_the_new_readers_on_a_recorded_step(tmp_path, monkeypatch):
    c = BENCH.config("moonlight-16b-a3b")
    sparse = c["num_hidden_layers"] - 1
    summaries = [dict(moe_rows_held=sparse * rows, moe_assignments=sparse * 98_304) for rows in (12_000, 12_288, 13_000)]
    ctx = _ctx(tmp_path, monkeypatch, summaries, {"attn": 0.2, "gmm": 0.02}, c)
    assert BENCH.reader("moe_held_share").read(ctx) == 0.125
    assert BENCH.reader("mla_attn_ms").read(ctx) == 200.0
    need = BENCH.flops("tpuft_fa_mla").per_step(c, ctx["traffic"])
    assert BENCH.reader("mla_attn_roofline").read(ctx) == pytest.approx(100 * need["flops"] / 197e12 / 0.2)
    held = BENCH.flops("tpuft_gmm_held").per_step(c, sparse * 12_288)
    assert BENCH.reader("gmm_held_roofline").read(ctx) == pytest.approx(100 * held["flops"] / 197e12 / 0.02)
    assert 0 < BENCH.reader("gmm_held_roofline").read(ctx) < 100 and 0 < BENCH.reader("mla_attn_roofline").read(ctx) < 100


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read(tmp_path, monkeypatch):
    """A program without the counters (the parent of the PR that added them),
    a configuration without latent attention, a trace without the kernels:
    every new reader returns None and does not raise."""
    ctx = _ctx(tmp_path, monkeypatch, [dict(moe_dropped=0)], {"attn": 0.01, "gmm": 0.01}, BENCH.config("olmoe-1b-7b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
    ctx = _ctx(tmp_path, monkeypatch, [], {}, BENCH.config("moonlight-16b-a3b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name


# -- the job, end to end ---------------------------------------------------------


def _copy_with_a_tiny_share_cell(tmp_path, compute="bfloat16") -> str:
    root = make_copy(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-mla.json"), "w", encoding="utf-8") as f:
        json.dump(tiny(compute), f)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    cell = "tiny-mla.tiny-steady"
    doc["configs"].append(dict(name="tiny-mla", source="none", file="benchmark/configs/tiny-mla.json", reduced=[], why="test"))
    doc["workloads"].append(dict(name=cell, config="tiny-mla", traffic="tiny-steady", chips=1, why="test"))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return root


def test_steady_job_with_a_tiny_share_on_the_cpu(tmp_path, monkeypatch):
    root = _copy_with_a_tiny_share_cell(tmp_path)
    cell = "tiny-mla.tiny-steady"
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
    assert per_layer["moe_dropped"] == 0.0 and 0.05 < per_layer["moe_held_share"] < 0.6
    assert 1.0 <= per_layer["moe_load_max_over_mean"] < 3.0
    for name in ("gmm_ms", "mla_attn_ms", "mla_attn_roofline", "gmm_held_roofline"):
        assert name not in per_layer  # no kernel runs on the CPU


def test_routing_ties_tool_counts_the_choices_that_differ(tmp_path):
    """`tools/routing_ties_mla.py` on the tiny cell: the float32 program's
    choices are the reference's, bf16 moves a few and fp8 more, and the bias
    decides some."""
    import subprocess
    import sys

    root = _copy_with_a_tiny_share_cell(tmp_path, "float32")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "routing_ties_mla.py"), "--workload",
         "tiny-mla.tiny-steady", "--seeds", "3,2147483999", "--platform", "cpu"], capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["seeds"] == 2 and last["program_vs_float32"]["max"] == 0.0
    assert 0.0 < last["reference_bfloat16_vs_float32"]["max"] < last["reference_float8_vs_float32"]["max"] < 0.5
    assert 0.0 < last["decided_by_the_bias"]["min"] < 0.5
