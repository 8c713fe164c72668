"""The sparse-expert configuration's benchmark files on the CPU: the plain
reference against float64 and against the program at a tiny size, the
operation counts against numbers worked by hand, and the `steady` job end to
end with a tiny OLMoE-shaped model (counters, readers).  Nothing is timed."""

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
REFERENCE = BENCH.reference("moe_lm")
PROGRAM = BENCH.program("moe_lm")
SEEDS = (3, 2**31 + 5, 77)


def tiny(compute: str = "float32", **changed):
    config = dict(
        source="none: a test size", architecture="moe_lm", vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=128, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=False, max_position_embeddings=256, rope_theta=1e4, rms_norm_eps=1e-5,
        router_aux_loss_coef=0.01, router_z_loss_coef=0.001, clip_qkv=None,
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
        program=dict(remat=False, scan_unroll=2),
        # float32: rounding only; bfloat16: rounding and, at this size, a routing choice or two that falls the other way
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.06),
    )
    config.update(changed)
    return config


def one_step(config, seed):
    from torchft_tpu.models.transformer import loss_and_counters

    cfg = PROGRAM.transformer_config(config)
    weights = REFERENCE.make_weights(seed, config)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, config["vocab_size"], size=(2, 128)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(np.roll(tokens, -1, axis=1))}
    (loss, counters), grads = jax.jit(
        jax.value_and_grad(lambda p, b: loss_and_counters(p, b, cfg), has_aux=True))(weights, batch)
    return weights, batch, loss, grads, counters


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_program_agrees_with_the_reference(seed):
    config = tiny("float32")
    weights, batch, loss, grads, counters = one_step(config, seed)
    indices = compare.sample_indices(seed, weights)
    out = compare.against_reference(REFERENCE, config, weights, batch, loss, compare.sample(grads, indices), indices)
    assert out["ok"], out
    assert out["loss_rel"] < 1e-5 and out["grad_rel"] < 1e-4
    assert int(counters["moe_dropped"]) == 0
    assert np.asarray(counters["moe_tokens_per_expert"]).sum(axis=1).tolist() == [2 * 128 * 2] * 2


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
    assert control["grad_rel"] > 3 * sound["grad_rel"]


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


def test_reference_routing_is_top_k_of_every_position():
    config = tiny("float32")
    weights = REFERENCE.make_weights(9, config)
    tokens = jnp.asarray(np.random.default_rng(9).integers(0, 512, size=(128,)).astype(np.int32))
    chosen = np.asarray(REFERENCE.routing(weights, tokens, config))
    assert chosen.shape == (2, 128, 2) and (chosen[..., 0] < chosen[..., 1]).all() and chosen.max() < 8


def test_weights_come_from_the_seed_alone():
    config = tiny("float32")
    a, b, c = (REFERENCE.make_weights(s, config) for s in (5, 5, 6))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["layers"]["w_up"], c["layers"]["w_up"]))
    assert a["layers"]["w_gate"].shape == (2, 8, 128, 128) and a["layers"]["q_norm"].shape == (2, 128)
    big = REFERENCE.make_weights(2**31 + 9, config)  # the driver's seeds pass 32 signed bits
    assert bool(jnp.all(jnp.isfinite(big["embed"])))


# -- the counts ----------------------------------------------------------------


def test_olmoe_cut_to_one_layer():
    c = BENCH.config("olmoe-1b-7b")
    flops = BENCH.flops("moe_lm")
    # a layer, per token: wq, wk, wv, wo 4 * 2048*2048 = 16,777,216; router 2048*64 = 131,072;
    #   8 of the 64 experts, 3 * 2048*1024 = 6,291,456 each = 50,331,648          -> 67,239,936
    # head: 2048 * 50,304 = 103,022,592
    assert flops.expert_params(c) == 6_291_456
    assert flops.matmul_params(c) == 67_239_936 + 103_022_592 == 170_262_528
    # held on the chip: all 64 experts 402,653,184 + attention + router + norms (2*2048 + 2*2048) = 419,569,664 a layer;
    #   embedding and head 206,045,184; the final norm 2048
    assert flops.total_params(c) == 419_569_664 + 206_045_184 + 2048 == 625_616_896
    # attention: 3 * 2 * (2 * 16 * 128 * 2048.5) = 50,343,936 a token
    assert flops.attention_flops_per_token(c, 4096) == pytest.approx(50_343_936)
    assert flops.train_flops_per_token(c, 4096) == pytest.approx(6 * 170_262_528 + 50_343_936)
    # counting every expert (6 * n_params without the embedding) would say 3.07 times as much
    every = 6 * (flops.total_params(c) - 2048 * 50_304)
    assert every / (6 * flops.matmul_params(c)) == pytest.approx(3.069, abs=1e-3)
    shapes = jax.eval_shape(lambda: BENCH.reference("moe_lm").make_weights(1, c))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == flops.total_params(c)


def test_grouped_matmul_counts_from_shapes():
    c, t = BENCH.config("olmoe-1b-7b"), BENCH.traffic("steady-1g")
    need = BENCH.flops("tpuft_gmm").per_step(c, t)
    rows = 8192 * 8
    # 3 projections * 3 products * 2 * rows * 2048 * 1024 = 6 * 8 * 3 * 2048 * 1024 * 8192
    assert need["flops"] == 9 * 2 * rows * 2048 * 1024 == 6 * 8 * 3 * 2048 * 1024 * 8192
    wide, narrow, matrices = rows * 2048 * 2, rows * 1024 * 2, 64 * 2048 * 1024
    assert need["bytes"] == 3 * (3 * (wide + narrow) + matrices * (2 + 2 + 4))
    peaks = BENCH.peaks("TPU v5 lite")
    assert need["flops"] / peaks["bf16_flops_per_s"] > need["bytes"] / peaks["hbm_bytes_per_s"]  # compute-bound
    assert need["flops"] / peaks["bf16_flops_per_s"] == pytest.approx(12.56e-3, rel=1e-3)


def test_the_cell_is_found_and_reports_its_metrics():
    cell = "olmoe-1b-7b.steady-1g"
    assert BENCH.cell(cell)["chips"] == 1 and BENCH.traffic(BENCH.cell(cell)["traffic"])["groups"] == 1
    assert {m["name"] for m in BENCH.end_to_end(cell)} == {"tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in BENCH.per_layer(cell)}
    assert {"gmm_roofline", "gmm_ms", "moe_load_max_over_mean", "moe_dropped", "mfu", "attn_roofline", "ce_roofline",
            "device_grad_ms", "device_update_ms", "step_p90_ms.steady"} <= per_layer
    for other in ("internlm2-1.8b.steady-1g", "mistral-7b.steady-1g", "internlm2-1.8b.steady-4g"):
        assert not {"gmm_roofline", "gmm_ms", "moe_load_max_over_mean", "moe_dropped"} & {
            m["name"] for m in BENCH.per_layer(other)}
    for name in ("gmm_roofline", "gmm_ms", "moe_load_max_over_mean", "moe_dropped"):
        metric = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
        reader = BENCH.reader(name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["moves"], metric["source"])
    assert set(PROGRAM.kernel_names()) == {"attn", "ce", "gmm"}
    assert PROGRAM.kernel_names()["gmm"]("%tpuft_gmm_drhs.3 = custom-call") and not PROGRAM.kernel_names()["gmm"]("fusion.3")


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read(monkeypatch, tmp_path):
    """A program without the kernels or the counters (the parent of the PR
    that added them): every new reader returns None and does not raise."""
    stream = tmp_path / "g0.metrics.jsonl"
    stream.write_text(json.dumps({"event": "step_summary", "t_mono": 1.0, "step": 1}) + "\n")
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(stream))
    ctx = {"trace": {"kernel_s_per_step": {"attn": 0.01}}, "peaks": BENCH.peaks("TPU v5 lite"), "bench": BENCH,
           "config": BENCH.config("internlm2-1.8b"), "traffic": BENCH.traffic("steady-1g"),
           "steady_steps": [{"start_mono_ns": 0.5e9, "ms": 1000.0}]}
    for name in ("gmm_roofline", "gmm_ms", "moe_load_max_over_mean", "moe_dropped"):
        assert BENCH.reader(name).read(ctx) is None


# -- the job, end to end ---------------------------------------------------------


def _copy_with_a_tiny_sparse_cell(tmp_path) -> str:
    root = make_copy(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-moe.json"), "w", encoding="utf-8") as f:
        json.dump(tiny("bfloat16"), f)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    cell = "tiny-moe.tiny-steady"
    doc["configs"].append(dict(name="tiny-moe", source="none", file="benchmark/configs/tiny-moe.json", reduced=[], why="test"))
    doc["workloads"].append(dict(name=cell, config="tiny-moe", traffic="tiny-steady", chips=1, why="test"))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if "olmoe-1b-7b.steady-1g" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return root


def test_steady_job_with_a_tiny_sparse_model_on_the_cpu(tmp_path, monkeypatch):
    root = _copy_with_a_tiny_sparse_cell(tmp_path)
    cell = "tiny-moe.tiny-steady"
    monkeypatch.setenv("PYTHONPATH", ROOT)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # a group owns one device (tests/conftest.py asks for eight)
    bench = Benchmark(root)
    job = bench.job(bench.traffic("tiny-steady")["job"])
    seed = 2**31 + 23
    result = job.run(bench, bench.cell(cell), seed=seed, seconds=6.0, trace=True, t0_wall=time.time(), platform="cpu")
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 8 and result["failed"] == 0 and result["compiled_in_window"] == 0
    per_layer = result["per_layer"]
    assert per_layer["moe_dropped"] == 0.0
    assert 1.0 <= per_layer["moe_load_max_over_mean"] < 2.0  # 8 experts, 1,024 assignments a step
    assert "gmm_ms" not in per_layer and "gmm_roofline" not in per_layer  # no kernel runs on the CPU
    # the counters are in the program's own stream, one step late and saying so
    with open(os.path.join(root, "benchmark", "out", f"{cell}.{seed}.trace.run", "g0.metrics.jsonl"), encoding="utf-8") as f:
        summaries = [r for r in map(json.loads, f) if r.get("event") == "step_summary"]
    noted = [s for s in summaries if "moe_tokens_per_expert_max" in s]
    assert len(noted) >= len(summaries) - 1
    assert all(s["moe_tokens_per_expert_mean"] == 2 * 256 * 2 / 8 and s["moe_dropped"] == 0 for s in noted)
    assert all(s["counters_step"] == s["step"] - 1 for s in noted)


def test_routing_ties_tool_counts_the_choices_that_differ(tmp_path):
    """`tools/routing_ties.py` on the tiny cell: the float32 program's choices
    are the reference's, bf16 moves a few and fp8 more."""
    import subprocess
    import sys

    root = _copy_with_a_tiny_sparse_cell(tmp_path)
    with open(os.path.join(root, "benchmark", "configs", "tiny-moe.json"), "w", encoding="utf-8") as f:
        json.dump(tiny("float32"), f)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "routing_ties.py"), "--workload", "tiny-moe.tiny-steady",
         "--seeds", "3,2147483999", "--platform", "cpu"], capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["seeds"] == 2 and last["program_vs_float32"]["max"] == 0.0
    assert 0.0 < last["reference_bfloat16_vs_float32"]["max"] < last["reference_float8_vs_float32"]["max"] < 0.5
