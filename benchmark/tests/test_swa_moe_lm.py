"""The window-and-full-attention sparse configuration's benchmark files on the
CPU: the plain reference against float64 and against the program at a tiny
size, the operation counts against numbers worked by hand at the cell's sizes,
the new readers on what they read and on nothing, and the `steady` job end to
end with a tiny Laguna-shaped share.  Nothing is timed."""

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
REFERENCE = BENCH.reference("swa_moe_lm")
PROGRAM = BENCH.program("swa_moe_lm")
SEEDS = (3, 2**31 + 5, 77)
CELL = "laguna-xs.2.steady-1g-16k"
NEW_METRICS = ("swa_attn_ms", "swa_attn_roofline", "full_attn_ms", "full_attn_roofline", "swa_pairs_share",
               "gmm_small_roofline")
PERIOD = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"]


def tiny(compute: str = "float32", **changed):
    """The cut's 1 + 4 layers in small; 2 of the router's 8 experts held; a window of 24."""
    config = dict(
        source="none: a test size", architecture="swa_moe_lm", vocab_size=512, hidden_size=128, num_hidden_layers=5,
        num_attention_heads=6, num_attention_heads_per_layer=[6, 8, 8, 8] * 2, num_key_value_heads=2, head_dim=32,
        intermediate_size=256, moe_intermediate_size=64, shared_expert_intermediate_size=64, num_experts=2,
        num_experts_per_tok=2, moe_routed_scaling_factor=2.5, moe_apply_router_weight_on_input=False, gating=True,
        sliding_window=24, layer_types=PERIOD * 2, mlp_layer_types=["dense"] + ["sparse"] * 7, attention_bias=False,
        tie_word_embeddings=False, rms_norm_eps=1e-6, max_position_embeddings=256, aux_loss_alpha=0.001,
        rope_parameters={
            "full_attention": dict(rope_type="yarn", rope_theta=100.0, factor=4.0, original_max_position_embeddings=32,
                                   beta_fast=4.0, beta_slow=1.0, attention_factor=1.14, partial_rotary_factor=0.5),
            "sliding_attention": dict(rope_type="default", rope_theta=1e4, partial_rotary_factor=1.0),
        },
        expert_parallel=dict(chips=4, rank=1, router_outputs=8, first_expert_held=2),
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
        program=dict(remat=True, remat_keeps_attention=True, remat_keeps_window_attention=False, scan_unroll=8),
        # float32: rounding only; bfloat16: rounding and, at this size, a routing choice or two that falls the other
        # way in one of four sparse layers (sound 0.022 to 0.104 over five seeds, the last layer's router the worst
        # leaf at 0.53 where one did; the fp8 control 0.164 to 0.192)
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.13),
    )
    config.update(changed)
    return config


def one_step(config, seed, seq=128):
    weights = REFERENCE.make_weights(seed, config)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, config["vocab_size"], size=(2, seq)).astype(np.int32)
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
    assert int(counters["moe_dropped"]) == 0 and int(counters["moe_assignments"]) == 4 * 2 * 128 * 2
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
    assert control["grad_rel"] > 1.5 * sound["grad_rel"]  # 256 tokens: one choice that falls the other way is 0.05


def test_reference_in_float32_agrees_with_itself_in_float64():
    """The reference's own rounding: its float32 gradients against the same
    code in float64 (weights and arithmetic), far under any limit."""
    config = tiny("float32")
    weights = REFERENCE.make_weights(5, config)
    tokens = np.random.default_rng(5).integers(0, config["vocab_size"], size=(1, 96)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    loss32, grads32 = REFERENCE.loss_and_grads(weights, jnp.asarray(tokens), jnp.asarray(targets), config)
    with jax.enable_x64():
        wide = jax.tree.map(lambda a: jnp.asarray(np.asarray(a), jnp.float64), weights)
        s = REFERENCE.sizes_of(config)
        loss64, grads64 = jax.value_and_grad(REFERENCE.loss)(wide, jnp.asarray(tokens[0]), jnp.asarray(targets[0]), s)
        assert jax.tree.leaves(grads64)[0].dtype == jnp.float64
        assert abs(float(loss32) - float(loss64)) / float(loss64) < 1e-6
        for a, b in zip(jax.tree.leaves(grads32), jax.tree.leaves(grads64)):
            a, b = np.asarray(a, np.float64), np.asarray(b)
            assert np.linalg.norm(a - b) <= 2e-5 * np.linalg.norm(b)


def test_reference_attends_in_blocks_of_queries_as_it_does_whole(monkeypatch):
    """Window and full attention a block of queries at a time give what they
    give in one block, and the window's mask is the positions' alone."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((64, 16)), jnp.float32) for _ in range(3))
    for window in (None, 8, 64):
        whole = REFERENCE._attend(q, k, v, window, "float32")
        monkeypatch.setattr(REFERENCE, "QUERY_BLOCK", 16)
        blocks = REFERENCE._attend(q, k, v, window, "float32")
        monkeypatch.undo()
        np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole), rtol=1e-5, atol=1e-6)
    # a window of one position sees itself alone: the output is the value
    np.testing.assert_allclose(np.asarray(REFERENCE._attend(q, k, v, 1, "float32")), np.asarray(v), rtol=1e-6)
    assert not np.allclose(np.asarray(REFERENCE._attend(q, k, v, 8, "float32")), np.asarray(whole), atol=1e-3)


def test_weights_come_from_the_seed_alone():
    config = tiny()
    a, b, c = (REFERENCE.make_weights(s, config) for s in (2**31 + 5, 2**31 + 5, 5))
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["layers"]["wq"], c["layers"]["wq"])
    assert set(a) == {"embed", "final_norm", "lm_head", "dense_layers", "window_layers", "layers"}
    assert a["window_layers"]["wq"].shape == (3, 128, 8 * 32) and a["layers"]["w_gate"].shape == (1, 2, 128, 64)
    assert a["dense_layers"]["w_gate"].shape == (1, 128, 256) and a["layers"]["router"].shape == (1, 128, 8)
    # scaled initialisation of what writes into the stream: by sqrt(2 x published layers)
    assert abs(float(jnp.std(a["window_layers"]["wo"])) - (8 * 32) ** -0.5 * (2 * 5) ** -0.5) < 2e-3


def test_the_configuration_keeps_every_published_width():
    """The file against the catalog row's `config`: every key not in `reduced`
    is the published one; the cut is 1 + 4 layers, 32 of 256 experts, an
    eighth of the vocabulary."""
    c = BENCH.config("laguna-xs.2")
    assert (c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"], c["shared_expert_intermediate_size"],
            c["head_dim"], c["num_key_value_heads"], c["num_attention_heads"], c["sliding_window"],
            c["num_experts_per_tok"], c["moe_routed_scaling_factor"], c["rms_norm_eps"]) == (
        2048, 8192, 512, 512, 128, 8, 48, 512, 8, 2.5, 1e-6)
    assert c["layer_types"] == PERIOD * 10 and c["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert c["rope_parameters"]["full_attention"] == dict(
        rope_theta=500000, rope_type="yarn", factor=64, original_max_position_embeddings=4096, beta_slow=1,
        beta_fast=64, attention_factor=1.4158883083359672, partial_rotary_factor=0.5)
    assert c["rope_parameters"]["sliding_attention"] == dict(rope_type="default", rope_theta=10000, partial_rotary_factor=1)
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == dict(num_hidden_layers=40, num_experts=256, vocab_size=100352)
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"] * 8) == (5, 32, 100352)
    share = c["expert_parallel"]
    assert (share["chips"], share["router_outputs"], share["first_expert_held"]) == (8, 256, 0)
    entry = next(e for e in BENCH.doc["configs"] if e["name"] == "laguna-xs.2")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    assert set(c["assumed"]) >= {"router_score", "gating", "qk_norm", "aux_loss_alpha", "rope_pairing", "weights",
                                 "learning_rate"}


def test_operation_counts_from_shapes():
    c, t = BENCH.config("laguna-xs.2"), BENCH.traffic("steady-1g-16k")
    flops = BENCH.flops("swa_moe_lm")
    assert flops.pairs(16384) == 134_225_920 and flops.pairs(16384, 512) == 8_257_792 == 512 * 513 // 2 + 15_872 * 512
    full, window = 2 * 2048 * 48 * 128 + 2 * 2048 * 1024 + 2048 * 48, 2 * 2048 * 64 * 128 + 2 * 2048 * 1024 + 2048 * 64
    assert (flops.attention_params(c, 48), flops.attention_params(c, 64)) == (full, window)
    expert = 3 * 2048 * 512
    sparse = 2048 * 256 + expert + 8 * 32 / 256 * expert
    assert flops.matmul_params(c) == 2 * full + 3 * window + 3 * 2048 * 8192 + 4 * sparse + 2048 * 12544
    attention = 3 * 2 * 2 * 128 * (2 * 48 * 134_225_920 + 3 * 64 * 8_257_792) / 16384
    assert flops.train_flops_per_token(c, 16384) == pytest.approx(6 * flops.matmul_params(c) + attention)
    swa = BENCH.flops("tpuft_swa").per_step(c, t)
    assert swa["flops"] == 3 * 64 * 6 * 2.0 * 8_257_792 * 128
    assert swa["bytes"] == 3 * 64 * (12 * 16384 * 128 * 2 + 3 * 16384 * 4)
    fa = BENCH.flops("tpuft_fa_full").per_step(c, t)
    assert fa["flops"] == 2 * 48 * 6 * 2.0 * 134_225_920 * 128
    assert fa["bytes"] == 2 * 48 * (12 * 16384 * 128 * 2 + 3 * 16384 * 4)
    rows = 4 * 16_384.0
    gmm = BENCH.flops("tpuft_gmm_small").per_step(c, rows)
    assert gmm["flops"] == 9 * 2 * rows * 2048 * 512
    wide, narrow, matrices = rows * 2048 * 2, rows * 512 * 2, 4 * 32 * 2048 * 512
    assert gmm["bytes"] == 3 * (3 * (wide + narrow) + matrices * (2 + 2 + 4))
    peaks = BENCH.peaks("TPU v5 lite")
    assert fa["flops"] / peaks["bf16_flops_per_s"] > 10 * fa["bytes"] / peaks["hbm_bytes_per_s"]  # compute-bound
    # the band at 504 keys a query and the small experts sit at the ridge: within a quarter either way
    for need in (swa, gmm):
        assert 0.75 < (need["flops"] / peaks["bf16_flops_per_s"]) / (need["bytes"] / peaks["hbm_bytes_per_s"]) < 1.25


def test_the_cell_is_found_and_reports_its_metrics():
    assert BENCH.cell(CELL)["chips"] == 1 and BENCH.traffic(BENCH.cell(CELL)["traffic"])["groups"] == 1
    traffic, base = BENCH.traffic("steady-1g-16k"), BENCH.traffic("steady-1g-8k")
    assert {k for k in traffic if traffic[k] != base[k]} == {"seq_len", "sequences_per_step", "name"}
    assert (traffic["seq_len"], traffic["sequences_per_step"]) == (16384, 1)
    assert {m["name"] for m in BENCH.end_to_end(CELL)} == {"tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in BENCH.per_layer(CELL)}
    assert set(NEW_METRICS) | {"gmm_ms", "ce_roofline", "moe_dropped", "moe_load_max_over_mean", "mfu", "device_grad_ms",
                               "device_update_ms", "step_p90_ms.steady", "quorum_ms", "ft_step_self_ms", "ffn_ms",
                               "experts_ms", "grad_recompute_ms", "alloc_peak_bytes"} <= per_layer
    # they count one head count over every layer, every chip's rows, or keys this file does not have
    assert not {"attn_roofline", "gmm_roofline", "gmm_held_roofline", "moe_held_share"} & per_layer
    for other in (w["name"] for w in BENCH.doc["workloads"] if w["name"] != CELL):
        assert not set(NEW_METRICS) & {m["name"] for m in BENCH.per_layer(other)}
    for name in NEW_METRICS:
        metric = next(m for m in BENCH.doc["per_layer"] if m["name"] == name)
        reader = BENCH.reader(name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["moves"], metric["source"])
        assert metric["workloads"] == [CELL]
    assert [m["name"] for m in BENCH.doc["per_layer"][-6:]] == list(NEW_METRICS)
    names = PROGRAM.kernel_names()
    assert set(names) == {"attn", "ce", "gmm", "swa"}
    assert names["swa"]("%tpuft_swa_bwd_dkdv_dq.3 = custom-call") and not names["attn"]("%tpuft_swa_fwd.1")
    assert names["attn"]("%tpuft_fa_bwd_dkdv_dq.3") and not names["swa"]("%tpuft_fa_fwd.1")


def _ctx(tmp_path, monkeypatch, summaries, kernels, config):
    stream = tmp_path / "g0.metrics.jsonl"
    stream.write_text("".join(json.dumps(dict(event="step_summary", t_mono=1.0 + i, step=i, **s)) + "\n"
                              for i, s in enumerate(summaries)))
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(stream))
    return {"trace": {"kernel_s_per_step": kernels}, "peaks": BENCH.peaks("TPU v5 lite"), "bench": BENCH,
            "config": config, "traffic": BENCH.traffic("steady-1g-16k"),
            "steady_steps": [{"start_mono_ns": 0.5e9, "ms": 10_000.0}]}


def test_the_new_readers_on_a_recorded_step(tmp_path, monkeypatch):
    c = BENCH.config("laguna-xs.2")
    summaries = [dict(moe_rows_held=4 * rows, moe_assignments=4 * 131_072) for rows in (16_000, 16_384, 17_000)]
    ctx = _ctx(tmp_path, monkeypatch, summaries, {"attn": 0.25, "swa": 0.04, "gmm": 0.03}, c)
    assert BENCH.reader("swa_attn_ms").read(ctx) == 40.0 and BENCH.reader("full_attn_ms").read(ctx) == 250.0
    need = BENCH.flops("tpuft_swa").per_step(c, ctx["traffic"])
    assert BENCH.reader("swa_attn_roofline").read(ctx) == pytest.approx(100 * need["flops"] / 197e12 / 0.04)
    need = BENCH.flops("tpuft_fa_full").per_step(c, ctx["traffic"])
    assert BENCH.reader("full_attn_roofline").read(ctx) == pytest.approx(100 * need["flops"] / 197e12 / 0.25)
    held = BENCH.flops("tpuft_gmm_small").per_step(c, 4 * 16_384)
    assert BENCH.reader("gmm_small_roofline").read(ctx) == pytest.approx(100 * held["bytes"] / 819e9 / 0.03)
    for name in ("swa_attn_roofline", "full_attn_roofline", "gmm_small_roofline"):
        assert 0 < BENCH.reader(name).read(ctx) < 100


def test_the_share_of_the_triangle_is_read_from_the_live_steps_compiled_calls(tmp_path, monkeypatch):
    """`swa_pairs_share` asks the live train step for its programs' text and
    sets the windowed calls' grid steps against a triangular walk's (the
    parsing of a compiled call is pinned where one is compiled:
    tests/test_chip_compile.py)."""
    from torchft_tpu.obs import opmap

    reader = BENCH.reader("swa_pairs_share")
    band = [dict(name=name, grid=[64, 63], block_q=512, seq=16_384) for name in ("tpuft_swa_fwd", "tpuft_swa_bwd_dkdv_dq")]
    whole = dict(name="tpuft_swa_fwd", grid=[64, 528], block_q=512, seq=16_384)
    ctx = dict(_ctx(tmp_path, monkeypatch, [], {}, BENCH.config("laguna-xs.2")), cell=BENCH.cell(CELL))

    class Step:
        def compiled_texts(self):
            return {"jit_value_and_grad": "gradient", "jit_apply": "update"}

    monkeypatch.setattr(opmap, "train_steps", lambda: [Step()])
    monkeypatch.setattr(reader, "grids", lambda text: list(band) if text == "gradient" else [])
    assert reader.read(ctx) == pytest.approx(63 / 528)
    with open(tmp_path / reader.FILE, encoding="utf-8") as f:
        written = json.load(f)
    assert written["steps"] == 2 * 64 * 63 and written["triangle"] == 2 * 64 * 528
    assert [c["triangle"] for c in written["calls"]] == [[64, 528]] * 2
    # a window layer that walked the whole triangle shows
    monkeypatch.setattr(reader, "grids", lambda text: [dict(band[0]), dict(whole)] if text == "gradient" else [])
    assert reader.read(ctx) == pytest.approx((63 + 528) / (2 * 528))
    # no kernel in the program (off the chip), no method on the step (an older program), no step
    monkeypatch.setattr(reader, "grids", lambda text: [])
    assert reader.read(ctx) is None
    monkeypatch.setattr(opmap, "train_steps", lambda: [object()])
    assert reader.read(ctx) is None
    monkeypatch.setattr(opmap, "train_steps", lambda: [])
    assert reader.read(ctx) is None


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read(tmp_path, monkeypatch):
    """A program without the counters or the kernels (the parent of the PR that
    added them), a configuration of another family: every new reader returns
    None and does not raise."""
    ctx = _ctx(tmp_path, monkeypatch, [dict(moe_dropped=0, moe_rows_held=5, moe_assignments=9)],
               {"attn": 0.01, "gmm": 0.01}, BENCH.config("moonlight-16b-a3b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
    ctx = _ctx(tmp_path, monkeypatch, [], {}, BENCH.config("laguna-xs.2"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name


# -- the job, end to end ---------------------------------------------------------


def _copy_with_a_tiny_share_cell(tmp_path, compute="bfloat16") -> str:
    root = make_copy(str(tmp_path))
    with open(os.path.join(root, "benchmark", "configs", "tiny-swa.json"), "w", encoding="utf-8") as f:
        json.dump(tiny(compute), f)
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    cell = "tiny-swa.tiny-steady"
    doc["configs"].append(dict(name="tiny-swa", source="none", file="benchmark/configs/tiny-swa.json", reduced=[], why="test"))
    doc["workloads"].append(dict(name=cell, config="tiny-swa", traffic="tiny-steady", chips=1, why="test"))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return root


def test_steady_job_with_a_tiny_share_on_the_cpu(tmp_path, monkeypatch):
    root = _copy_with_a_tiny_share_cell(tmp_path)
    cell = "tiny-swa.tiny-steady"
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
    for name in ("gmm_ms", "swa_attn_ms", "swa_attn_roofline", "full_attn_ms", "full_attn_roofline", "gmm_small_roofline",
                 "swa_pairs_share"):
        assert name not in per_layer  # no kernel runs on the CPU


def test_routing_ties_tool_counts_the_choices_that_differ(tmp_path):
    """`tools/routing_ties_swa.py` on the tiny cell: the float32 program's
    choices are the reference's in each of the four sparse layers, bf16 moves
    a few and fp8 more."""
    import subprocess
    import sys

    root = _copy_with_a_tiny_share_cell(tmp_path, "float32")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "routing_ties_swa.py"), "--workload",
         "tiny-swa.tiny-steady", "--seeds", "3,2147483999", "--platform", "cpu"], capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert lines[0]["sparse_layers"] == 4 and len(lines[0]["program_vs_float32"]) == 4
    last = lines[-1]
    assert last["seeds"] == 2 and last["program_vs_float32"]["max"] == 0.0
    assert 0.0 < last["reference_bfloat16_vs_float32"]["max"] < last["reference_float8_vs_float32"]["max"] < 0.5


def test_tie_free_tool_reads_the_comparison_without_the_routing_ties(tmp_path):
    """`tools/tie_free_swa.py` on the tiny cell in bf16: given the program's
    choices the reference is closer to the program than when it chooses for
    itself, and the fp8 control given the float32 choices stays far from it."""
    import subprocess
    import sys

    root = _copy_with_a_tiny_share_cell(tmp_path, "bfloat16")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "tie_free_swa.py"), "--workload", "tiny-swa.tiny-steady",
         "--seeds", "3,2147483999", "--control-seeds", "3", "--platform", "cpu"], capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert "control" in lines[0] and "control" not in lines[1]
    for line in lines[:2]:
        assert 0.0 < line["sound_tie_free"]["all"] <= line["sound"]["all"] and 0.0 < line["ties_alone"]["all"]
    last = lines[-1]
    assert last["seeds"] == 2 and last["control_seeds"] == 1
    assert last["sound_tie_free"]["all"]["max"] < last["control_tie_free"]["all"]["min"] <= last["control"]["all"]["min"] * 1.05
