"""The delta-attention sparse configuration's benchmark files on the CPU: the
plain reference against float64 and against the program at a tiny size, each
left-out piece against the whole, the operation counts against numbers worked
by hand at the cell's sizes, the new readers on what they read and on nothing.
Nothing is timed."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.spec import Benchmark

BENCH = Benchmark()
REFERENCE = BENCH.reference("kda_mla_moe_lm")
PROGRAM = BENCH.program("kda_mla_moe_lm")
SEEDS = (3, 2**31 + 5, 77)
CELL = "kimi-linear-48b-a3b.steady-1g-16k"
NEW_METRICS = ("kda_scan_ms", "kda_scan_roofline", "kda_mix_ms", "mla_nope_attn_ms", "mla_nope_attn_roofline",
               "gmm_held256_roofline", "kda_alpha_mean")


def tiny(compute: str = "float32", **changed):
    """The cut's layers 1-5 in small; experts 2-5 of the router's 8 held."""
    published = BENCH.config("kimi-linear-48b-a3b")
    config = dict(
        published, source="none: a test size", vocab_size=300, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_attention_heads=2, num_key_value_heads=2, num_experts=4, num_experts_per_token=2, model_max_length=256,
        linear_attn_config=dict(published["linear_attn_config"], head_dim=16, num_heads=2),
        expert_parallel=dict(chips=2, rank=0, router_outputs=8, first_expert_held=2),
        router_bias=dict(seed=5, scale=0.02),
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
        program=dict(remat=True, remat_keeps_attention=True, scan_unroll=8),
        # float32: rounding only; bfloat16: rounding and, at 160 positions a layer, a top-2 choice or two that falls the other way
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.15),
    )
    config.update(changed)
    return config


def one_step(config, seed, seq=80):
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
    assert int(counters["moe_dropped"]) == 0 and int(counters["moe_assignments"]) == 4 * 2 * 2 * 80
    assert 0 < float(counters["kda_alpha_mean"]) < 1


@pytest.mark.parametrize("piece", REFERENCE.LEFT_OUT)
def test_the_reference_without_a_piece_fails_the_comparison(piece):
    """What `tools/routing_ties_kda.py --left-out 1` shows on the chip, at a
    tiny size: the reference without the decay, the delta term, the
    convolutions' earlier taps, the q / k norm or the output gate, or with a
    rotated latent layer, put in the program's place, is far over any limit."""
    config = tiny("float32")
    weights, batch, _, _, _ = one_step(config, 9)
    indices = compare.sample_indices(9, weights)
    _, want = compare.sequence_by_sequence(REFERENCE, config, weights, batch, indices)
    one = REFERENCE.one_sequence_fn(config, "float32", left_out=piece)
    total = None
    for i in range(2):
        part = compare.sample(one(weights, batch["tokens"][i], batch["targets"][i])[1], indices)
        total = {k: v / 2 for k, v in part.items()} if total is None else {k: total[k] + v / 2 for k, v in part.items()}
    rel, _ = compare.grad_rel(total, want)
    assert rel > 0.1, (piece, rel)  # the rotation of ONE layer of five moves least: 0.155


def test_reference_in_float32_agrees_with_itself_in_float64():
    """The reference's own rounding: its float32 gradients against the same
    code in float64 (weights and arithmetic), far under any limit."""
    config = tiny("float32")
    weights = REFERENCE.make_weights(5, config)
    tokens = np.random.default_rng(5).integers(0, config["vocab_size"], size=(1, 80)).astype(np.int32)
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
            assert np.linalg.norm(a - b) <= 5e-5 * np.linalg.norm(b)


def test_the_recurrence_in_blocks_of_positions_is_the_recurrence_whole(monkeypatch):
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((256, 2, 8)), jnp.float32) for _ in range(3))
    g = -jnp.asarray(rng.uniform(0, 1, (256, 2, 8)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (256, 2)), jnp.float32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    blocks = REFERENCE._recurrence(q, k, v, g, beta)  # two blocks of 128 positions
    monkeypatch.setattr(REFERENCE, "POSITION_BLOCK", 256)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(REFERENCE._recurrence(q, k, v, g, beta)), rtol=1e-6, atol=1e-6)


def test_weights_come_from_the_seed_alone():
    config = tiny()
    a, b, c = (REFERENCE.make_weights(s, config) for s in (7, 7, 2**31 + 7))
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(c["embed"]))
    assert set(a) == {"embed", "final_norm", "lm_head", "kda_dense", "kda_layers", "mla_layers"}
    assert a["kda_layers"]["wq"].shape[0] == 3 and a["mla_layers"]["wq"].shape[0] == 1 and "router" not in a["kda_dense"]
    assert REFERENCE.router_bias(config).shape == (4, 8)
    assert REFERENCE.layer_plan(config) == [("kda", False), ("kda", True), ("kda", True), ("mla", True), ("kda", True)]
    stats = REFERENCE.decay_statistics(a, jnp.zeros((80,), jnp.int32), config)
    assert 0.5 < stats["mean"] < 1.0 and 0.0 < stats["share_under_half"] < 0.5


def test_operation_counts_from_shapes():
    c, t = BENCH.config("kimi-linear-48b-a3b"), BENCH.traffic("steady-1g-16k")
    flops = BENCH.flops("kda_mla_moe_lm")
    # ISSUE 48's counts: a KDA mixer, a latent mixer, an expert
    assert flops.kda_matmul_params(c) + flops.kda_other_params(c) == 39_518_368
    assert flops.mla_params(c) + 512 == 29_114_880 and flops.expert_params(c) == 7_077_888
    assert flops.held_experts_per_token(c) == 0.25
    sparse_kda = 39_518_368 + 2 * 2304 + 2304 * 256 + 9 * 7_077_888
    assert sparse_kda == 103_813_792
    assert flops.total_params(c) == 103_223_968 + 3 * 103_813_792 + 93_410_304 + 94_371_840 + 2304 == 602_449_792
    # uncut: 27 layers of 256 experts and the whole vocabulary: 48.4B without the embedding and head
    whole = dict(c, num_hidden_layers=27, num_experts=256, vocab_size=163_840, expert_parallel=None)
    assert round((flops.total_params(whole) - 2 * 2304 * 163_840) / 1e9, 1) == 48.4
    kda = BENCH.flops("tpuft_kda")
    assert kda.layers_within_depth(c) == 4 and BENCH.flops("tpuft_fa_mla_nope").layers_within_depth(c) == 1
    a_chunk = 2 * 64 * 64 * 128 + 2 / 3 * 64 ** 3 + 2 * 64 * 64 * 128 + 6 * 64 * 128 * 128 + 64 * 64 * 128 + 128 * 128
    assert kda.forward_flops_per_position(128) == pytest.approx(a_chunk / 64)
    scan = kda.per_step(c, t)
    assert scan["flops"] == pytest.approx(3 * a_chunk / 64 * 16_384 * 32 * 4)
    assert scan["bytes"] == 16_384 * 32 * 4 * ((4 * 256 + 512 + 4) + (7 * 256 + 2 * 512 + 8))
    latent = 3 * 2 * 32 * (192 + 128) * 8192.5
    assert flops.mixer_flops_per_token(c, 16_384) == pytest.approx(4 * 3 * a_chunk / 64 * 32 + latent)
    fa = BENCH.flops("tpuft_fa_mla_nope").per_step(c, t)
    assert fa["flops"] == pytest.approx(32 * 2 * 16_384 * 16_385 / 2 * (3 * 192 + 3 * 128))
    assert fa["flops"] * 5 == pytest.approx(BENCH.flops("tpuft_fa_mla").per_step(dict(c, n_routed_experts=8), t)["flops"])
    gmm = BENCH.flops("tpuft_gmm_held256").per_step(c, 4 * 4_096)
    assert gmm["flops"] == 9 * 2.0 * 16_384 * 2304 * 1024
    peaks = BENCH.peaks("TPU v5 lite")
    # the scan is bound by HBM by these counts, attention by the MXU; at 512 rows an expert the grouped matmuls'
    # two bounds meet (3.5 ms of products, 3.4 ms of traffic)
    for need, by_hbm in ((scan, True), (fa, False)):
        assert (need["bytes"] / peaks["hbm_bytes_per_s"] > need["flops"] / peaks["bf16_flops_per_s"]) == by_hbm
    assert 0.9 < (gmm["bytes"] / peaks["hbm_bytes_per_s"]) / (gmm["flops"] / peaks["bf16_flops_per_s"]) < 1.1


def test_the_cell_is_found_and_reports_its_metrics():
    cell = BENCH.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("kimi-linear-48b-a3b", "steady-1g-16k", 1)
    reported = {m["name"] for m in BENCH.per_layer(CELL)}
    assert set(NEW_METRICS) | {"gmm_ms", "ce_roofline", "moe_dropped", "moe_load_max_over_mean", "mfu", "device_grad_ms",
                               "head_loss_ms", "experts_ms", "ffn_ms", "grad_recompute_ms", "step_p90_ms.steady",
                               "quorum_ms", "commit_vote_ms", "exchange_exposed_ms", "device_step_ms",
                               "alloc_peak_bytes"} <= reported
    assert not {"moe_held_share", "attn_roofline", "mla_attn_ms", "mla_attn_roofline", "gmm_held_roofline"} & reported
    for other in (w["name"] for w in BENCH.doc["workloads"] if w["name"] != CELL):
        assert not set(NEW_METRICS) & {m["name"] for m in BENCH.per_layer(other)}
    names = [m["name"] for m in BENCH.doc["per_layer"]]
    assert [n for n in names if n in NEW_METRICS] == list(NEW_METRICS)
    names = PROGRAM.kernel_names()
    assert set(names) == {"attn", "ce", "gmm", "kda"} and names["kda"]("%tpuft_kda_fwd.13")


def _ctx(tmp_path, monkeypatch, summaries, kernels, config):
    stream = tmp_path / "g0.metrics.jsonl"
    stream.write_text("".join(json.dumps(dict(event="step_summary", t_mono=1.0 + i, step=i, **s)) + "\n"
                              for i, s in enumerate(summaries)))
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(stream))
    return {"trace": {"kernel_s_per_step": kernels}, "peaks": BENCH.peaks("TPU v5 lite"), "bench": BENCH,
            "config": config, "traffic": BENCH.traffic("steady-1g-16k"),
            "steady_steps": [{"start_mono_ns": 0.5e9, "ms": 10_000.0}]}


def test_the_new_readers_on_a_recorded_step(tmp_path, monkeypatch):
    c = BENCH.config("kimi-linear-48b-a3b")
    summaries = [dict(moe_rows_held=rows, moe_assignments=524_288, kda_alpha_mean=alpha)
                 for rows, alpha in ((16_000, 0.82), (16_400, 0.83), (16_900, 0.84))]
    ctx = _ctx(tmp_path, monkeypatch, summaries, {"attn": 0.0937, "gmm": 0.0122, "kda": 0.234}, c)
    assert BENCH.reader("kda_scan_ms").read(ctx) == pytest.approx(234.0)
    assert BENCH.reader("mla_nope_attn_ms").read(ctx) == pytest.approx(93.7)
    scan = BENCH.flops("tpuft_kda").per_step(c, ctx["traffic"])
    assert BENCH.reader("kda_scan_roofline").read(ctx) == pytest.approx(100 * scan["bytes"] / 819e9 / 0.234)
    fa = BENCH.flops("tpuft_fa_mla_nope").per_step(c, ctx["traffic"])
    assert BENCH.reader("mla_nope_attn_roofline").read(ctx) == pytest.approx(100 * fa["flops"] / 197e12 / 0.0937)
    held = BENCH.flops("tpuft_gmm_held256").per_step(c, 16_400)
    assert BENCH.reader("gmm_held256_roofline").read(ctx) == pytest.approx(100 * held["flops"] / 197e12 / 0.0122)
    assert BENCH.reader("kda_alpha_mean").read(ctx) == 0.83
    for name in ("kda_scan_roofline", "mla_nope_attn_roofline", "gmm_held256_roofline"):
        assert 0 < BENCH.reader(name).read(ctx) < 100


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read(tmp_path, monkeypatch):
    """A program without the counter, the part or the kernels (the parent of
    the PR that added them), a configuration of another family: every new
    reader returns None and does not raise."""
    ctx = _ctx(tmp_path, monkeypatch, [dict(moe_dropped=0, moe_rows_held=5, moe_assignments=9)],
               {"attn": 0.01, "gmm": 0.01}, BENCH.config("moonlight-16b-a3b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
    ctx = _ctx(tmp_path, monkeypatch, [], {}, BENCH.config("kimi-linear-48b-a3b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
