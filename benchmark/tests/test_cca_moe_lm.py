"""The compressed-attention sparse configuration's benchmark files on the CPU:
the plain reference against float64 and against the program at a tiny size, the
operation counts against numbers worked by hand at the cell's sizes, the new
readers on what they read and on nothing.  Nothing is timed."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.spec import Benchmark

BENCH = Benchmark()
REFERENCE = BENCH.reference("cca_moe_lm")
PROGRAM = BENCH.program("cca_moe_lm")
SEEDS = (3, 2**31 + 5, 77)
CELL = "zaya1-8b.steady-1g-16k"
NEW_METRICS = ("cca_mix_ms", "cca_attn_ms", "cca_attn_roofline", "router_mlp_ms", "gmm_wide_roofline", "moe_skipped_share")


def tiny(compute: str = "float32", **changed):
    """The cut's 4 layers in small; experts 4-7 of the router's 8 (and the skip choice) held."""
    config = dict(
        source="none: a test size", architecture="cca_moe_lm", vocab_size=300, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16, cca_time0=2, cca_time1=2, moe_intermediate_size=32,
        num_experts=4, num_experts_per_tok=1, router_hidden_size=16, layer_types=["hybrid"] * 6, hidden_act="silu",
        attention_bias=False, lm_head_bias=False, sliding_window=None, tie_word_embeddings=True, rms_norm_eps=1e-5,
        max_position_embeddings=256, partial_rotary_factor=0.5,
        rope_parameters={"hybrid": dict(partial_rotary_factor=0.5, rope_theta=100.0, rope_type="default"),
                         "rope_type": "default"},
        expert_parallel=dict(chips=2, rank=1, routed_experts=8, router_outputs=9, first_expert_held=4),
        router_bias=dict(seed=5, scale=0.002),
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
        program=dict(remat=True, remat_keeps_attention=True, scan_unroll=8),
        # float32: rounding only; bfloat16: rounding and, at 128 positions a layer, a top-1 choice or two that falls
        # the other way and swaps a position's whole expert
        correct=dict(grad_rel_limit=1e-4 if compute == "float32" else 0.15),
    )
    config.update(changed)
    return config


def one_step(config, seed, seq=64):
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
    assert int(counters["moe_dropped"]) == 0 and int(counters["moe_assignments"]) == 4 * 2 * 64
    assert int(counters["moe_rows_held"]) + int(counters["moe_skipped"]) < int(counters["moe_assignments"])


def test_reference_in_float32_agrees_with_itself_in_float64():
    """The reference's own rounding: its float32 gradients against the same
    code in float64 (weights and arithmetic), far under any limit."""
    config = tiny("float32")
    weights = REFERENCE.make_weights(5, config)
    tokens = np.random.default_rng(5).integers(0, config["vocab_size"], size=(1, 64)).astype(np.int32)
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


def test_the_head_in_blocks_of_rows_is_the_head_whole(monkeypatch):
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    embed = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 40, 64))
    whole = REFERENCE._head_loss(h, embed, targets, "float32")
    monkeypatch.setattr(REFERENCE, "HEAD_BLOCK", 16)
    blocks = REFERENCE._head_loss(h, embed, targets, "float32")
    logits = np.asarray(h) @ np.asarray(embed).T
    want = np.mean(np.log(np.exp(logits).sum(-1)) - logits[np.arange(64), np.asarray(targets)])
    np.testing.assert_allclose([float(whole), float(blocks)], [want, want], rtol=1e-5)


def test_weights_come_from_the_seed_alone():
    config = tiny()
    a, b, c = (REFERENCE.make_weights(s, config) for s in (7, 7, 2**31 + 7))
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(c["embed"]))
    # the two merges are buffers of their own (a step donates each leaf), at one and zero
    merge = np.asarray(a["layers"]["attn_merge"])
    assert a["layers"]["attn_merge"] is not a["layers"]["mlp_merge"]
    assert np.array_equal(merge[:, 0], np.ones_like(merge[:, 0])) and not merge[:, 1].any()
    # the router's second and third matrices have zero mean over their inputs
    assert abs(float(jnp.mean(a["layers"]["router"]["w3"], axis=-2).max())) < 1e-6
    assert REFERENCE.router_bias(config).shape == (4, 9)


def test_operation_counts_from_shapes():
    c, t = BENCH.config("zaya1-8b"), BENCH.traffic("steady-1g-16k")
    flops = BENCH.flops("cca_moe_lm")
    # attention: 2 * 2048 * 1024 + 2 * 2048 * 256 + 10 heads * 2 taps * 128 * 128
    assert flops.attention_params(c) == 4_194_304 + 1_048_576 + 327_680 == 5_570_560
    # router: 2048 * 256 + 2 * 256 * 256 + 256 * 17
    assert flops.router_params(c) == 524_288 + 131_072 + 4_352 == 659_712
    assert flops.expert_params(c) == 12_582_912 and flops.held_experts_per_token(c) == pytest.approx(8 / 17)
    layer = 5_570_560 + 659_712 + 8 / 17 * 12_582_912
    assert flops.matmul_params(c) == pytest.approx(4 * layer + 2048 * 131_136)
    assert flops.attention_flops_per_token(c, 16_384) == pytest.approx(4 * 3 * 2 * 8 * 2 * 128 * 8192.5)
    # 696,250,376 held: a layer 106,920,450 (ISSUE 41's count), the embedding once, the final norm
    assert flops.total_params(c) == 4 * 106_920_450 + 268_566_528 + 2048 == 696_250_376
    # uncut: 40 layers of 16 experts and the whole vocabulary, 8.30B without the embedding
    whole = dict(c, num_hidden_layers=40, num_experts=16, vocab_size=262_272, expert_parallel=None)
    assert flops.total_params(whole) - 2048 * 262_272 - 2048 == 8_303_349_840
    fa = BENCH.flops("tpuft_fa_cca").per_step(c, t)
    assert fa["flops"] == pytest.approx(4 * 8 * 6 * 2 * 16_384 * 16_385 / 2 * 128)
    assert fa["bytes"] == 4 * (8 * (6 * 16_384 * 128 * 2 + 3 * 16_384 * 4) + 2 * 6 * 16_384 * 128 * 2)
    gmm = BENCH.flops("tpuft_gmm_wide").per_step(c, 4 * 7_700)
    assert gmm["flops"] == 9 * 2.0 * 30_800 * 2048 * 2048
    peaks = BENCH.peaks("TPU v5 lite")
    for need in (fa, gmm):  # both bound by the MXU by these counts
        assert need["flops"] / peaks["bf16_flops_per_s"] > need["bytes"] / peaks["hbm_bytes_per_s"]


def test_the_cell_is_found_and_reports_its_metrics():
    cell = BENCH.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("zaya1-8b", "steady-1g-16k", 1)
    reported = {m["name"] for m in BENCH.per_layer(CELL)}
    assert set(NEW_METRICS) | {"gmm_ms", "ce_roofline", "moe_dropped", "moe_load_max_over_mean", "mfu", "device_grad_ms",
                               "head_loss_ms", "experts_ms", "grad_recompute_ms", "step_p90_ms.steady"} <= reported
    assert not {"ffn_ms", "moe_held_share", "attn_roofline", "full_attn_ms", "gmm_held_roofline"} & reported
    for other in (w["name"] for w in BENCH.doc["workloads"] if w["name"] != CELL):
        assert not set(NEW_METRICS) & {m["name"] for m in BENCH.per_layer(other)}
    # in the order given, wherever later PRs have appended theirs
    names = [m["name"] for m in BENCH.doc["per_layer"]]
    assert [n for n in names if n in NEW_METRICS] == list(NEW_METRICS)
    names = PROGRAM.kernel_names()
    assert set(names) == {"attn", "ce", "gmm"} and names["attn"]("%tpuft_fa_bwd_dkdv_dq.3")


def _ctx(tmp_path, monkeypatch, summaries, kernels, config):
    stream = tmp_path / "g0.metrics.jsonl"
    stream.write_text("".join(json.dumps(dict(event="step_summary", t_mono=1.0 + i, step=i, **s)) + "\n"
                              for i, s in enumerate(summaries)))
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(stream))
    return {"trace": {"kernel_s_per_step": kernels}, "peaks": BENCH.peaks("TPU v5 lite"), "bench": BENCH,
            "config": config, "traffic": BENCH.traffic("steady-1g-16k"),
            "steady_steps": [{"start_mono_ns": 0.5e9, "ms": 10_000.0}]}


def test_the_new_readers_on_a_recorded_step(tmp_path, monkeypatch):
    c = BENCH.config("zaya1-8b")
    summaries = [dict(moe_rows_held=rows, moe_assignments=65_536, moe_skipped=skipped)
                 for rows, skipped in ((30_000, 3_800), (30_800, 3_855), (31_500, 3_900))]
    ctx = _ctx(tmp_path, monkeypatch, summaries, {"attn": 0.07, "gmm": 0.03}, c)
    assert BENCH.reader("cca_attn_ms").read(ctx) == 70.0
    need = BENCH.flops("tpuft_fa_cca").per_step(c, ctx["traffic"])
    assert BENCH.reader("cca_attn_roofline").read(ctx) == pytest.approx(100 * need["flops"] / 197e12 / 0.07)
    held = BENCH.flops("tpuft_gmm_wide").per_step(c, 30_800)
    assert BENCH.reader("gmm_wide_roofline").read(ctx) == pytest.approx(100 * held["flops"] / 197e12 / 0.03)
    assert BENCH.reader("moe_skipped_share").read(ctx) == pytest.approx(3_855 / 65_536)
    for name in ("cca_attn_roofline", "gmm_wide_roofline"):
        assert 0 < BENCH.reader(name).read(ctx) < 100


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read(tmp_path, monkeypatch):
    """A program without the counter, the part or the kernels (the parent of
    the PR that added them), a configuration of another family: every new
    reader returns None and does not raise."""
    ctx = _ctx(tmp_path, monkeypatch, [dict(moe_dropped=0, moe_rows_held=5, moe_assignments=9)],
               {"attn": 0.01, "gmm": 0.01}, BENCH.config("moonlight-16b-a3b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
    ctx = _ctx(tmp_path, monkeypatch, [], {}, BENCH.config("zaya1-8b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
