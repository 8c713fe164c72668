"""The Gated DeltaNet / gated attention sparse configuration's benchmark files
on the CPU: the plain reference against float64 and against the program at a
tiny size, each left-out piece against the whole, the operation counts against
numbers worked by hand at the cell's sizes, the new readers on what they read
and on nothing.  Nothing is timed."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare
from benchmark.spec import Benchmark

BENCH = Benchmark()
REFERENCE = BENCH.reference("gdn_moe_lm")
PROGRAM = BENCH.program("gdn_moe_lm")
SEEDS = (3, 2**31 + 5)
CELL = "qwen3-next-80b-a3b.steady-1g-16k"
NEW_METRICS = ("gdn_scan_ms", "gdn_scan_roofline", "gdn_mix_ms", "gated_attn_ms", "gated_attn_roofline", "gmm_held512_roofline",
               "gdn_alpha_mean", "moe_shared_gate_mean")


def tiny(compute: str = "float32", **changed):
    """The cut's four layers in small; experts 2-5 of the router's 8 held."""
    published = BENCH.config("qwen3-next-80b-a3b")
    config = dict(
        published, source="none: a test size", vocab_size=300, hidden_size=64, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, head_dim=32, num_attention_heads=4, num_key_value_heads=2, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16, num_experts=4, num_experts_per_tok=3,
        max_position_embeddings=256, rope_theta=100.0, expert_parallel=dict(chips=2, rank=0, router_outputs=8, first_expert_held=2),
        training=dict(compute_dtype=compute, param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
        program=dict(remat=True, remat_keeps_attention=True, scan_unroll=8),
        correct=dict(grad_rel_limit=1e-4),
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
    assert int(counters["moe_dropped"]) == 0 and int(counters["moe_assignments"]) == 4 * 2 * 3 * 80
    assert 0 < float(counters["gdn_alpha_mean"]) < 1 and 0 < float(counters["moe_shared_gate_mean"]) < 1


@pytest.mark.parametrize("piece", REFERENCE.LEFT_OUT)
def test_the_reference_without_a_piece_fails_the_comparison(piece):
    """What `tools/routing_ties_gdn.py --left-out 1` shows on the chip, at a
    tiny size: the reference without one piece, or with the wrong mechanism in
    its place, put in the program's place, is far over any limit."""
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
    assert rel > 0.1, (piece, rel)


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
        loss64, grads64 = jax.value_and_grad(REFERENCE.loss)(wide, jnp.asarray(tokens[0]), jnp.asarray(targets[0]), REFERENCE.sizes_of(config))
        assert jax.tree.leaves(grads64)[0].dtype == jnp.float64
        assert abs(float(loss32) - float(loss64)) / float(loss64) < 1e-6
        for a, b in zip(jax.tree.leaves(grads32), jax.tree.leaves(grads64)):
            a, b = np.asarray(a, np.float64), np.asarray(b)
            assert np.linalg.norm(a - b) <= 5e-5 * np.linalg.norm(b)


def test_weights_come_from_the_seed_alone():
    config = tiny()
    a, b, c = (REFERENCE.make_weights(s, config) for s in (7, 7, 2**31 + 7))
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(c["embed"]))
    assert set(a) == {"embed", "final_norm", "lm_head", "gdn_layers", "attn_layers"}
    assert a["gdn_layers"]["wq"].shape[0] == 3 and a["attn_layers"]["wq"].shape[0] == 1
    assert REFERENCE.layer_plan(config) == [("gdn", True)] * 3 + [("attention", True)]
    assert abs(float(jnp.mean(a["final_norm"]))) < 0.05 and 0.05 < float(jnp.std(a["final_norm"])) < 0.2  # zero-centred, not zero
    stats = REFERENCE.decay_statistics(a, jnp.zeros((80,), jnp.int32), config)
    assert 0.5 < stats["mean"] < 1.0 and 0.0 <= stats["share_under_half"] < 0.5


def test_operation_counts_from_shapes():
    c, t = BENCH.config("qwen3-next-80b-a3b"), BENCH.traffic("steady-1g-16k")
    flops = BENCH.flops("gdn_moe_lm")
    # ISSUE 68's counts: a Gated DeltaNet mixer, an attention mixer, an expert
    assert flops.gdn_matmul_params(c) + flops.gdn_other_params(c) == 33_718_464
    assert flops.attention_matmul_params(c) + 512 == 27_263_488 and flops.expert_params(c) == 3_145_728
    assert flops.held_experts_per_token(c) == 0.625
    assert flops.total_params(c) == 3 * 138_582_208 + 132_127_232 + 77_791_232 + 2048 == 625_667_136
    assert flops.published_params(c) == 36 * 37_918_912 + 12 * 31_463_936 + 48 * 512 * 3_145_728 + 622_329_856 + 2048 == 79_674_391_296
    gdn = BENCH.flops("tpuft_gdn")
    assert gdn.layers_within_depth(c) == 3 and BENCH.flops("tpuft_fa_gated").attention_layers(c) == 1
    a_chunk = 2 * 64 * 64 * 128 + 2 / 3 * 64 ** 3 + 2 * 64 * 64 * 128 + 6 * 64 * 128 * 128 + 64 * 64 * 128 + 128 * 128
    assert gdn.forward_flops_per_position(128, 128) == pytest.approx(a_chunk / 64) == pytest.approx(BENCH.flops("tpuft_kda").forward_flops_per_position(128))
    scan = gdn.per_step(c, t)
    assert scan["flops"] == pytest.approx(3 * a_chunk / 64 * 16_384 * 32 * 3)
    # q and k once a KEY head (read forward and backward, dq and dk written), v, o, their cotangents and the four scalars a value head
    assert scan["bytes"] == 16_384 * 3 * (16 * (2 + 4) * 256 + 32 * ((2 + 4) * 256 + (2 + 4) * 4))
    attention = 3 * 2 * 16 * (256 + 256) * 8192.5
    assert flops.mixer_flops_per_token(c, 16_384) == pytest.approx(3 * 3 * a_chunk / 64 * 32 + attention)
    fa = BENCH.flops("tpuft_fa_gated").per_step(c, t)
    assert fa["flops"] == pytest.approx(16 * 6 * 2 * 16_384 * 16_385 / 2 * 256)
    assert fa["bytes"] == 16 * (6 * 16_384 * 512 + 3 * 16_384 * 4) + 2 * 6 * 16_384 * 512
    gmm = BENCH.flops("tpuft_gmm_held512").per_step(c, 4 * 10_240)
    assert gmm["flops"] == 9 * 2.0 * 40_960 * 2048 * 512
    peaks = BENCH.peaks("TPU v5 lite")
    # by these counts the scan and the grouped matmuls (320 rows an expert: the matrices lead) are bound by HBM, attention by the MXU
    for need, by_hbm in ((scan, True), (fa, False), (gmm, True)):
        assert (need["bytes"] / peaks["hbm_bytes_per_s"] > need["flops"] / peaks["bf16_flops_per_s"]) == by_hbm


def test_the_cell_is_found_and_reports_its_metrics():
    cell = BENCH.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("qwen3-next-80b-a3b", "steady-1g-16k", 1)
    reported = {m["name"] for m in BENCH.per_layer(CELL)}
    # a SUBSET of what the cell reports, wherever later PRs put their own entries
    assert set(NEW_METRICS) | {"gmm_ms", "ce_roofline", "moe_dropped", "moe_load_max_over_mean", "moe_held_share", "mfu", "device_grad_ms",
                               "device_update_ms", "head_loss_ms", "attn_proj_ms", "experts_ms", "grad_fwd_ms", "grad_bwd_ms",
                               "grad_recompute_ms", "unattributed_ms", "step_p90_ms.steady", "quorum_ms", "quorum_wait_ms",
                               "ft_step_self_ms", "commit_vote_ms", "exchange_exposed_ms", "device_step_ms", "alloc_peak_bytes"} <= reported
    assert not {"attn_roofline", "kda_scan_ms", "kda_scan_roofline", "kda_mix_ms", "kda_alpha_mean", "gmm_held256_roofline", "ffn_ms"} & reported
    for other in (w["name"] for w in BENCH.doc["workloads"] if w["name"] != CELL):
        assert not set(NEW_METRICS) & {m["name"] for m in BENCH.per_layer(other)}
    assert {m["name"] for m in BENCH.end_to_end(CELL)} == {"tokens_per_s", "setup_s"}
    names = PROGRAM.kernel_names()
    assert set(names) == {"attn", "ce", "gmm", "gdn"} and names["gdn"]("%tpuft_kda_fwd.13")


def _ctx(tmp_path, monkeypatch, summaries, kernels, config):
    stream = tmp_path / "g0.metrics.jsonl"
    stream.write_text("".join(json.dumps(dict(event="step_summary", t_mono=1.0 + i, step=i, **s)) + "\n"
                              for i, s in enumerate(summaries)))
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(stream))
    return {"trace": {"kernel_s_per_step": kernels}, "peaks": BENCH.peaks("TPU v5 lite"), "bench": BENCH,
            "config": config, "traffic": BENCH.traffic("steady-1g-16k"),
            "steady_steps": [{"start_mono_ns": 0.5e9, "ms": 10_000.0}]}


def test_the_new_readers_on_a_recorded_step(tmp_path, monkeypatch):
    c = BENCH.config("qwen3-next-80b-a3b")
    summaries = [dict(moe_rows_held=rows, moe_assignments=655_360, gdn_alpha_mean=alpha, moe_shared_gate_mean=gate)
                 for rows, alpha, gate in ((41_000, 0.88, 0.50), (41_400, 0.89, 0.51), (41_900, 0.90, 0.52))]
    ctx = _ctx(tmp_path, monkeypatch, summaries, {"attn": 0.045, "gmm": 0.0213, "gdn": 0.0803}, c)
    assert BENCH.reader("gdn_scan_ms").read(ctx) == pytest.approx(80.3)
    assert BENCH.reader("gated_attn_ms").read(ctx) == pytest.approx(45.0)
    scan = BENCH.flops("tpuft_gdn").per_step(c, ctx["traffic"])
    assert BENCH.reader("gdn_scan_roofline").read(ctx) == pytest.approx(100 * scan["bytes"] / 819e9 / 0.0803)
    fa = BENCH.flops("tpuft_fa_gated").per_step(c, ctx["traffic"])
    assert BENCH.reader("gated_attn_roofline").read(ctx) == pytest.approx(100 * fa["flops"] / 197e12 / 0.045)
    held = BENCH.flops("tpuft_gmm_held512").per_step(c, 41_400)
    assert BENCH.reader("gmm_held512_roofline").read(ctx) == pytest.approx(100 * held["bytes"] / 819e9 / 0.0213)
    assert BENCH.reader("gdn_alpha_mean").read(ctx) == 0.89 and BENCH.reader("moe_shared_gate_mean").read(ctx) == 0.51
    for name in ("gdn_scan_roofline", "gated_attn_roofline", "gmm_held512_roofline"):
        assert 0 < BENCH.reader(name).read(ctx) < 100


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read(tmp_path, monkeypatch):
    """A program without the counter, the part or the kernels (the parent of
    the PR that added them), a configuration of another family: every new
    reader returns None and does not raise."""
    ctx = _ctx(tmp_path, monkeypatch, [dict(moe_dropped=0, moe_rows_held=5, moe_assignments=9)],
               {"attn": 0.01, "gmm": 0.01, "kda": 0.01}, BENCH.config("kimi-linear-48b-a3b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
    ctx = _ctx(tmp_path, monkeypatch, [], {}, BENCH.config("qwen3-next-80b-a3b"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
