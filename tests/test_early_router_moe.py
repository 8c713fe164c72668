"""SmallThinker-shaped models (a router that reads the layer's input before
attention, ReGLU experts under a softmax over the kept, window layers under
RoPE 3:1 with full layers that have no position term, seven query heads a KV
head) through the program, on the CPU at small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/early_router_moe_lm.py``).  What only
this architecture has is tested here: the un-rotated kind's q and k against the
bare projections, the router on the normed input, operation counts against
hand arithmetic, the new readers on what they read and on nothing.
"""

import contextlib
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, HELD, REMAT, Architecture, Case, ExpertLayer, Piece, Tiny, batches, pytest_generate_tests, store,
    test_a_model_without_a_piece_is_another_model, test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_rematerialised_layers_give_the_gradients_of_the_stored_ones, test_the_adapter_raises_on_what_it_does_not_honour,
    test_the_published_configuration_is_handed_over_whole, test_the_shares_add_up_to_the_uncut_layer,
    test_the_tree_goes_through, test_the_tree_is_the_reference_s)
from benchmark import spec
from torchft_tpu.models import TransformerConfig
from torchft_tpu.models import attention, moe
from torchft_tpu.models.moe import moe_layer, routing
from torchft_tpu.models.transformer import loss_and_counters

REFERENCE = BENCH.reference("early_router_moe_lm")
PROGRAM = BENCH.program("early_router_moe_lm")
PUBLISHED = BENCH.config("smallthinker-21b-a3b")
CELL = "smallthinker-21b-a3b.steady-1g-16k"
NEW_METRICS = ("swa4k_attn_ms", "swa4k_attn_roofline", "full_nope_attn_ms", "full_nope_attn_roofline",
               "gmm_reglu_roofline", "early_router_ms", "reglu_active_share")

SEQ, WINDOW, LAYERS = 32, 8, 5
SIZES = """32 positions under a window of 8: a window layer's band is a quarter of the triangle, so the band and the
triangle differ in most pairs.  Five layers — a period (a full layer without a position term, three window layers under
RoPE) and the next period's full layer: both kinds, the full layers' stack in two runs around the window layers'.  7 query
heads over ONE KV head of 16 (the group of 7 is there), 8 routed experts of width 32, 3 a token.  The layouts keep a
published length: the first `num_hidden_layers` entries count.  Float32 throughout."""
CONFIG = dict(
    architecture="early_router_moe_lm", vocab_size=256, hidden_size=64, num_hidden_layers=LAYERS, num_attention_heads=7,
    num_key_value_heads=1, head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rope_layout=[0, 1, 1, 1] * 3, sliding_window_layout=[0, 1, 1, 1] * 3, sliding_window_size=WINDOW,
    rope_theta=1e4, rope_scaling=None, rms_norm_eps=1e-6, max_position_embeddings=64, tie_word_embeddings=False,
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
    program=dict(remat=False, scan_unroll=8),
)
# The same model as one of the four chips that share each layer holds it:
# experts 2 and 3 of the router's 8.
SHARE = dict(CONFIG, moe_num_primary_experts=2,
             expert_parallel=dict(chips=4, rank=1, router_outputs=8, first_expert_held=2))
# Both sides compute in float32 on the CPU, so they differ by the order of
# their sums alone: every leaf agrees to under 1e-5 of its norm.  The least of
# the named omissions moves its leaf by far more, so 3e-5 passes the one and
# fails the others.
LEAF_TOLERANCE = 3e-5


_batch = batches(CONFIG["vocab_size"], SEQ)


def _counters(counters, config) -> None:
    assert int(counters["moe_dropped"]) == 0
    assert np.asarray(counters["moe_tokens_per_expert"]).sum(axis=1).tolist() == [2 * SEQ * 3] * LAYERS
    # ReLU leaves about half of the held experts' hidden units above zero; the denominator is rows x width
    held_rows = int(counters["moe_rows_held"]) if "moe_rows_held" in counters else LAYERS * 2 * SEQ * 3
    assert int(counters["moe_units_held"]) == held_rows * 32
    assert 0.4 < int(counters["moe_active_units"]) / int(counters["moe_units_held"]) < 0.6


# What the reference computes with one piece of the published mathematics left
# out or put in the wrong layers (`REFERENCE.LEFT_OUT`): the program as
# published has to fail the comparison with each.
LEFT_OUT = (
    "early_router",        # the router fed the experts' input h2
    "relu",                # SiLU for ReLU
    "nope_on_full",        # RoPE on the full layers too
    "rope_on_window",      # no RoPE on the window layers
    "window",              # the window dropped
    "softmax_over_kept",   # the gates a softmax over all the outputs, left as it is
)
assert set(LEFT_OUT) == set(REFERENCE.LEFT_OUT)
# The same from the other side: a PROGRAM that runs the wrong mechanism in a
# kind of layer (what `benchmark/tools/routing_ties_reglu.py --wrong 1` tries on
# the chip) fails against the reference as published.
WRONG_PROGRAMS = spec._module("tools", "routing_ties_reglu", BENCH.bench_dir).wrong_programs
WRONG = ("window_layers_over_the_whole_triangle", "full_layers_under_the_window", "rope_on_the_full_layers",
         "router_on_the_experts_input", "silu_for_relu")
assert set(WRONG_PROGRAMS(PROGRAM.transformer_config(SHARE), WINDOW)) == set(WRONG)
PIECES = [Piece(piece, "reference", piece) for piece in LEFT_OUT] + [
    Piece(wrong, "program", lambda cfg, weights, wrong=wrong: (contextlib.nullcontext(), WRONG_PROGRAMS(cfg, WINDOW)[wrong], weights))
    for wrong in WRONG]


def test_the_router_fed_the_normed_input_chooses_the_same_experts() -> None:
    """The configuration file's `assumed` note: with norm weights of one
    RMSNorm scales a position by a positive number, so a router fed h = RMSNorm(x)
    takes the six (here three) experts a router fed x takes, and the two differ
    in the softmax's temperature alone."""
    weights = REFERENCE.make_weights(8, CONFIG)
    x = weights["embed"][_batch(8)["tokens"]] * 3.0  # [2, S, E], positions of unequal norm
    w = weights["layers"]["router"][0]
    assert bool(jnp.all(weights["layers"]["attn_norm"] == 1.0))
    h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    gates_x, chosen_x, _ = routing(x, w, top_k=3, norm_topk=True, score="softmax")
    gates_h, chosen_h, _ = routing(h, w, top_k=3, norm_topk=True, score="softmax")
    assert np.array_equal(np.sort(np.asarray(chosen_x), axis=1), np.sort(np.asarray(chosen_h), axis=1))
    assert float(jnp.max(jnp.abs(gates_x - gates_h))) > 0.01


def test_an_unrotated_kinds_q_and_k_are_the_projections_bit_for_bit(monkeypatch) -> None:
    """`rotary_fraction == 0` on the plain (GQA) branch skips `_rotary`
    outright: what reaches the attention call in a full layer is h Wq and h Wk
    as the products give them, in every bit; a window layer's are turned."""
    cfg = dataclasses.replace(PROGRAM.transformer_config(SHARE), n_layers=2,
                              pattern=PROGRAM.transformer_config(SHARE).pattern[:2])
    weights = REFERENCE.make_weights(3, dict(SHARE, num_hidden_layers=2))
    batch = _batch(3)
    seen, turned = [], []
    real_attention, real_rotary = attention.flash_attention, attention._rotary
    monkeypatch.setattr(attention, "flash_attention",
                        lambda q, k, v, **kw: seen.append((q, k, kw.get("window"))) or real_attention(q, k, v, **kw))
    monkeypatch.setattr(attention, "_rotary",
                        lambda x, positions, kind, **kw: turned.append(kind.stack) or real_rotary(x, positions, kind, **kw))
    loss_and_counters(weights, batch, cfg)
    assert [window for _, _, window in seen] == [None, WINDOW] and turned == ["window_layers"] * 2
    q, k, _ = seen[0]
    x = weights["embed"][batch["tokens"]]
    w = {name: leaf[0] for name, leaf in weights["layers"].items()}
    h = attention.rms_norm(x, w["attn_norm"], cfg.rms_eps)
    want_q = (h @ w["wq"]).reshape(2, SEQ, 7, 16)
    want_k = (h @ w["wk"]).reshape(2, SEQ, 1, 16)
    assert np.array_equal(np.asarray(q), np.asarray(want_q)) and np.array_equal(np.asarray(k), np.asarray(want_k))



def _tree_facts(cfg, own) -> None:
    assert {s: (k.n_heads, k.window, k.rotary_fraction, k.sparse, n) for s, (k, n) in cfg.stacks.items()} == {
        "layers": (7, None, 0.0, True, 2), "window_layers": (7, WINDOW, 1.0, True, 3)}
    assert own["window_layers"]["wq"].shape == (3, 64, 7 * 16) and own["layers"]["wk"].shape == (2, 64, 16)
    assert own["layers"]["w_gate"].shape == (2, 2, 64, 32) and own["layers"]["router"].shape == (2, 64, 8)


def _published_facts(cfg, _) -> None:
    assert [(k.stack, k.n_heads, k.window, k.rotary_fraction, k.rope_theta, k.sparse) for k in cfg.layers] == [
        ("layers", 28, None, 0.0, 1.5e6, True)] + [("window_layers", 28, 4096, 1.0, 1.5e6, True)] * 3 + [
        ("layers", 28, None, 0.0, 1.5e6, True)] + [("window_layers", 28, 4096, 1.0, 1.5e6, True)] * 3
    assert (cfg.d_model, cfg.n_kv_heads, cfg.d_head, cfg.d_ff, cfg.vocab_size, cfg.rms_eps) == (
        2560, 4, 128, 768, 18992, 1e-6)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_score, cfg.moe_norm_topk, cfg.moe_aux_coef) == (
        64, 6, (0, 8), "softmax", True, 0.0)
    assert cfg.moe_router_early and cfg.moe_activation == "relu" and not cfg.moe_shared_experts
    assert cfg.remat and cfg.max_seq == 16384
    flops = BENCH.flops("early_router_moe_lm")
    shapes = jax.eval_shape(lambda: REFERENCE.make_weights(1, PUBLISHED))
    assert flops.total_params(PUBLISHED) == sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == 643_852_800
    # the whole model by the same count is the published 21B: the config's keys account for every weight
    whole = dict(PUBLISHED, **PUBLISHED["published"], expert_parallel=None)
    assert flops.total_params(whole) == 21_506_562_560
    # every number of the published file that the cut does not name is the catalog's
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert PUBLISHED["published"] == dict(num_hidden_layers=52, moe_num_primary_experts=64, vocab_size=151936)
    assert PUBLISHED["rope_layout"] == PUBLISHED["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert (PUBLISHED["sliding_window_size"], PUBLISHED["rope_theta"], PUBLISHED["moe_num_active_primary_experts"]) == (
        4096, 1500000, 6)
    assert set(PUBLISHED["assumed"]) >= {"router_input", "aux_loss", "rope_pairing", "weights", "learning_rate",
                                         "held_rows_factor"}


REFUSALS = [
    ("all_window", dict(sliding_window_layout=[1, 1, 1, 1] * 3, rope_layout=[1, 1, 1, 1] * 3), "not the period"),
    ("period_shifted", dict(sliding_window_layout=[1, 0, 1, 1] * 3, rope_layout=[1, 0, 1, 1] * 3), "not the period"),
    ("rope_everywhere", dict(rope_layout=[1, 1, 1, 1] * 3), "rope_layout is not"),
    ("rope_scaling", dict(rope_scaling=dict(type="yarn", factor=4.0)), "no rope_scaling", "and the reference"),
    ("tied_head", dict(tie_word_embeddings=True), "untied", "and the reference"),
    ("gates_not_normalised", dict(norm_topk_prob=False), "normalised over the kept"),
    ("sigmoid_router", dict(moe_primary_router_apply_softmax=False), "sigmoid-then-normalise"),
]


def test_the_new_fields_are_checked_where_the_configuration_is_made() -> None:
    with pytest.raises(AssertionError, match="unknown moe_activation"):
        TransformerConfig(moe_activation="gelu")
    with pytest.raises(AssertionError, match="early router"):
        TransformerConfig(moe_router_early=True)  # no experts
    with pytest.raises(AssertionError, match="early router"):
        TransformerConfig(moe_router_early=True, moe_experts=4, moe_capacity_factor=None, moe_router_state=8)
    assert not TransformerConfig().moe_router_early and TransformerConfig().moe_activation == "silu"


# -- one chip's share of an expert-parallel layer: the router's published 64 outputs and 6 a token at small widths,
# routed on a tensor that is not the experts' input (8 chips: `moe_held = (8r, 8)`, r = 0..7) ----------------------


def _expert_layer() -> ExpertLayer:
    x, early, w = _layer_inputs()
    s = REFERENCE.sizes_of(dict(CONFIG, moe_num_primary_experts=64, moe_num_active_primary_experts=6))
    assert (s["held"], s["experts"], s["first"], s["top_k"]) == (64, 64, 0, 6)

    def share(first, count, _, x, early):
        return _share(x, early, w, first, count)

    def uncut(x, early):  # and the units ReLU leaves above zero where one chip holds every expert
        y = jnp.stack([REFERENCE._experts(h2, *REFERENCE._route(e, w, s), w, s, "float32") for h2, e in zip(x, early)])
        return y, _share(x, early, w, 0, 64)[1]["active_units"]

    def facts(stats, whole_active_units, dwant) -> None:
        assert float(jnp.max(jnp.abs(dwant[1]))) > 1e-3  # the gates' cotangent reaches the tensor the router read
        assert sum(int(st["active_units"]) for st in stats) == int(whole_active_units)  # the units add up too
        assert 0.4 < int(whole_active_units) / (64 * 6 * 16) < 0.6

    return ExpertLayer((x, early), 64, share, uncut, 64 * 6, facts=facts)


def _layer_inputs(seed=7, tokens=64, hidden=64, inner=16, n_exp=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5  # noqa: E731
    x = jax.random.normal(ks[0], (2, tokens // 2, hidden), jnp.float32)
    early = jax.random.normal(ks[5], (2, tokens // 2, hidden), jnp.float32)  # what the router reads: not x
    w = dict(router=normal(ks[1], (hidden, n_exp), hidden), w_gate=normal(ks[2], (n_exp, hidden, inner), hidden),
             w_up=normal(ks[3], (n_exp, hidden, inner), hidden), w_down=normal(ks[4], (n_exp, inner, hidden), inner))
    return x, early, w


def _share(x, early, w, first, count):
    routed = routing(early, w["router"], top_k=6, norm_topk=True, score="softmax")
    return moe_layer(
        x, w["router"], w["w_gate"][first:first + count], w["w_up"][first:first + count],
        w["w_down"][first:first + count], top_k=6, capacity_factor=None, norm_topk=True, score="softmax",
        held_first=first, dtype=jnp.float32, activation="relu", routed=routed)



def test_relu_counts_and_silu_does_not() -> None:
    """`active_units` is the ReGLU layer's: a layer under SiLU counts nothing,
    and SiLU put in ReLU's place would read 1 (no unit is exactly zero)."""
    x, early, w = _layer_inputs()
    args = (x, w["router"], w["w_gate"][:8], w["w_up"][:8], w["w_down"][:8])
    form = dict(top_k=6, capacity_factor=None, score="softmax", dtype=jnp.float32)
    assert "active_units" not in moe_layer(*args, **form)[1]
    relu = moe_layer(*args, activation="relu", **form)[1]
    assert 0.4 < int(relu["active_units"]) / (int(relu["rows_held"]) * 16) < 0.6
    saved = moe.ACTIVATIONS["relu"]
    try:
        moe.ACTIVATIONS["relu"] = jax.nn.silu
        silu = moe_layer(*args, activation="relu", **form)[1]
    finally:
        moe.ACTIVATIONS["relu"] = saved
    assert int(silu["active_units"]) == int(silu["rows_held"]) * 16
    # the capacity-bound path and the dense feed-forward take the activation too
    y_relu, _ = moe_layer(x, w["router"], w["w_gate"], w["w_up"], w["w_down"], top_k=6, capacity_factor=2.0,
                          activation="relu", dtype=jnp.float32)
    y_silu, _ = moe_layer(x, w["router"], w["w_gate"], w["w_up"], w["w_down"], top_k=6, capacity_factor=2.0,
                          dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(y_relu - y_silu))) > 1e-3
    assert set(moe.ACTIVATIONS) == {"silu", "relu", "relu2"}  # "relu2": the un-gated experts' (tests/test_mamba2_moe.py)


# -- the counter through ft_step: `moe_active_units` and `moe_units_held` ride the next step's summary beside the
# counters every share has, through the benchmark's own programs file -----------------------------------------------


def _tiny() -> Tiny:
    def facts(moved, summaries, step, after) -> None:
        for summary in summaries[1:]:
            assert summary["moe_assignments"] == LAYERS * 2 * SEQ * 3 and summary["moe_dropped"] == 0
            assert summary["moe_units_held"] == summary["moe_rows_held"] * 32
            assert 0.4 < summary["moe_active_units"] / summary["moe_units_held"] < 0.6

    return Tiny(lambda: REFERENCE.make_weights(2, SHARE), PROGRAM.loss(SHARE), _batch, 3, facts)


ARCH = Architecture(
    name="early_router_moe_lm", configs=dict(zip(HELD, (CONFIG, SHARE))), sizes=SIZES, seq=SEQ,
    variants=dict(REMAT, as_published={}),
    leaf_cases=[Case(f"{seed}-{held}", held, "as_published", seed) for seed in (11, 2**31 + 29) for held in HELD],
    leaf_tolerance=LEAF_TOLERANCE, loss_tolerance=1e-6, counters=_counters,
    # what is recomputed — the early choice from the kept layer input, ReLU's mask from the recomputed gate
    remat=("a_share_of_the_experts", 4, tuple(REMAT)),
    pieces=PIECES, pieces_at=("a_share_of_the_experts", 5), piece_floor=3 * LEAF_TOLERANCE,
    chips=[8, 16, 4, 1], expert_layer=_expert_layer,
    published="smallthinker-21b-a3b", tree_config="a_share_of_the_experts", tree_facts=_tree_facts,
    published_facts=_published_facts, refusals=REFUSALS, refusal_config="every_expert_held",
    through=("ft_step",), tiny=_tiny,
)


# -- operation counts from shapes ---------------------------------------------------


def test_operation_counts_against_hand_arithmetic() -> None:
    c, t = PUBLISHED, BENCH.traffic("steady-1g-16k")
    swa, fa, gmm = BENCH.flops("tpuft_swa4k"), BENCH.flops("tpuft_fa_gqa7"), BENCH.flops("tpuft_gmm_reglu")
    flops = BENCH.flops("early_router_moe_lm")
    assert swa.band_pairs(16384, 4096) == 58_722_304 == flops.pairs(16384, 4096)
    assert swa.band_pairs(16384, 4096) == sum(min(t + 1, 4096) for t in range(16384))
    assert swa.band_pairs(4096, 4096) == 4096 * 4097 // 2 and swa.window_layers(c) == 6 and fa.full_layers(c) == 2
    window = swa.per_step(c, t)
    assert window["flops"] == 6 * 28 * 6 * 2.0 * 58_722_304 * 128
    assert window["bytes"] == 6 * 28 * (12 * 16384 * 128 * 2 + 3 * 16384 * 4)
    full = fa.per_step(c, t)
    assert full["flops"] == 2 * 28 * 6 * 2.0 * (16384 * 16385 / 2) * 128
    assert full["bytes"] == 2 * 28 * (12 * 16384 * 128 * 2 + 3 * 16384 * 4)
    held = gmm.per_step(c, 8 * 12_288)
    assert held["flops"] == 9 * 2.0 * 98_304 * 2560 * 768
    matrices = 8 * 8 * 2560 * 768
    assert held["bytes"] == 3 * (3 * (98_304 * 2560 * 2 + 98_304 * 768 * 2) + matrices * (2 + 2 + 4))
    # a token: 6 x the matrices it meets (attention 20,971,520, the router's 64 columns, three quarters of an expert,
    # the head's slice) and attention's products over the keys it sees on average
    a_layer = 20_971_520 + 2560 * 64 + 0.75 * 5_898_240
    assert flops.matmul_params(c) == 2560 * 18_992 + 8 * a_layer
    attention = 3 * 2 * 28 * 2 * 128 * (6 * 58_722_304 + 2 * 134_225_920) / 16384
    assert flops.attention_flops_per_token(c, 16384) == pytest.approx(attention)
    assert flops.train_flops_per_token(c, 16384) == pytest.approx(6 * flops.matmul_params(c) + attention)
    # the kernels' counts are the model's attention term, step for step
    assert (window["flops"] + full["flops"]) == pytest.approx(attention * 16384)
    peaks = BENCH.peaks("TPU v5 lite")
    for need in (window, full, held):  # all three bound by the MXU at these sizes
        assert need["flops"] / peaks["bf16_flops_per_s"] > need["bytes"] / peaks["hbm_bytes_per_s"]


# -- the benchmark's entries and the new readers -------------------------------------


def test_the_cell_is_found_and_reports_its_metrics() -> None:
    cell = BENCH.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("smallthinker-21b-a3b", "steady-1g-16k", 1)
    reported = {m["name"] for m in BENCH.per_layer(CELL)}
    assert set(NEW_METRICS) | {
        "ce_roofline", "quorum_wait_ms", "ft_step_self_ms", "device_grad_ms", "device_update_ms", "gmm_ms",
        "moe_load_max_over_mean", "moe_dropped", "moe_held_share", "grad_fwd_ms", "grad_bwd_ms", "grad_recompute_ms",
        "head_loss_ms", "attn_proj_ms", "experts_ms", "unattributed_ms", "swa_pairs_share", "mfu", "quorum_ms",
        "commit_vote_ms", "exchange_exposed_ms", "device_step_ms", "alloc_peak_bytes"} <= reported
    assert not {"ffn_ms", "swa_attn_ms", "swa_attn_roofline", "full_attn_ms", "full_attn_roofline",
                "gmm_small_roofline", "attn_roofline"} & reported
    for other in (w["name"] for w in BENCH.doc["workloads"] if w["name"] != CELL):
        assert not set(NEW_METRICS) & {m["name"] for m in BENCH.per_layer(other)}
    # in their order, wherever later PRs' entries stand (a subset check: PERF.md section 7 lists the tests
    # that pinned "the last entries" and broke with the next configuration)
    names = [m["name"] for m in BENCH.doc["per_layer"]]
    assert [name for name in names if name in NEW_METRICS] == list(NEW_METRICS)
    assert CELL in [w["name"] for w in BENCH.doc["workloads"]]
    assert "smallthinker-21b-a3b" in [c["name"] for c in BENCH.doc["configs"]]
    assert sum(w["chips"] == 4 for w in BENCH.doc["workloads"]) == 1
    kernels = PROGRAM.kernel_names()
    assert set(kernels) == {"attn", "ce", "gmm", "swa"} and kernels["swa"]("%tpuft_swa_fwd.13")


def _ctx(tmp_path, monkeypatch, summaries, kernels, config):
    stream = tmp_path / "g0.metrics.jsonl"
    stream.write_text("".join(json.dumps(dict(event="step_summary", t_mono=1.0 + i, step=i, **s)) + "\n"
                              for i, s in enumerate(summaries)))
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(stream))
    return {"trace": {"kernel_s_per_step": kernels}, "peaks": BENCH.peaks("TPU v5 lite"), "bench": BENCH,
            "config": config, "traffic": BENCH.traffic("steady-1g-16k"),
            "steady_steps": [{"start_mono_ns": 0.5e9, "ms": 10_000.0}]}


def test_the_new_readers_on_a_recorded_step(tmp_path, monkeypatch) -> None:
    c = PUBLISHED
    summaries = [dict(moe_rows_held=rows, moe_assignments=786_432, moe_active_units=units, moe_units_held=rows * 768)
                 for rows, units in ((97_000, 37_100_000), (98_304, 37_748_736), (99_000, 38_400_000))]
    ctx = _ctx(tmp_path, monkeypatch, summaries, {"attn": 0.128, "swa": 0.171, "gmm": 0.061}, c)
    assert BENCH.reader("swa4k_attn_ms").read(ctx) == pytest.approx(171.0)
    assert BENCH.reader("full_nope_attn_ms").read(ctx) == pytest.approx(128.0)
    swa = BENCH.flops("tpuft_swa4k").per_step(c, ctx["traffic"])
    assert BENCH.reader("swa4k_attn_roofline").read(ctx) == pytest.approx(100 * swa["flops"] / 197e12 / 0.171)
    fa = BENCH.flops("tpuft_fa_gqa7").per_step(c, ctx["traffic"])
    assert BENCH.reader("full_nope_attn_roofline").read(ctx) == pytest.approx(100 * fa["flops"] / 197e12 / 0.128)
    held = BENCH.flops("tpuft_gmm_reglu").per_step(c, 98_304)
    assert BENCH.reader("gmm_reglu_roofline").read(ctx) == pytest.approx(100 * held["flops"] / 197e12 / 0.061)
    assert BENCH.reader("reglu_active_share").read(ctx) == pytest.approx(0.5)
    for name in ("swa4k_attn_roofline", "full_nope_attn_roofline", "gmm_reglu_roofline"):
        assert 0 < BENCH.reader(name).read(ctx) < 100
    # the part `router` alone, from a run's attribution
    from benchmark import device_parts, program_spans

    execution = {"router/fwd": 1.25, "router/bwd": 2.5, "router/recompute": 1.25, "experts/fwd": 9.0}
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: {"programs": {program_spans.GRAD_PROGRAM: {
        "by_part": {"router": 5.0, "experts": 9.0}, "per_execution": [execution]}}})
    assert BENCH.reader("early_router_ms").read(ctx) == pytest.approx(5.0)


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read(tmp_path, monkeypatch) -> None:
    """A program without the counter, the part or the kernels (the parent of
    the PR that added them), a configuration of another family: every new
    reader returns None and does not raise."""
    ctx = _ctx(tmp_path, monkeypatch, [dict(moe_dropped=0, moe_rows_held=5, moe_assignments=9)],
               {"attn": 0.01, "gmm": 0.01, "swa": 0.01}, BENCH.config("laguna-xs.2"))
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
    ctx = _ctx(tmp_path, monkeypatch, [], {}, PUBLISHED)
    for name in NEW_METRICS:
        assert BENCH.reader(name).read(ctx) is None, name
