"""SmallThinker-shaped models (a router that reads the layer's input before
attention, ReGLU experts under a softmax over the kept, window layers under
RoPE 3:1 with full layers that have no position term, seven query heads a KV
head) through the program, on the CPU at small sizes.

The program (``models/transformer.py`` with ``moe_router_early`` and
``moe_activation="relu"`` under a two-kind ``pattern``) against the benchmark's
plain float32 reference (``benchmark/reference/early_router_moe_lm.py``, which
shares no code with it) on seeded random weights; the reference without a piece
and the program with a wrong one against the whole; the un-rotated kind's q and
k against the bare projections; the shares of an expert-parallel layer against
the uncut layer; the adapter's refusals; operation counts against hand
arithmetic; the new counter through ``ft_step``; the new readers on what they
read and on nothing.
"""

import dataclasses
import json
import math
import os
import sys
from unittest.mock import MagicMock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from test_manager import make_manager, make_quorum, store  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import spec  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402
from torchft_tpu.models import TransformerConfig, init_params  # noqa: E402
from torchft_tpu.models import moe, transformer  # noqa: E402
from torchft_tpu.models.moe import moe_layer, routing  # noqa: E402
from torchft_tpu.models.transformer import loss_and_counters, param_axes  # noqa: E402
from torchft_tpu.parallel import TrainStep, ft_init_mesh  # noqa: E402

BENCH = Benchmark(ROOT)
REFERENCE = BENCH.reference("early_router_moe_lm")
PROGRAM = BENCH.program("early_router_moe_lm")
PUBLISHED = BENCH.config("smallthinker-21b-a3b")
CELL = "smallthinker-21b-a3b.steady-1g-16k"
NEW_METRICS = ("swa4k_attn_ms", "swa4k_attn_roofline", "full_nope_attn_ms", "full_nope_attn_roofline",
               "gmm_reglu_roofline", "early_router_ms", "reglu_active_share")

SEQ, WINDOW = 32, 8
# Two whole periods in small, float32 throughout: 7 query heads over ONE KV head
# of 16 (the group of 7 is there), a window of 8 under 32 positions, 8 routed
# experts of width 32, 3 a token.  The layouts keep a published length: the
# first `num_hidden_layers` entries count.
CONFIG = dict(
    architecture="early_router_moe_lm", vocab_size=256, hidden_size=64, num_hidden_layers=8, num_attention_heads=7,
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
LOSS_TOLERANCE = 1e-6


def _batch(seed: int, config=CONFIG, sequences: int = 2, seq_len: int = SEQ):
    tokens = np.random.default_rng(seed).integers(0, config["vocab_size"], size=(sequences, seq_len)).astype(np.int32)
    return {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(np.roll(tokens, -1, axis=1))}


def _worst_leaf(grads, want):
    worst = ("", 0.0)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
        rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
        if rel > worst[1]:
            worst = (jax.tree_util.keystr(path), rel)
    return worst


def _program_grads(cfg, weights, batch):
    return jax.jit(jax.value_and_grad(lambda p, b: loss_and_counters(p, b, cfg), has_aux=True))(weights, batch)


@pytest.mark.parametrize("config", [CONFIG, SHARE], ids=["every_expert_held", "a_share_of_the_experts"])
@pytest.mark.parametrize("seed", [11, 2**31 + 29])
def test_loss_and_every_gradient_leaf_against_the_plain_reference(seed, config) -> None:
    cfg = PROGRAM.transformer_config(config)
    weights, batch = REFERENCE.make_weights(seed, config), _batch(seed)
    (loss, counters), grads = _program_grads(cfg, weights, batch)
    want_loss, want = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], config)
    leaf, rel = _worst_leaf(grads, want)
    loss_rel = abs(float(loss) - float(want_loss)) / float(want_loss)
    assert rel < LEAF_TOLERANCE and loss_rel < LOSS_TOLERANCE, (leaf, rel, loss_rel)
    assert jax.tree.structure(grads) == jax.tree.structure(weights)
    assert int(counters["moe_dropped"]) == 0
    assert np.asarray(counters["moe_tokens_per_expert"]).sum(axis=1).tolist() == [2 * SEQ * 3] * 8
    # ReLU leaves about half of the held experts' hidden units above zero; the denominator is rows x width
    held_rows = int(counters["moe_rows_held"]) if "moe_rows_held" in counters else 8 * 2 * SEQ * 3
    assert int(counters["moe_units_held"]) == held_rows * 32
    assert 0.4 < int(counters["moe_active_units"]) / int(counters["moe_units_held"]) < 0.6


# What the reference computes with one piece of the published mathematics left
# out or put in the wrong layers (`REFERENCE.LEFT_OUT`): the program as
# published has to fail the comparison with each.
@pytest.mark.parametrize("piece", [
    "early_router",        # the router fed the experts' input h2
    "relu",                # SiLU for ReLU
    "nope_on_full",        # RoPE on the full layers too
    "rope_on_window",      # no RoPE on the window layers
    "window",              # the window dropped
    "softmax_over_kept",   # the gates a softmax over all the outputs, left as it is
])
def test_the_reference_without_a_piece_fails_the_comparison(piece) -> None:
    assert piece in REFERENCE.LEFT_OUT
    seed = 5
    weights, batch = REFERENCE.make_weights(seed, SHARE), _batch(seed)
    (loss, _), grads = _program_grads(PROGRAM.transformer_config(SHARE), weights, batch)
    want_loss, want = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], SHARE, left_out=piece)
    leaf, rel = _worst_leaf(grads, want)
    assert rel > 3 * LEAF_TOLERANCE, f"{piece}: the comparison did not see it ({leaf} {rel})"


# The same from the other side: a PROGRAM that runs the wrong mechanism in a
# kind of layer (what `benchmark/tools/routing_ties_reglu.py --wrong 1` tries on
# the chip) fails against the reference as published.
WRONG_PROGRAMS = spec._module("tools", "routing_ties_reglu", BENCH.bench_dir).wrong_programs


@pytest.mark.parametrize("wrong", [
    "window_layers_over_the_whole_triangle", "full_layers_under_the_window", "rope_on_the_full_layers",
    "router_on_the_experts_input", "silu_for_relu"])
def test_a_program_with_a_wrong_mechanism_fails_the_comparison(wrong) -> None:
    seed = 6
    weights, batch = REFERENCE.make_weights(seed, SHARE), _batch(seed)
    tried = WRONG_PROGRAMS(PROGRAM.transformer_config(SHARE), WINDOW)
    assert len(tried) == 5
    (loss, _), grads = _program_grads(tried[wrong], weights, batch)
    _, want = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], SHARE)
    leaf, rel = _worst_leaf(grads, want)
    assert rel > 3 * LEAF_TOLERANCE, f"{wrong}: the comparison did not see it ({leaf} {rel})"


@pytest.mark.parametrize("keeps", [False, True], ids=["remat", "remat_that_keeps_attention"])
def test_rematerialised_layers_give_the_gradients_of_the_stored_ones(keeps) -> None:
    """`remat`, with and without both kinds' attention output kept: what is
    recomputed — the early choice from the kept layer input, ReLU's mask from
    the recomputed gate — is not computed differently, and agrees with the
    reference as the stored program does."""
    cfg = PROGRAM.transformer_config(SHARE)
    weights, batch = REFERENCE.make_weights(4, SHARE), _batch(4)
    (loss, stored_counters), stored = _program_grads(cfg, weights, batch)
    (again_loss, counters), again = _program_grads(
        dataclasses.replace(cfg, remat=True, remat_keeps_attention=keeps), weights, batch)
    assert float(again_loss) == float(loss)
    assert int(counters["moe_active_units"]) == int(stored_counters["moe_active_units"])
    leaf, rel = _worst_leaf(again, stored)
    assert rel < 1e-6, (leaf, rel)
    _, want = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], SHARE)
    leaf, rel = _worst_leaf(again, want)
    assert rel < LEAF_TOLERANCE, (leaf, rel)


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
    real_attention, real_rotary = transformer.flash_attention, transformer._rotary
    monkeypatch.setattr(transformer, "flash_attention",
                        lambda q, k, v, **kw: seen.append((q, k, kw.get("window"))) or real_attention(q, k, v, **kw))
    monkeypatch.setattr(transformer, "_rotary",
                        lambda x, positions, kind, **kw: turned.append(kind.stack) or real_rotary(x, positions, kind, **kw))
    loss_and_counters(weights, batch, cfg)
    assert [window for _, _, window in seen] == [None, WINDOW] and turned == ["window_layers"] * 2
    q, k, _ = seen[0]
    x = weights["embed"][batch["tokens"]]
    w = {name: leaf[0] for name, leaf in weights["layers"].items()}
    h = transformer.rms_norm(x, w["attn_norm"], cfg.rms_eps)
    want_q = (h @ w["wq"]).reshape(2, SEQ, 7, 16).transpose(0, 2, 1, 3)
    want_k = (h @ w["wk"]).reshape(2, SEQ, 1, 16).transpose(0, 2, 1, 3)
    assert np.array_equal(np.asarray(q), np.asarray(want_q)) and np.array_equal(np.asarray(k), np.asarray(want_k))


def test_the_tree_has_a_stack_a_kind_of_layer() -> None:
    cfg = PROGRAM.transformer_config(SHARE)
    assert {s: (k.n_heads, k.window, k.rotary_fraction, k.sparse, n) for s, (k, n) in cfg.stacks.items()} == {
        "layers": (7, None, 0.0, True, 2), "window_layers": (7, WINDOW, 1.0, True, 6)}
    own = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    made = jax.eval_shape(lambda: REFERENCE.make_weights(1, SHARE))
    assert jax.tree.structure(own) == jax.tree.structure(made)
    assert [a.shape for a in jax.tree.leaves(own)] == [a.shape for a in jax.tree.leaves(made)]
    assert own["window_layers"]["wq"].shape == (6, 64, 7 * 16) and own["layers"]["wk"].shape == (2, 64, 16)
    assert own["layers"]["w_gate"].shape == (2, 2, 64, 32) and own["layers"]["router"].shape == (2, 64, 8)
    axes = param_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))) == \
        jax.tree.structure(jax.tree.map(lambda a: 0, own))


def test_the_published_configuration_is_handed_over_whole() -> None:
    cfg = PROGRAM.transformer_config(PUBLISHED)
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


@pytest.mark.parametrize("change,message", [
    (dict(sliding_window_layout=[1, 1, 1, 1] * 3, rope_layout=[1, 1, 1, 1] * 3), "not the period"),
    (dict(sliding_window_layout=[1, 0, 1, 1] * 3, rope_layout=[1, 0, 1, 1] * 3), "not the period"),
    (dict(rope_layout=[1, 1, 1, 1] * 3), "rope_layout is not"),
    (dict(rope_scaling=dict(type="yarn", factor=4.0)), "no rope_scaling"),
    (dict(tie_word_embeddings=True), "untied"),
    (dict(norm_topk_prob=False), "normalised over the kept"),
    (dict(moe_primary_router_apply_softmax=False), "sigmoid-then-normalise"),
], ids=["all_window", "period_shifted", "rope_everywhere", "rope_scaling", "tied_head", "gates_not_normalised",
        "sigmoid_router"])
def test_the_adapter_raises_on_what_it_does_not_honour(change, message) -> None:
    with pytest.raises(ValueError, match=message):
        PROGRAM.transformer_config(dict(CONFIG, **change))
    if "tie_word_embeddings" not in change and "rope_scaling" not in change:
        return
    with pytest.raises(ValueError):
        REFERENCE.sizes_of(dict(CONFIG, **change))


def test_the_new_fields_are_checked_where_the_configuration_is_made() -> None:
    with pytest.raises(AssertionError, match="unknown moe_activation"):
        TransformerConfig(moe_activation="gelu")
    with pytest.raises(AssertionError, match="early router"):
        TransformerConfig(moe_router_early=True)  # no experts
    with pytest.raises(AssertionError, match="early router"):
        TransformerConfig(moe_router_early=True, moe_experts=4, moe_capacity_factor=None, moe_router_state=8)
    assert not TransformerConfig().moe_router_early and TransformerConfig().moe_activation == "silu"


# -- one chip's share of an expert-parallel layer ---------------------------------


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


@pytest.mark.parametrize("chips", [8, 16, 4, 1])
def test_the_shares_add_up_to_the_uncut_layer(chips) -> None:
    """The router's published 64 outputs and 6 a token at small widths, routed
    on a tensor that is not the experts' input: what every chip of an
    expert-parallel layer computes of the routed experts (8 chips: `moe_held =
    (8r, 8)`, r = 0..7), summed over the chips, is what the uncut plain
    reference gives for the whole layer — values and the gradients of both
    inputs."""
    x, early, w = _layer_inputs()
    count = 64 // chips
    s = REFERENCE.sizes_of(dict(CONFIG, moe_num_primary_experts=64, moe_num_active_primary_experts=6))
    assert (s["held"], s["experts"], s["first"], s["top_k"]) == (64, 64, 0, 6)

    def uncut(x, early):
        return jnp.stack([REFERENCE._experts(h2, *REFERENCE._route(e, w, s), w, s, "float32") for h2, e in zip(x, early)])

    def summed(x, early):
        return sum(_share(x, early, w, r * count, count)[0] for r in range(chips))

    with jax.default_matmul_precision("highest"):
        want, got = uncut(x, early), summed(x, early)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
        dwant = jax.grad(lambda x, e: jnp.sum(jnp.sin(uncut(x, e))), argnums=(0, 1))(x, early)
        dgot = jax.grad(lambda x, e: jnp.sum(jnp.sin(summed(x, e))), argnums=(0, 1))(x, early)
        for a, b in zip(dgot, dwant):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
        assert float(jnp.max(jnp.abs(dwant[1]))) > 1e-3  # the gates' cotangent reaches the tensor the router read
    # the counters: the shares' held rows are all the assignments, none dropped, and the units add up too
    stats = [_share(x, early, w, r * count, count)[1] for r in range(chips)]
    assert sum(int(st["rows_held"]) for st in stats) == int(stats[0]["assignments"]) == 64 * 6
    assert all(int(st["dropped"]) == 0 for st in stats)
    whole = _share(x, early, w, 0, 64)[1]
    assert sum(int(st["active_units"]) for st in stats) == int(whole["active_units"])
    assert 0.4 < int(whole["active_units"]) / (64 * 6 * 16) < 0.6


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


# -- the counter through ft_step ---------------------------------------------------


def _records(path, event):
    with open(path, encoding="utf-8") as f:
        return [r for r in map(json.loads, f) if r.get("event") == event]


def test_active_units_land_in_the_step_summary(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    """ft_steps of the share under a real Manager, through the benchmark's own
    programs file: `moe_active_units` and `moe_units_held` ride the next step's
    summary beside the counters every share has."""
    path = tmp_path / "stream.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    client = MagicMock()
    client._quorum.return_value = make_quorum()
    client.should_commit.return_value = True
    manager, _, _ = make_manager(store, client_mock=client)
    ftmesh = ft_init_mesh({"data": 1}, devices=jax.devices()[:1])
    ftmesh.manager = manager
    step = TrainStep(ftmesh, optax.adamw(1e-3), PROGRAM.loss(SHARE), loss_has_counters=True, overlap_commit=False)
    params = REFERENCE.make_weights(2, SHARE)
    opt = step.init_opt_state(params)
    try:
        for i in range(3):
            manager.start_quorum()
            params, opt, loss, committed = step.ft_step(params, opt, _batch(i))
            assert committed and np.isfinite(float(loss))
    finally:
        manager.shutdown()
    _, second, third = _records(path, "step_summary")
    for summary in (second, third):
        assert summary["moe_assignments"] == 8 * 2 * SEQ * 3 and summary["moe_dropped"] == 0
        assert summary["moe_units_held"] == summary["moe_rows_held"] * 32
        assert 0.4 < summary["moe_active_units"] / summary["moe_units_held"] < 0.6


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
