"""Nemotron-H-shaped models (blocks that are ONE norm and ONE part: a Mamba-2
mixer, un-rotated grouped-query attention or un-gated ReLU^2 experts beside a
shared expert, in a pattern string's order; three stacks of unequal leaf sets)
through the program, on the CPU at small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/mamba2_moe_lm.py``).  What only this
architecture has is tested here: the `tpuft_ssd_*` kernels (interpret mode)
against the XLA chunk form and the loop, each piece of ``ssm_mix`` against a
written-out loop, the kinds a pattern may hold (``grouped_matmul`` at its experts'
14.5 lane tiles: `tests/test_grouped_matmul.py`).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, Architecture, Case, ExpertLayer, Piece, Tiny, batches, patched, pytest_generate_tests, store, tiny_of_the_small_model,
    test_a_model_without_a_piece_is_another_model, test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_the_adapter_raises_on_what_it_does_not_honour, test_the_published_configuration_is_handed_over_whole,
    test_the_shares_add_up_to_the_uncut_layer, test_the_tree_goes_through, test_the_tree_is_the_reference_s)
from torchft_tpu.models import LayerKind, TransformerConfig, init_params
from torchft_tpu.models import mamba
from torchft_tpu.models.moe import moe_layer
from torchft_tpu.models.mixer import _causal_conv
from torchft_tpu.models.transformer import loss_and_counters
from torchft_tpu.ops import ssd

REFERENCE = BENCH.reference("mamba2_moe_lm")
PROGRAM = BENCH.program("mamba2_moe_lm")
PUBLISHED = BENCH.config("nemotron-twotower-30b-a3b")

SEQ = 40
SIZES = """40 positions: two chunks of 16 and a half, so the scan crosses a chunk's edge twice and ends inside one, and the
kernel-4 convolution's first three positions are a small share.  Five blocks `ME*ME`: every kind of block, two stacks of
two and one of one, each kind after each other kind (the published nine letters are `MEMEM*EME`: the same kinds four,
four and one time; `test_runs_of_one_kind_go_through_the_scan` has runs).  Mamba-2 at 6 heads of 8 in 2 groups over a
state of 16: three heads a group, a head count that is no multiple of eight.  Attention at 4 query heads over 2 KV heads
of 16.  8 router outputs of which this chip holds experts 2-5, 2 a token, at a width of 24 (no lane multiple), beside a
shared expert of 48.  Float32 throughout."""
CONFIG = dict(
    PUBLISHED, vocab_size=300, hidden_size=64, num_hidden_layers=5, mamba_num_heads=6, mamba_head_dim=8, n_groups=2,
    ssm_state_size=16, chunk_size=16, num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_routed_experts=4, num_experts_per_tok=2, max_position_embeddings=128,
    hybrid_override_pattern="ME*ME" + PUBLISHED["hybrid_override_pattern"][5:],
    expert_parallel=dict(chips=2, rank=0, router_outputs=8, first_expert_held=2),
    router_bias=dict(seed=5, scale=0.05),
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
    program=dict(remat=False, remat_keeps_attention=False, scan_unroll=16),
)
# The published pattern's first letters have no two alike in a row, so its walk is a static loop whatever `scan_unroll`;
# `MMMEE**EM` has runs, and with `scan_unroll` 1 each run is a `lax.scan` over its stack's slice.
RUNS = dict(CONFIG, num_hidden_layers=9, hybrid_override_pattern="MMMEE**EM" + PUBLISHED["hybrid_override_pattern"][9:])
WALKS = {
    "static_loop": dict(remat=False, scan_unroll=16),
    "remat": dict(remat=True, scan_unroll=16),
    "remat_that_keeps_the_mixers_outputs": dict(remat=True, remat_keeps_attention=True, scan_unroll=16),
}
STACKS = ("mamba", "attn", "moe", "embed", "final_norm", "lm_head")


_batch = batches(300, SEQ)


SCANS_OF_RUNS = {"scan": dict(remat=False, scan_unroll=1),
                 "remat_in_the_scan": dict(remat=True, remat_keeps_attention=True, scan_unroll=1)}


def _counters(counters, config) -> None:
    expert_blocks = config["hybrid_override_pattern"][:config["num_hidden_layers"]].count("E")
    assert counters["moe_tokens_per_expert"].shape == (expert_blocks, 8)
    assert int(counters["moe_dropped"]) == 0 and int(counters["moe_assignments"]) == expert_blocks * 2 * 2 * SEQ
    assert 0 < int(counters["moe_rows_held"]) < int(counters["moe_assignments"])
    assert 0.0 < float(counters["ssm_decay_mean"]) < 1.0
    held_units = int(counters["moe_units_held"])
    assert held_units == int(counters["moe_rows_held"]) * 24 and 0.3 < int(counters["moe_active_units"]) / held_units < 0.7


# -- a model without a piece is another model: the program without the decay (a = 1), the D skip, the convolution's
# earlier taps, the gate SiLU(z), the norm's groups (one norm over all 48 columns), the square, the scale 2.5 or the
# shared expert, or with its attention block rotated, is not the reference's model


def _without(piece):
    import torchft_tpu.models.moe as moe
    import torchft_tpu.models.mamba as model

    def how(cfg, weights):
        changes = []
        if piece == "decay":
            real = ssd.ssd
            changes = [(ssd, "ssd", lambda xdt, bm, cm, la, **kw: real(xdt, bm, cm, jnp.zeros_like(la), **kw))]
        elif piece == "skip":
            weights = dict(weights, mamba=dict(weights["mamba"], ssm_D=jnp.zeros_like(weights["mamba"]["ssm_D"])))
        elif piece == "convolution":
            changes = [(model, "_causal_conv", lambda z, taps: taps[-1] * z)]
        elif piece == "gate":  # a gate that is always one: SiLU(1.2785) = 1
            real_after = mamba._after
            changes = [(mamba, "_after", lambda y, x, z, *a: real_after(y, x, jnp.full_like(z, 1.27846454), *a))]
        elif piece == "one_norm_over_all_groups":
            real_after = mamba._after
            changes = [(mamba, "_after", lambda y, x, z, w, c, heads: real_after(
                y, x, z, w, dataclasses.replace(c, ssm_groups=1), heads))]
        elif piece == "square":
            changes = [(moe.ACTIVATIONS, "relu2", jax.nn.relu)]
        elif piece == "route_scale":
            cfg = dataclasses.replace(cfg, moe_route_scale=1.0)
        elif piece == "shared_expert":
            cfg = dataclasses.replace(cfg, moe_shared_experts=0)
        elif piece == "rotated_attention":
            cfg = dataclasses.replace(cfg, pattern=tuple(
                dataclasses.replace(kind, rotary_fraction=1.0) if kind.mixer == "attention" else kind for kind in cfg.pattern))
        return patched(*changes), cfg, weights

    return Piece(piece, "program", how)


PIECES = ("decay", "skip", "convolution", "gate", "one_norm_over_all_groups", "square", "route_scale", "shared_expert",
          "rotated_attention")


# -- the shares add up: 16 chips hold two of 32 experts each ---------------------------------------


def _expert_layer() -> ExpertLayer:
    hidden, ffn, experts, k = 32, 24, 32, 6
    rng = np.random.default_rng(4)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)  # noqa: E731
    w = dict(mlp_norm=jnp.ones((hidden,)), router=draw(hidden, experts), w_up=draw(experts, hidden, ffn),
             w_down=draw(experts, ffn, hidden), shared_up=draw(hidden, 2 * ffn), shared_down=draw(2 * ffn, hidden))
    bias = jnp.asarray(rng.standard_normal(experts) * 0.05, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 24, hidden)), jnp.float32)
    s = dict(experts=experts, held=experts, first=0, top_k=k, route_scale=2.5, eps=1e-5)

    def normed(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5)

    def uncut(x):  # the reference's block adds the stream; the part is what is compared
        return jnp.stack([REFERENCE._experts(seq, w, bias, s, "float32") - seq for seq in x]), None

    def share(first, count, with_shared, x):
        held = slice(first, first + count)
        return moe_layer(normed(x), w["router"], None, w["w_up"][held], w["w_down"][held], top_k=k,
                         capacity_factor=None, norm_topk=True, score="sigmoid", route_bias=bias, route_scale=2.5,
                         held_first=first, shared=(None, w["shared_up"], w["shared_down"]) if with_shared else None,
                         activation="relu2", dtype=jnp.float32)

    return ExpertLayer((x,), experts, share, uncut, 2 * 24 * k, sin=5.0, grad_rtol=1e-3, shared=True)


# -- the tree, the configuration, the adapter --------------------------------------------------


def _tree_facts(cfg, ours) -> None:
    """Three stacks of unequal leaf sets, a block ONE norm; the experts' leaves
    keep the published 1,856 columns, `A_log`, `ssm_D` and `dt_bias` are float32
    vectors of 64 a block, the taps [6,144, 4]; and the decay's initialisation is
    the published one on both sides."""
    assert [(s, k.mixer, k.feed_forward, k.sparse, n) for s, (k, n) in cfg.stacks.items()] == [
        ("mamba", "mamba2", False, False, 4), ("moe", "none", True, True, 4), ("attn", "attention", False, False, 1)]
    assert "".join({"mamba": "M", "moe": "E", "attn": "*"}[kind.stack] for kind in cfg.layers) == "MEMEM*EME"
    theirs = jax.eval_shape(lambda: REFERENCE.make_weights(1, PUBLISHED))
    assert [l.dtype for l in jax.tree.leaves(ours)] == [l.dtype for l in jax.tree.leaves(theirs)]
    assert set(ours["mamba"]) == {"attn_norm", "ssm_in", "ssm_conv", "ssm_conv_bias", "dt_bias", "A_log", "ssm_D",
                                  "ssm_norm", "ssm_out"}
    assert set(ours["attn"]) == {"attn_norm", "wq", "wk", "wv", "wo"}
    assert set(ours["moe"]) == {"mlp_norm", "router", "w_up", "w_down", "shared_up", "shared_down"}
    assert ours["moe"]["w_up"].shape == (4, 8, 2688, 1856) and ours["moe"]["w_down"].shape == (4, 8, 1856, 2688)
    assert ours["moe"]["shared_up"].shape == (4, 2688, 3712) and ours["moe"]["router"].shape == (4, 2688, 128)
    assert ours["mamba"]["ssm_in"].shape == (4, 2688, 10304) and ours["mamba"]["ssm_conv"].shape == (4, 6144, 4)
    assert all(ours["mamba"][name].shape == (4, 64) for name in ("A_log", "ssm_D", "dt_bias"))
    assert ours["attn"]["wq"].shape == (1, 2688, 4096) and ours["attn"]["wk"].shape == (1, 2688, 256)
    flops = BENCH.flops("mamba2_moe_lm")
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(ours)) == 666_962_944 == flops.total_params(PUBLISHED)
    whole = dict(PUBLISHED, expert_parallel=None, **PUBLISHED["published"])
    assert flops.total_params(whole) == 31_577_937_344  # the "30B": every weight has a key
    small = PROGRAM.transformer_config(CONFIG)
    for tree in (init_params(jax.random.PRNGKey(2), small), REFERENCE.make_weights(2, CONFIG)):
        rate, steps = np.exp(np.asarray(tree["mamba"]["A_log"])), np.asarray(jax.nn.softplus(tree["mamba"]["dt_bias"]))
        assert 1.0 <= rate.min() and rate.max() <= 16.0 and 0.001 * 0.999 <= steps.min() and steps.max() <= 0.1 * 1.001
        assert np.all(np.asarray(tree["mamba"]["ssm_D"]) == 1.0) and not np.any(np.asarray(tree["mamba"]["ssm_conv_bias"]))


def _published_facts(cfg, _) -> None:
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers, cfg.d_head) == (2688, 1856, 16384, 9, 128)
    assert (cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_chunk) == (64, 8, 128, 4, 128)
    assert [kind.n_heads for kind in cfg.pattern if kind.mixer == "mamba2"] == [64] * 4 and cfg.n_kv_heads == 2
    assert all(kind.rotary_fraction == 0.0 and kind.window is None for kind in cfg.pattern)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_route_scale, cfg.moe_shared_experts) == (128, 6, (0, 8), 2.5, 2)
    assert cfg.moe_score == "sigmoid" and cfg.moe_norm_topk and cfg.moe_activation == "relu2" and cfg.moe_aux_coef == 0.0
    assert cfg.rms_eps == 1e-5 and not cfg.tied_head and cfg.n_sparse_layers == 4
    assert PROGRAM.router_bias(PUBLISHED).shape == (4, 128)
    # every number of the catalog's row under the same key, the three cuts listed, the departure said
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert PUBLISHED["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131_072}
    pattern = PUBLISHED["hybrid_override_pattern"]
    assert len(pattern) == 52 and (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (23, 23, 6)
    assert "denoiser" in json.dumps(PUBLISHED["departures"]) and "not written" in json.dumps(PUBLISHED["departures"])
    assert PUBLISHED["expert_parallel"]["chips"] == 16 and PUBLISHED["moe_intermediate_size"] == 1856
    assert set(PROGRAM.kernel_names()) >= {"attn", "ce", "gmm", "ssd"} and PROGRAM.kernel_names()["ssd"]("x.tpuft_ssd_bwd.3")


REFUSALS = [
    ("n_group", dict(n_group=8), "one group"),
    ("topk_group", dict(topk_group=4), "one group"),
    ("tied_head", dict(tie_word_embeddings=True), "untied"),
    ("sliding_window", dict(sliding_window=4096), "all of the past"),
    ("residual_in_fp32", dict(residual_in_fp32=True), "compute type"),
    ("time_step_ceiling", dict(time_step_limit=[0.0, 0.1]), "no clamp"),
    ("time_step_floor", dict(time_step_limit=[0.001, None]), "no clamp"),
    ("a_dense_block", dict(hybrid_override_pattern="MEM-M*EME" + "M" * 43), "pattern letter '-'"),
    ("an_unknown_letter", dict(hybrid_override_pattern="MEMXM*EME" + "M" * 43), "pattern letter 'X'"),
    ("a_short_pattern", dict(hybrid_override_pattern="MEM"), "3 letters for 5 blocks"),
    ("no_conv_bias", dict(use_conv_bias=False), "convolution has a bias"),
    ("a_projection_bias", dict(mamba_proj_bias=True), "no bias"),
    ("silu_experts", dict(mlp_hidden_act="silu"), "ReLU\\^2"),
    ("gates_not_renormalised", dict(norm_topk_prob=False), "renormalised"),
    ("a_shared_width_of_40", dict(moe_shared_expert_intermediate_size=40), "whole number of expert widths"),
    ("four_groups_for_six_heads", dict(n_groups=4), "whole number of heads"),
]


# -- the three-stack tree through ft_step, a heal's transport and the checkpoint -----------------


def _tiny() -> Tiny:
    def tree_facts(tree) -> None:
        assert set(tree) == {"embed", "final_norm", "lm_head", "mamba", "attn", "moe"}
        assert tree["mamba"]["A_log"].shape == (2, 6) and tree["mamba"]["A_log"].dtype == jnp.float32
        assert tree["mamba"]["ssm_conv"].shape == (2, 112, 4) and "mlp_norm" not in tree["mamba"]

    def facts(moved, summaries, step, after) -> None:
        assert {"['embed']", "['mamba']['A_log']", "['mamba']['ssm_D']", "['mamba']['dt_bias']", "['mamba']['ssm_conv']",
                "['mamba']['ssm_conv_bias']", "['attn']['wk']", "['moe']['router']", "['moe']['w_up']"} <= moved
        summary = summaries[-1]
        assert summary["moe_dropped"] == 0
        assert 0 < summary["moe_rows_held"] < summary["moe_assignments"] == 2 * 2 * 2 * SEQ
        assert 0.0 < summary["ssm_decay_mean"] < 1.0 and 0 < summary["moe_active_units"] < summary["moe_units_held"]

    return tiny_of_the_small_model("mamba2_moe_lm", CONFIG, _batch(0), tree_facts, facts)


ARCH = Architecture(
    name="mamba2_moe_lm", configs={"share": CONFIG, "runs": RUNS}, sizes=SIZES, seq=SEQ, variants=dict(WALKS, as_published={}, **SCANS_OF_RUNS),
    leaf_cases=[Case(f"{walk}-{stack}", "share", walk, 1, stack=stack) for walk in WALKS for stack in STACKS]
    + [Case(f"{walk}-a_pattern_with_runs", "runs", walk, 3) for walk in SCANS_OF_RUNS],
    # what differs is the order of sums — the chunk form against the recurrence position by position, the grouped experts
    # against the masked loop: every leaf to 2e-4 of its largest entry; a missing term is 1e-2 or more (the pieces)
    leaf_error="max", leaf_tolerance=2e-4, loss_tolerance=1e-6, off_start=True, counters=_counters, stacks=STACKS,
    pieces=[_without(piece) for piece in PIECES], pieces_at=("share", 2), piece_floor=2e-2,
    chips=[16, 4, 1], expert_layer=_expert_layer,
    published="nemotron-twotower-30b-a3b", tree_facts=_tree_facts, published_facts=_published_facts,
    refusals=REFUSALS, refusal_config="share", through=("ft_step", "heal", "disk_checkpoint"), tiny=_tiny,
)


# -- the scan: kernels, chunk form, loop -------------------------------------------------------


def _scan_operands(heads, p, groups, n, seq, seed=0, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (batch, seq, heads * p), jnp.float32)
    bm = jax.random.normal(ks[1], (batch, seq, groups * n), jnp.float32) * n ** -0.5
    cm = jax.random.normal(ks[2], (batch, seq, groups * n), jnp.float32) * n ** -0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (batch, seq, heads)) - 2.0)
    la = -jnp.exp(jax.random.uniform(ks[4], (heads,), minval=0.0, maxval=2.7)) * dt
    return x * jnp.repeat(dt, p, axis=-1), bm, cm, la


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


SCANS = {
    # heads, head width, groups, state, positions, chunk: several chunks, the last one partial where seq % chunk
    "six_heads_in_two_groups_a_partial_chunk": (6, 16, 2, 32, 100, 32),
    "twelve_heads_of_64_in_three_groups": (12, 64, 3, 128, 256, 128),
    "one_head_a_group": (2, 128, 2, 128, 256, 128),
}


@pytest.mark.parametrize("what", ["output", "gradients"])
@pytest.mark.parametrize("form", ["xla", "kernels_interpreted"])
@pytest.mark.parametrize("shape", list(SCANS))
def test_the_chunk_scan_against_the_recurrence_position_by_position(shape, form, what) -> None:
    """`ops.ssd.ssd` — the XLA chunk form, and the `tpuft_ssd_fwd` /
    `tpuft_ssd_bwd` kernels in interpret mode — against `ssd_loop`, float32: the
    output and the four gradients to 2e-5 of their norm, at head counts that are
    no multiple of eight, several chunks and a sequence that ends inside one."""
    heads, p, groups, n, seq, chunk = SCANS[shape]
    args = _scan_operands(heads, p, groups, n, seq)
    kw = dict(head_dim=p, groups=groups)
    run = lambda *a: ssd.ssd(*a, chunk=chunk, interpret=form != "xla", **kw)   # noqa: E731
    loop = lambda *a: ssd.ssd_loop(*a, **kw)[0]                                 # noqa: E731
    if what == "output":
        assert _rel(run(*args), loop(*args)) < 2e-5
        return
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = jax.grad(lambda *a: jnp.sum(run(*a) * weight), argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(lambda *a: jnp.sum(loop(*a) * weight), argnums=(0, 1, 2, 3))(*args)
    for name, g, w in zip(("xdt", "B", "C", "la"), got, want):
        assert _rel(g, w) < 2e-5, name


def test_the_kernels_are_the_chunk_form_and_a_fast_decay_underflows() -> None:
    """Interpreted, the kernels run the XLA form's two chunk functions: equal
    to rounding.  And a log decay of -40 a position (a = 4e-18) gives zeros
    where it means zeros, never an overflow: every exponent is <= 0."""
    heads, p, groups, n, seq, chunk = SCANS["twelve_heads_of_64_in_three_groups"]
    xdt, bm, cm, la = _scan_operands(heads, p, groups, n, seq, seed=3)
    la = la.at[:, ::3].set(-40.0)
    kw = dict(head_dim=p, groups=groups, chunk=chunk)
    fn = lambda interpret: jax.value_and_grad(                                  # noqa: E731
        lambda *a: jnp.sum(jnp.square(ssd.ssd(*a, interpret=interpret, **kw))), argnums=(0, 1, 2, 3))(xdt, bm, cm, la)
    (ya, ga), (yb, gb) = fn(False), fn(True)
    assert abs(float(ya) - float(yb)) <= 1e-6 * abs(float(ya))
    assert all(bool(jnp.all(jnp.isfinite(g))) and _rel(g, h) < 1e-6 for g, h in zip(gb, ga))
    assert _rel(ssd.ssd(xdt, bm, cm, la, **kw), ssd.ssd_loop(xdt, bm, cm, la, head_dim=p, groups=groups)[0]) < 2e-5


def test_the_kernels_run_where_a_group_fills_whole_lane_blocks(monkeypatch) -> None:
    monkeypatch.setattr(ssd._pallas_util, "kernels_apply", lambda mesh=None: True)
    assert ssd.applies(64, 8, 128) and ssd.applies(128, 1, 128) and ssd.applies(32, 4, 256)
    assert not ssd.applies(64, 3, 128) and not ssd.applies(48, 8, 128) and not ssd.applies(64, 8, 64)
    assert ssd.CHUNK == PUBLISHED["chunk_size"] == 128 and ssd.SAVED_NAMES == ("tpuft_ssd_out",)


# -- the pieces of ssm_mix against written-out loops --------------------------------------------


def test_the_convolution_s_first_three_positions_and_its_bias() -> None:
    """c_t = sum_i w_i z_{t-3+i} + b with zeros before the first position: the
    first three outputs see one, two and three positions, and the taps leaf is
    [channel, tap] as published."""
    rng = np.random.default_rng(0)
    z, taps = rng.standard_normal((2, 9, 5)).astype(np.float32), rng.standard_normal((5, 4)).astype(np.float32)
    bias = rng.standard_normal(5).astype(np.float32)
    want = np.zeros_like(z) + bias
    for b in range(2):
        for t in range(9):
            for i in range(4):
                if t - 3 + i >= 0:
                    want[b, t] += taps[:, i] * z[b, t - 3 + i]
    got = _causal_conv(jnp.asarray(z), jnp.asarray(taps).T) + bias
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    for t in range(3):  # position t sees positions 0..t alone
        np.testing.assert_allclose(want[:, t], bias + sum(taps[:, 3 - j] * z[:, t - j] for j in range(t + 1)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(REFERENCE._short_conv(jnp.asarray(z[0]), jnp.asarray(taps), jnp.asarray(bias))),
                               want[0], rtol=1e-6, atol=1e-6)


def test_the_group_norm_has_the_gate_inside() -> None:
    """o = RMSNorm over each group's columns of (y + D x) * SiLU(z): the gate
    FIRST.  Norm first and gate after is another number."""
    cfg = TransformerConfig(vocab_size=32, d_model=12, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=16, dtype=jnp.float32,
                            ssm_head_dim=4, ssm_groups=2, ssm_state=8, rms_eps=1e-5)
    rng = np.random.default_rng(1)
    y, x, z = (rng.standard_normal((1, 5, 16)).astype(np.float32) for _ in range(3))
    w = {"ssm_D": rng.standard_normal(4).astype(np.float32), "ssm_norm": (1 + 0.3 * rng.standard_normal(16)).astype(np.float32)}
    got = np.asarray(mamba._after(jnp.asarray(y), jnp.asarray(x), jnp.asarray(z), {k: jnp.asarray(v) for k, v in w.items()}, cfg, 4))
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    gated = (y + np.repeat(w["ssm_D"], 4) * x) * silu(z)
    want, after = np.zeros_like(gated), np.zeros_like(gated)
    for g in range(2):
        cols = slice(8 * g, 8 * g + 8)
        want[..., cols] = gated[..., cols] / np.sqrt((gated[..., cols] ** 2).mean(-1, keepdims=True) + 1e-5)
        plain = y + np.repeat(w["ssm_D"], 4) * x
        after[..., cols] = plain[..., cols] / np.sqrt((plain[..., cols] ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want * w["ssm_norm"], rtol=1e-5, atol=1e-6)
    assert np.abs(got - after * w["ssm_norm"] * silu(z)).max() > 0.1


def test_the_whole_mixer_against_a_written_out_loop() -> None:
    """`mamba2_mixer` — the three products of W_in, the convolution with its
    bias and SiLU, softplus, the recurrence with a scalar decay a head and a
    group's B and C, the skip, the gated group norm, W_out — against numpy
    loops over positions and heads at 11 positions x 4 heads of 4 in 2 groups."""
    kind = LayerKind("layers", False, 4, 1e4, mixer="mamba2", feed_forward=False)
    cfg = TransformerConfig(vocab_size=32, d_model=12, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=16, dtype=jnp.float32,
                            ssm_head_dim=4, ssm_groups=2, ssm_state=8, ssm_chunk=4, rms_eps=1e-5, pattern=(kind,))
    w = jax.tree.map(lambda a: a[0], init_params(jax.random.PRNGKey(3), cfg)["layers"])
    rng = np.random.default_rng(3)
    w = dict(w, ssm_conv_bias=jnp.asarray(0.3 * rng.standard_normal(48), jnp.float32),
             ssm_D=jnp.asarray(1 + 0.3 * rng.standard_normal(4), jnp.float32),
             ssm_norm=jnp.asarray(1 + 0.3 * rng.standard_normal(16), jnp.float32))
    h = rng.standard_normal((1, 11, 12)).astype(np.float32)
    got, decay = mamba.mamba2_mixer(cfg, kind, None, jnp.asarray(h), w)
    n = {k: np.asarray(v, np.float64) for k, v in w.items()}
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    S, H, P, G, N = 11, 4, 4, 2, 8
    proj = h[0].astype(np.float64) @ n["ssm_in"]
    z, u, dt = proj[:, :16], proj[:, 16:64], np.log1p(np.exp(proj[:, 64:] + n["dt_bias"]))
    conv = np.zeros((S, 48)) + n["ssm_conv_bias"]
    for t in range(S):
        for i in range(4):
            if t - 3 + i >= 0:
                conv[t] += n["ssm_conv"][:, i] * u[t - 3 + i]
    conv = silu(conv)
    x, bm, cm = conv[:, :16].reshape(S, H, P), conv[:, 16:32].reshape(S, G, N), conv[:, 32:].reshape(S, G, N)
    a = np.exp(-np.exp(n["A_log"]) * dt)                                         # [S, H]
    y = np.zeros((S, H, P))
    for head in range(H):
        state = np.zeros((P, N))
        for t in range(S):
            state = a[t, head] * state + dt[t, head] * np.outer(x[t, head], bm[t, head // 2])
            y[t, head] = state @ cm[t, head // 2] + n["ssm_D"][head] * x[t, head]
    gated = y.reshape(S, 16) * silu(z)
    gated = gated.reshape(S, G, 8)
    gated = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(S, 16) * n["ssm_norm"]
    np.testing.assert_allclose(np.asarray(got[0]), gated @ n["ssm_out"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(decay), a.mean(), rtol=1e-5)


def test_a_block_is_a_mixer_a_feed_forward_or_both() -> None:
    """The kinds the pattern may hold: a kind without a mixer AND without a
    feed-forward is refused, as is a sparse kind without a feed-forward; the
    nine accepted configurations' kinds keep both halves by default."""
    base = dict(vocab_size=32, d_model=16, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=16)
    with pytest.raises(AssertionError, match="a mixer, a feed-forward, or both"):
        TransformerConfig(**base, pattern=(LayerKind("layers", False, 2, 1e4, mixer="none", feed_forward=False),))
    with pytest.raises(AssertionError, match="a mixer, a feed-forward, or both"):
        TransformerConfig(**base, moe_experts=4, pattern=(LayerKind("layers", True, 2, 1e4, feed_forward=False),))
    assert LayerKind("layers", True, 2, 1e4).feed_forward and LayerKind("layers", True, 2, 1e4).mixer == "attention"
    dense = TransformerConfig(**base, dtype=jnp.float32, moe_activation="relu2",
                              pattern=(LayerKind("layers", False, 2, 1e4, mixer="none"),))
    params = init_params(jax.random.PRNGKey(0), dense)
    assert set(params["layers"]) == {"mlp_norm", "w_up", "w_down"}  # `-`: a dense un-gated feed-forward alone
    loss, _ = loss_and_counters(params, _batch(0, vocab=32, seq_len=8), dense)
    assert np.isfinite(float(loss))

