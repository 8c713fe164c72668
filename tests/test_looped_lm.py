"""Looped models (Ouro's shape: a stack of dense blocks with a norm before AND
after each sublayer, run several times over the same weights, the final norm
after every pass, a head and an exit gate after every pass, the exit-weighted
loss) through the program, on the CPU at a small size.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`): the
program against the benchmark's plain float32 reference
(``benchmark/reference/looped_lm.py``, which shares no code with it) walk by
walk — the layers a static loop and a scan, rematerialised, the passes a static
loop and a scan — each piece of the looped mathematics left out of the
reference, the tree at the published widths, the adapter's refusals and the
tree through `ft_step`, a heal and the disk checkpoint.  What only this
architecture has is tested below: one pass without post-norms and gate is the
dense model bit for bit, a tied weight's gradient is the sum of the untied
passes', and the exit distribution's edges.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, Architecture, Case, Piece, Tiny, batch, inputs, program_run, pytest_generate_tests, reference_run, store,
    test_a_model_without_a_piece_is_another_model, test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_the_adapter_raises_on_what_it_does_not_honour, test_the_published_configuration_is_handed_over_whole,
    test_the_tree_goes_through, test_the_tree_is_the_reference_s, worst_leaf)
from torchft_tpu.models import LayerKind, TransformerConfig, init_params
from torchft_tpu.models.transformer import loss_and_counters

REFERENCE = BENCH.reference("looped_lm")
PROGRAM = BENCH.program("looped_lm")
PUBLISHED = BENCH.config("ouro-2.6b")

SEQ = 48
SIZES = """48 positions.  Two layers: a layer after a layer, a stack the scan walks, and a weight that two layers do not
share.  Three passes: a first, a middle and a last one (the last has no gate), so a weight's gradient is a sum of three
terms.  4 heads of 16 over 4 KV heads, a feed-forward of 96, 300 ids.  Float32 throughout."""
CONFIG = dict(
    PUBLISHED, vocab_size=300, hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=96, num_hidden_layers=2, total_ut_steps=3, max_position_embeddings=64,
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
    program=dict(remat=False, remat_keeps_attention=False, scan_unroll=2, loop_scan=False),
)
WALKS = {
    "static_loop": dict(remat=False, scan_unroll=2, loop_scan=False),
    "scan": dict(remat=False, scan_unroll=1, loop_scan=False),
    "remat_in_the_scan": dict(remat=True, scan_unroll=1, loop_scan=False),
    # the pass loop in the form the cell runs (static, rematerialised layers that keep nothing) and in the other one
    "remat_static_passes": dict(remat=True, remat_keeps_attention=False, scan_unroll=2, loop_scan=False),
    "passes_in_a_scan": dict(remat=True, remat_keeps_attention=True, scan_unroll=2, loop_scan=True),
}
PIECES = ("passes", "entropy", "exit_weights", "post_norms", "norm_between_passes")


def _counters(counters, config) -> None:
    """p sums to one a token; the passes' losses and the entropy are a looped model's."""
    passes, tokens = config["total_ut_steps"], 2 * SEQ
    mass = np.asarray(counters["loop_exit_mass"])
    assert mass.shape == (passes,) and abs(float(mass.sum()) - tokens) < 1e-3 * tokens and (mass > 0).all()
    assert np.asarray(counters["loop_pass_loss"]).shape == (passes,)
    assert 0.0 < float(counters["loop_exit_entropy"]) <= np.log(passes) + 1e-6


def _tree_facts(cfg, ours) -> None:
    assert set(ours) == {"embed", "final_norm", "lm_head", "layers", "exit_gate"} and list(cfg.stacks) == ["layers"]
    assert set(ours["layers"]) == {"attn_norm", "attn_post_norm", "wq", "wk", "wv", "wo", "mlp_norm", "mlp_post_norm",
                                   "w_gate", "w_up", "w_down"}
    assert ours["layers"]["wk"].shape == (8, 2048, 16 * 128) and ours["layers"]["w_up"].shape == (8, 2048, 5632)
    assert ours["layers"]["mlp_post_norm"].shape == (8, 2048) and ours["embed"].shape == (49152, 2048)
    assert ours["exit_gate"]["w"].shape == (2048,) and ours["exit_gate"]["b"].shape == (1,)
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(ours)) == BENCH.flops("looped_lm").total_params(PUBLISHED)


def _published_facts(cfg, published) -> None:
    assert (cfg.loop_steps, cfg.exit_beta, cfg.n_layers, cfg.rms_eps) == (4, 0.05, 8, 1e-6) and not cfg.tied_head
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff, cfg.vocab_size) == (2048, 16, 16, 128, 5632, 49152)
    assert set(cfg.pattern) == {LayerKind("layers", False, 16, 1e6, post_norms=True)} and len(cfg.pattern) == 8
    assert cfg.remat and cfg.loop_scan == published["program"]["loop_scan"] and cfg.dtype == jnp.bfloat16
    assert published["reduced"] == ["num_hidden_layers"] and published["published"] == {"num_hidden_layers": 48}
    assert {"beta", "four_norms_a_block", "no_bias", "gate_input", "objective", "gate_stage_left_out", "weights",
            "learning_rate"} <= set(published["assumed"])


REFUSALS = [
    ("a_sliding_window", dict(use_sliding_window=True), "all of the past", True),
    ("a_rope_scaling", dict(rope_scaling={"type": "yarn", "factor": 4.0}), "plain RoPE", True),
    ("a_tied_head", dict(tie_word_embeddings=True), "untied head", True),
    ("an_exit_threshold_under_one", dict(early_exit_threshold=0.9), "every pass"),
    ("a_windowed_layer", dict(layer_types=["full_attention", "sliding_attention"]), "every layer attends"),
    ("one_pass", dict(total_ut_steps=1), "twice at least"),
]


def _tiny() -> Tiny:
    cfg = dataclasses.replace(PROGRAM.transformer_config(CONFIG), remat=True, scan_unroll=2)
    data = batch(0, CONFIG["vocab_size"], SEQ)

    def params():
        tree = init_params(jax.random.PRNGKey(5), cfg)
        assert tree["exit_gate"]["b"].shape == (1,) and tree["layers"]["attn_post_norm"].shape == (2, 64)
        return tree

    def facts(moved, summaries, step, after) -> None:
        assert {"['embed']", "['exit_gate']['w']", "['exit_gate']['b']", "['layers']['attn_post_norm']",
                "['layers']['mlp_post_norm']", "['layers']['wk']", "['final_norm']"} <= moved
        summary = summaries[-1]
        mass, losses = summary["loop_exit_mass"], summary["loop_pass_loss"]  # a number a pass lands whole
        assert len(mass) == len(losses) == 3 and abs(sum(mass) - 2 * SEQ) < 1e-2 and all(l > 0 for l in losses)
        assert summary["loop_exit_mass_max"] == max(mass) and 0.0 < summary["loop_exit_entropy"] < np.log(3) + 1e-6

    return Tiny(params, lambda p, b: loss_and_counters(p, b, cfg), lambda i: data, 2, facts)


ARCH = Architecture(
    name="looped_lm", configs={"whole": CONFIG}, sizes=SIZES, seq=SEQ, variants=dict(WALKS, as_published={}),
    leaf_cases=[Case(walk, "whole", walk, 7) for walk in WALKS],
    # float32 on both sides: the order of sums alone, every leaf to 3e-5 of its norm (the four norms' and the gate's too)
    leaf_tolerance=3e-5, loss_tolerance=1e-6, off_start=True, counters=_counters,
    # the reference without a piece against the program as published: each moves some leaf by a tenth of its norm or more
    pieces=[Piece(piece, "reference", piece) for piece in PIECES], pieces_at=("whole", 7), piece_floor=0.1,
    published="ouro-2.6b", tree_facts=_tree_facts, published_facts=_published_facts,
    refusals=REFUSALS, refusal_config="whole", through=("ft_step", "heal", "disk_checkpoint"), tiny=_tiny,
)


# -- what only a looped model has ------------------------------------------------------------------


def test_one_pass_without_post_norms_and_gate_is_the_dense_model_to_the_last_bit() -> None:
    """`loop_steps` 1, no `post_norms`, no `exit_beta`: the defaults.  A
    pattern of the plain kind is then the dense adapter's model — the same loss
    and the same gradient, bit for bit."""
    dense = BENCH.program("dense_lm").transformer_config(dict(
        CONFIG, architecture="dense_lm", program=dict(remat=False, scan_unroll=2)))
    dense = dataclasses.replace(dense, head_dim=16, rms_eps=1e-6)
    kind = LayerKind("layers", False, 4, float(CONFIG["rope_theta"]))
    plain = dataclasses.replace(PROGRAM.transformer_config(CONFIG), pattern=(kind,) * 2, loop_steps=1, exit_beta=None)
    assert plain.layers == dense.layers and (plain.loop_steps, plain.exit_beta) == (dense.loop_steps, dense.exit_beta) == (1, None)
    params = init_params(jax.random.PRNGKey(3), dense)
    assert jax.tree.structure(params) == jax.tree.structure(init_params(jax.random.PRNGKey(3), plain))
    data = batch(3, CONFIG["vocab_size"], SEQ)
    run = lambda cfg: jax.jit(jax.value_and_grad(lambda p: loss_and_counters(p, data, cfg)[0]))(params)  # noqa: E731
    (loss_a, grads_a), (loss_b, grads_b) = run(dense), run(plain)
    assert float(loss_a) == float(loss_b)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(jax.tree.leaves(grads_a), jax.tree.leaves(grads_b)))


def test_a_weights_gradient_is_the_sum_of_the_passes_gradients_with_the_weights_untied() -> None:
    """The reference with a copy of the layers a pass (`left_out="untied"`,
    the layers' leaves [T, L, ...], every copy the tied value): its loss is the
    tied model's, and its gradient summed over the copies is the tied weight's
    — the reference's and the program's."""
    weights, data = inputs(ARCH, "whole", 7)
    passes = CONFIG["total_ut_steps"]
    untied = dict(weights, layers=jax.tree.map(lambda l: jnp.broadcast_to(l, (passes,) + l.shape), weights["layers"]))
    one = REFERENCE.one_sequence_fn(CONFIG, "float32", "untied")
    runs = [one(untied, tokens, targets) for tokens, targets in zip(data["tokens"], data["targets"])]
    loss = float(sum(l for l, _ in runs) / len(runs))
    grads = jax.tree.map(lambda *g: sum(g) / len(runs), *[g for _, g in runs])
    want_loss, want = reference_run(ARCH, "whole", 7)
    assert abs(loss - want_loss) <= 1e-6 * abs(want_loss)
    a_pass = jax.tree.map(lambda g: g[1], grads["layers"])  # one pass's term alone is not the gradient
    assert worst_leaf(dict(grads, layers=a_pass), want)[1] > 0.3
    summed = dict(grads, layers=jax.tree.map(lambda g: g.sum(0), grads["layers"]))
    leaf, rel = worst_leaf(summed, want)
    assert rel < 1e-5, (leaf, rel)
    leaf, rel = worst_leaf(summed, program_run(ARCH, "whole", "static_loop", 7)[2])
    assert rel < 3e-5, (leaf, rel)


def test_gates_that_never_open_leave_the_last_passs_mean_loss() -> None:
    """lambda forced to (0, 0): no token leaves before the last pass, p = (0, 0,
    1) exactly and finite in logarithms, and the loss with beta = 0 is the
    loss of a looped model without a gate — the last pass's mean loss."""
    weights, data = inputs(ARCH, "whole", 7)
    cfg = dataclasses.replace(PROGRAM.transformer_config(CONFIG), exit_beta=0.0)
    shut = dict(weights, exit_gate={"w": jnp.zeros_like(weights["exit_gate"]["w"]), "b": jnp.full((1,), -1e4, jnp.float32)})
    (loss, counters), grads = jax.jit(jax.value_and_grad(lambda p: loss_and_counters(p, data, cfg), has_aux=True))(shut)
    bare = {k: v for k, v in weights.items() if k != "exit_gate"}
    last, _ = jax.jit(lambda p: loss_and_counters(p, data, dataclasses.replace(cfg, exit_beta=None)))(bare)
    assert abs(float(loss) - float(last)) <= 1e-6 * float(last)
    assert np.allclose(np.asarray(counters["loop_exit_mass"]), [0.0, 0.0, 2 * SEQ]) and float(counters["loop_exit_entropy"]) == 0.0
    assert abs(float(counters["loop_pass_loss"][-1]) - float(last)) <= 1e-6 * float(last)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))


def test_a_looped_configuration_refuses_what_the_passes_do_not_carry() -> None:
    import pytest

    base = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64, max_seq=32, dtype=jnp.float32)
    with pytest.raises(AssertionError, match="the exit gate is a looped model's"):
        TransformerConfig(**base, exit_beta=0.05)
    with pytest.raises(AssertionError, match="dense ones under an untied head"):
        TransformerConfig(**base, loop_steps=2, moe_experts=4, moe_capacity_factor=None)
    with pytest.raises(AssertionError, match="post-norms: a mixer and a dense feed-forward"):
        TransformerConfig(**base, pattern=(LayerKind("layers", False, 2, 1e4, feed_forward=False, post_norms=True),) * 2)
