"""Qwen3-Next-shaped models (Gated DeltaNet — a decay a head, key heads shared
by value heads — 3 : 1 with output-gated attention, zero-centred norms, a
softmax top-k router beside a shared expert under a sigmoid gate, two layer
stacks) through the program, on the CPU at small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/gdn_moe_lm.py``).  What only this
architecture has is tested here: the whole mixer against a written-out loop,
the column gate and the partial rotation of the attention kind, the program in
bfloat16, and what the new mixer refuses.
"""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, Architecture, Case, ExpertLayer, Piece, Tiny, batches, program_cfg, inputs, reference_run, pytest_generate_tests, store,
    test_a_model_without_a_piece_is_another_model, test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_the_adapter_raises_on_what_it_does_not_honour, test_the_published_configuration_is_handed_over_whole,
    test_the_shares_add_up_to_the_uncut_layer, test_the_tree_goes_through, test_the_tree_is_the_reference_s)
from torchft_tpu.models import LayerKind, TransformerConfig, init_params
from torchft_tpu.models.gdn import _gdn_mixer
from torchft_tpu.models.moe import moe_layer
from torchft_tpu.models.transformer import loss_and_counters
from torchft_tpu.ops import delta_attention

REFERENCE = BENCH.reference("gdn_moe_lm")
PROGRAM = BENCH.program("gdn_moe_lm")
PUBLISHED = BENCH.config("qwen3-next-80b-a3b")

SEQ = 40
SIZES = """40 positions under chunks of 16 (`_small_chunks`: the model's chunk of 64 would be one chunk): two chunks and a
half, so the scan crosses a chunk's edge twice and ends inside one.  The cut's four layers (GDN, GDN, GDN, attention):
one whole period, a stack of three and a stack of one.  Gated DeltaNet at 2 key heads under 4 value heads of 16 (a key
head serves two value heads, as published) under a kernel-4 convolution, attention at 4 / 2 heads of 32 with 8 of a
head's columns rotated, 8 router outputs of which this chip holds experts 2-5, 3 a token, a shared expert under its
gate.  Float32 throughout."""
CONFIG = dict(
    PUBLISHED, vocab_size=300, hidden_size=64, moe_intermediate_size=32, shared_expert_intermediate_size=32, head_dim=32,
    num_attention_heads=4, num_key_value_heads=2, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, num_experts=4, num_experts_per_tok=3, max_position_embeddings=128, rope_theta=100.0,
    expert_parallel=dict(chips=2, rank=0, router_outputs=8, first_expert_held=2),
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
    program=dict(remat=False, remat_keeps_attention=False, scan_unroll=8),
)


@contextlib.contextmanager
def _chunks_of_16():
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(delta_attention.kda, "__kwdefaults__", dict(delta_attention.kda.__kwdefaults__, chunk=16))
        yield


@pytest.fixture(autouse=True)
def _small_chunks():
    """Chunks of 16 positions, so that a sequence of 40 crosses chunk
    boundaries and ends inside one."""
    with _chunks_of_16():
        yield


_batch = batches(300, SEQ)

# two walks, the cheapest and the one the benchmark's program is nearest to under `lax.scan`: each is a compile, and the
# walks themselves are the siblings' code (tests/test_kda_moe.py runs all five)
WALKS = {
    "static_loop": dict(remat=False, scan_unroll=8),
    "remat_in_the_scan": dict(remat=True, remat_keeps_attention=True, scan_unroll=1),
}
STACKS = ("gdn_layers", "attn_layers", "embed", "final_norm", "lm_head")


def _counters(counters, config) -> None:
    assert int(counters["moe_dropped"]) == 0 and int(counters["moe_assignments"]) == 4 * 2 * 3 * SEQ
    assert 0 < int(counters["moe_rows_held"]) < int(counters["moe_assignments"])
    assert 0.0 < float(counters["gdn_alpha_mean"]) < 1.0 and 0.0 < float(counters["moe_shared_gate_mean"]) < 1.0


# -- the shares add up: 16 chips hold 2 of 32 experts each, the gated shared expert counted once ----------


def _expert_layer() -> ExpertLayer:
    hidden, ffn, experts, k = 32, 16, 32, 4
    rng = np.random.default_rng(4)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)  # noqa: E731
    w = dict(router=draw(hidden, experts), w_gate=draw(experts, hidden, ffn), w_up=draw(experts, hidden, ffn),
             w_down=draw(experts, ffn, hidden), shared_gate=draw(hidden, ffn), shared_up=draw(hidden, ffn),
             shared_down=draw(ffn, hidden), shared_scale=draw(hidden, 1))
    x = jnp.asarray(rng.standard_normal((2, 24, hidden)), jnp.float32)
    s = dict(experts=experts, held=experts, first=0, top_k=k, aux_coef=0.0)

    def uncut(x):
        return jnp.stack([REFERENCE._experts(seq, w, s, "float32")[0] for seq in x]), None

    def share(first, count, with_shared, x):
        held = slice(first, first + count)
        return moe_layer(x, w["router"], w["w_gate"][held], w["w_up"][held], w["w_down"][held], top_k=k,
                         capacity_factor=None, norm_topk=True, score="softmax", held_first=first,
                         shared=(w["shared_gate"], w["shared_up"], w["shared_down"]) if with_shared else None,
                         shared_scale=w["shared_scale"] if with_shared else None, dtype=jnp.float32)

    return ExpertLayer((x,), experts, share, uncut, 2 * 24 * k, sin=5.0, grad_rtol=1e-3, shared=True)


# -- the tree, the configuration, the adapter --------------------------------------------------


def _tree_facts(cfg, ours) -> None:
    """Two stacks, both sparse; the decay's leaves are float32 and ONE number a
    value head, the zero-centred norms start at zero and the head norm at one,
    and the count is the file's and the published model's."""
    assert [(s, k.mixer, k.sparse, n) for s, (k, n) in cfg.stacks.items()] == [("gdn_layers", "gdn", True, 3), ("attn_layers", "attention", True, 1)]
    assert [kind.mixer for kind in cfg.layers] == ["gdn", "gdn", "gdn", "attention"]
    theirs = jax.eval_shape(lambda: REFERENCE.make_weights(1, PUBLISHED))
    assert [l.dtype for l in jax.tree.leaves(ours)] == [l.dtype for l in jax.tree.leaves(theirs)]
    assert ours["gdn_layers"]["A_log"].shape == ours["gdn_layers"]["dt_bias"].shape == (3, 32)
    assert ours["gdn_layers"]["wq"].shape == (3, 2048, 2048) and ours["gdn_layers"]["wv"].shape == ours["gdn_layers"]["wz"].shape == (3, 2048, 4096)
    assert ours["attn_layers"]["attn_out_gate"].shape == ours["attn_layers"]["wq"].shape == (1, 2048, 4096)
    assert ours["attn_layers"]["wk"].shape == (1, 2048, 512) and ours["attn_layers"]["q_norm"].shape == (1, 256)
    assert ours["gdn_layers"]["shared_scale"].shape == (3, 2048, 1) and ours["gdn_layers"]["router"].shape == (3, 2048, 512)
    flops = BENCH.flops("gdn_moe_lm")
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(ours)) == 625_667_136 == flops.total_params(PUBLISHED)
    assert flops.published_params(PUBLISHED) == 79_674_391_296  # the published 80B: 36 GDN and 12 attention layers, 512 experts each
    small = PROGRAM.transformer_config(CONFIG)
    started, seeded = init_params(jax.random.PRNGKey(2), small), REFERENCE.make_weights(2, CONFIG)
    for name in ("attn_norm", "mlp_norm"):
        assert not np.any(np.asarray(started["gdn_layers"][name])) and 0.05 < float(jnp.std(seeded["gdn_layers"][name])) < 0.2
    assert not np.any(np.asarray(started["final_norm"])) and not np.any(np.asarray(started["attn_layers"]["q_norm"]))
    for tree in (started, seeded):
        assert np.all(np.asarray(tree["gdn_layers"]["gdn_norm"]) == 1.0)
        rate, steps = np.exp(np.asarray(tree["gdn_layers"]["A_log"])), np.asarray(jax.nn.softplus(tree["gdn_layers"]["dt_bias"]))
        assert 0.0 < rate.min() and rate.max() <= 16.0 and 0.001 * 0.999 <= steps.min() and steps.max() <= 0.1 * 1.001


def _published_facts(cfg, _) -> None:
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.d_head, cfg.n_heads, cfg.n_kv_heads) == (2048, 512, 18992, 256, 16, 2)
    assert (cfg.gdn_key_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv) == (16, 128, 128, 4)
    assert [(kind.mixer, kind.n_heads, kind.rotary_fraction, kind.rope_theta) for kind in cfg.pattern] == [("gdn", 32, 0.25, 1e7)] * 3 + [("attention", 16, 0.25, 1e7)]
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_shared_experts, cfg.moe_aux_coef) == (512, 10, (0, 32), 1, 0.001)
    assert cfg.moe_score == "softmax" and cfg.moe_norm_topk and cfg.moe_shared_gate and cfg.rms_eps == 1e-6 and not cfg.tied_head
    assert cfg.norm_unit_offset and cfg.attn_out_gate and cfg.qk_norm_per_head and not cfg.attn_head_gate
    # every number of the catalog's row under the same key, the three cuts listed
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert PUBLISHED["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151_936}
    assert (PUBLISHED["full_attention_interval"], PUBLISHED["partial_rotary_factor"], PUBLISHED["shared_expert_intermediate_size"]) == (4, 0.25, 512)
    assert set(PROGRAM.kernel_names()) >= {"attn", "ce", "gmm", "gdn"} and PROGRAM.kernel_names()["gdn"]("x.tpuft_kda_bwd.3")


REFUSALS = [
    ("a_dense_layer", dict(mlp_only_layers=[1]), "sparse one"),
    ("a_sparse_step_of_two", dict(decoder_sparse_step=2), "sparse one"),
    ("a_sliding_window", dict(use_sliding_window=True), "sliding window"),
    ("tied_head", dict(tie_word_embeddings=True), "untied"),
    ("scaled_rope", dict(rope_scaling={"type": "yarn"}), "unscaled"),
    ("gates_not_renormalised", dict(norm_topk_prob=False), "renormalised"),
    ("a_wider_shared_expert", dict(shared_expert_intermediate_size=64), "as wide as a routed one"),
    ("extra_prediction_layers", dict(num_nextn_predict_layers=1), "extra prediction"),
]


# -- the two-stack tree through ft_step, a heal's transport and the checkpoint -----------------


def _tiny() -> Tiny:
    program = BENCH.program("gdn_moe_lm")
    cfg = dataclasses.replace(program.transformer_config(CONFIG), remat=True, remat_keeps_attention=True, scan_unroll=1)

    def params():
        tree = init_params(jax.random.PRNGKey(5), cfg)
        assert set(tree) == {"embed", "final_norm", "lm_head", "gdn_layers", "attn_layers"}
        assert tree["gdn_layers"]["A_log"].shape == (3, 4) and tree["gdn_layers"]["A_log"].dtype == jnp.float32
        return tree

    def facts(moved, summaries, step, after) -> None:
        assert {"['embed']", "['gdn_layers']['A_log']", "['gdn_layers']['dt_bias']", "['gdn_layers']['gdn_conv_k']", "['gdn_layers']['wz']",
                "['attn_layers']['attn_out_gate']", "['attn_layers']['q_norm']", "['gdn_layers']['shared_scale']", "['final_norm']"} <= moved
        summary = summaries[-1]
        assert summary["moe_dropped"] == 0 and 0 < summary["moe_rows_held"] < summary["moe_assignments"] == 4 * 2 * 3 * SEQ
        assert 0.0 < summary["gdn_alpha_mean"] < 1.0 and 0.0 < summary["moe_shared_gate_mean"] < 1.0

    data = _batch(0)
    return Tiny(params, lambda p, b: loss_and_counters(p, b, cfg), lambda i: data, 2, facts)


PIECES = ("key_head_map", "attention_gate", "rotate_all", "norm_offset", "shared_gate")
assert set(PIECES) <= set(REFERENCE.LEFT_OUT)

ARCH = Architecture(
    name="gdn_moe_lm", configs={"share": CONFIG}, sizes=SIZES, seq=SEQ, variants=dict(WALKS, as_published={}),
    leaf_cases=[Case(f"{walk}-{stack}", "share", walk, 1, stack=stack) for walk in WALKS for stack in STACKS],
    # what differs is the order of sums — the chunk form against the recurrence position by position, the grouped experts
    # against the masked loop: every leaf to 2e-4 of its largest entry; a missing term is 2e-2 or more (the pieces)
    leaf_error="max", leaf_tolerance=2e-4, loss_tolerance=1e-6, off_start=True, counters=_counters, stacks=STACKS,
    tracing=_chunks_of_16,  # the fixture's patch does not reach a cached call made once
    # a piece left out of the REFERENCE (its `LEFT_OUT`), against the program as published: those no sibling's test has
    # (all nine are `benchmark/tests/test_gdn_moe_lm.py`'s and, at the cell's size, `tools/routing_ties_gdn.py`'s)
    pieces=[Piece(piece, "reference", piece) for piece in PIECES], pieces_at=("share", 1), piece_floor=2e-2,
    chips=[16, 1], expert_layer=_expert_layer,
    published="qwen3-next-80b-a3b", tree_facts=_tree_facts, published_facts=_published_facts,
    refusals=REFUSALS, refusal_config="share", through=("ft_step", "heal", "disk_checkpoint"), tiny=_tiny,
)


# -- what only this architecture has -------------------------------------------------------------


def test_the_program_in_bfloat16_against_the_float32_reference() -> None:
    """The small model computed in bfloat16 (weights float32, as the benchmark's
    configuration states) against the float32 reference: the loss to 2e-3 and
    the cell's own number, `grad_rel` (benchmark/compare.py: a leaf's error over
    its norm, the root mean square over the leaves), under 0.06 — bfloat16
    rounds every product's operands to 2**-9, the chunked scan rounds its state
    as an operand, and top-3 of 8 over 80 positions settles a near-tie or two
    the other way; a missing piece reads 0.3 and more in the same measure."""
    from benchmark import compare

    cfg, _ = program_cfg(ARCH, "share")
    weights, data = inputs(ARCH, "share", 1)
    with _chunks_of_16():
        (loss, _), grads = jax.jit(jax.value_and_grad(
            lambda p: loss_and_counters(p, data, dataclasses.replace(cfg, dtype=jnp.bfloat16)), has_aux=True))(weights)
    want_loss, want = reference_run(ARCH, "share", 1)
    flat = lambda tree: {jax.tree_util.keystr(p): np.asarray(l, np.float32) for p, l in jax.tree_util.tree_leaves_with_path(tree)}  # noqa: E731
    rel, per_leaf = compare.grad_rel(flat(grads), flat(want))
    assert abs(float(loss) - want_loss) / want_loss < 2e-3 and rel < 0.06, (float(loss), want_loss, rel, max(per_leaf.items(), key=lambda kv: kv[1]))


def test_the_whole_mixer_against_a_written_out_loop() -> None:
    """`_gdn_mixer` — projections, the convolution with SiLU, the key heads'
    norms, the decay and beta a VALUE head, the recurrence with value head j on
    key head j // 2, the head norm times SiLU(z) — against numpy loops over
    positions and heads at 11 positions x 4 value heads of 4 over 2 key heads."""
    cfg = TransformerConfig(vocab_size=32, d_model=12, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=16, dtype=jnp.float32, rms_eps=1e-5,
                            gdn_key_heads=2, gdn_key_dim=4, gdn_value_dim=4, pattern=(LayerKind("layers", False, 4, 1e4, mixer="gdn"),))
    w = jax.tree.map(lambda a: a[0], init_params(jax.random.PRNGKey(3), cfg)["layers"])
    rng = np.random.default_rng(3)
    w = dict(w, gdn_norm=jnp.asarray(1 + 0.3 * rng.standard_normal(4), jnp.float32))
    h = rng.standard_normal((1, 11, 12)).astype(np.float32)
    got, alpha = _gdn_mixer(cfg, cfg.pattern[0], None, jnp.asarray(h), w)
    n = {k: np.asarray(v, np.float64) for k, v in w.items()}
    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731
    S, H, Hk, D = 11, 4, 2, 4
    conv = {}
    for name, taps, heads in (("wq", "gdn_conv_q", Hk), ("wk", "gdn_conv_k", Hk), ("wv", "gdn_conv_v", H)):
        proj, out = h[0].astype(np.float64) @ n[name], np.zeros((S, heads * D))
        for t in range(S):
            for i in range(4):
                if t - 3 + i >= 0:
                    out[t] += n[taps][i] * proj[t - 3 + i]
        conv[name] = silu(out).reshape(S, heads, D)
    g = -np.exp(n["A_log"]) * np.log1p(np.exp(h[0] @ n["gdn_a"] + n["dt_bias"]))      # [S, H]
    beta = 1 / (1 + np.exp(-(h[0] @ n["gdn_b"])))
    z = silu(h[0] @ n["wz"])
    want = np.zeros((S, H * D))
    for head in range(H):
        state, key = np.zeros((D, D)), head // (H // Hk)
        for t in range(S):
            q = conv["wq"][t, key] / np.sqrt((conv["wq"][t, key] ** 2).sum() + 1e-6) * D ** -0.5
            k = conv["wk"][t, key] / np.sqrt((conv["wk"][t, key] ** 2).sum() + 1e-6)
            state = np.exp(g[t, head]) * state
            state = state + beta[t, head] * np.outer(k, conv["wv"][t, head] - k @ state)
            o = state.T @ q
            want[t, head * D:(head + 1) * D] = o / np.sqrt((o * o).mean() + 1e-5) * n["gdn_norm"] * z[t, head * D:(head + 1) * D]
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(alpha), np.exp(g).mean(), rtol=1e-5)


def test_the_attention_kind_s_gate_is_a_column_s_and_its_rotation_a_quarter_of_a_head() -> None:
    """The attention kind alone: with `attn_out_gate` the output of the
    attention call is multiplied column by column by sigmoid(h W_g) before
    `wo` — a gate of zeros halves the layer's output exactly — and at
    `rotary_fraction` 0.25 the last three quarters of a head's columns pass
    RoPE unchanged while the first quarter turns."""
    from torchft_tpu.models.attention import ATTENTION, _plain_qkv

    kind = LayerKind("layers", False, 4, 100.0, rotary_fraction=0.25)
    base = dict(vocab_size=32, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=16, dtype=jnp.float32,
                qk_norm_per_head=True, norm_unit_offset=True, pattern=(kind,))
    gated, plain = TransformerConfig(**base, attn_out_gate=True), TransformerConfig(**base)
    w = jax.tree.map(lambda a: a[0], init_params(jax.random.PRNGKey(0), gated)["layers"])
    assert w["attn_out_gate"].shape == (32, 64) and "attn_gate" not in w
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 32))
    positions = jnp.broadcast_to(jnp.arange(24), (1, 24))
    ungated = ATTENTION.forward(plain, kind, None, None, h, w, positions)[0]
    halved = ATTENTION.forward(gated, kind, None, None, h, dict(w, attn_out_gate=jnp.zeros_like(w["attn_out_gate"])), positions)[0]
    np.testing.assert_allclose(np.asarray(halved), 0.5 * np.asarray(ungated), rtol=1e-5, atol=1e-6)
    by_column = ATTENTION.forward(gated, kind, None, None, h, w, positions)[0]
    assert float(jnp.max(jnp.abs(by_column - halved))) > 1e-3
    q, k, _ = _plain_qkv(gated, kind, h, w, positions)
    still = _plain_qkv(gated, dataclasses.replace(kind, rotary_fraction=0.0), h, w, positions)
    assert np.array_equal(np.asarray(q[..., 4:]), np.asarray(still[0][..., 4:])) and np.array_equal(np.asarray(k[..., 4:]), np.asarray(still[1][..., 4:]))
    assert float(jnp.max(jnp.abs(q[:, 1:, :, :4] - still[0][:, 1:, :, :4]))) > 1e-3


@pytest.mark.parametrize("key,message", [("qk_norm", "no QK-norm"), ("attn_head_gate", "no QK-norm"),
                                          ("qk_norm_per_head", "attention's alone"), ("attn_out_gate", "attention's alone")])
def test_the_mixer_refuses_what_is_attention_s_alone(key, message) -> None:
    """A pattern of Gated DeltaNet layers alone refuses the model-level keys
    that only an attention layer can take, as `KDA._check` does; beside an
    attention kind the per-head QK-norm and the column gate are that kind's."""
    gdn = LayerKind("layers", False, 4, 1e4, mixer="gdn")
    base = dict(vocab_size=32, d_model=16, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=16, gdn_key_heads=2, gdn_key_dim=4, gdn_value_dim=4)
    with pytest.raises(AssertionError, match=message):
        TransformerConfig(**base, pattern=(gdn, gdn), **{key: True})
    if key in ("qk_norm_per_head", "attn_out_gate"):
        TransformerConfig(**base, pattern=(gdn, LayerKind("attn", False, 2, 1e4)), **{key: True})
