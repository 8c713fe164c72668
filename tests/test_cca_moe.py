"""ZAYA1-shaped models (attention inside a compressed latent, a router that is
an MLP with a state carried from layer to layer, one expert a token or none,
learned residual merges, a head that is the embedding) through the program, on
the CPU at small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/cca_moe_lm.py``).  What only this
architecture has is tested here: each piece of the compressed mixer against a
written-out loop, the top-one gate, the tied head against the untied model's.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, Architecture, Case, ExpertLayer, Piece, Tiny, batches, off_start, pytest_generate_tests, store, tiny_of_the_small_model,
    test_a_model_without_a_piece_is_another_model, test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_the_adapter_raises_on_what_it_does_not_honour, test_the_published_configuration_is_handed_over_whole,
    test_the_shares_add_up_to_the_uncut_layer, test_the_tree_goes_through, test_the_tree_is_the_reference_s)
from torchft_tpu.models import LayerKind, TransformerConfig, init_params
from torchft_tpu.models.moe import moe_layer
from torchft_tpu.models.attention import _cca_qkv
from torchft_tpu.models.transformer import loss_and_counters, param_axes

REFERENCE = BENCH.reference("cca_moe_lm")
PROGRAM = BENCH.program("cca_moe_lm")
PUBLISHED = BENCH.config("zaya1-8b")

SEQ = 32
SIZES = """32 positions: the kernel-2 convolutions and the value shift see a first position and 31 others, and 256 positions
a step leave the biased top-1 choice some that take no expert, some held here and some held elsewhere.  The cut's 4
layers: the carried state crosses three boundaries (two layers, one boundary, for the pieces).  8 query heads on 2 KV
heads of 16, RoPE on half a head, a router state of 16 over 8 experts and the skip choice, of which this chip holds
experts 4-7.  `layer_types` keeps a longer list: the first four entries count.  Float32 throughout."""
CONFIG = dict(
    architecture="cca_moe_lm", vocab_size=300, hidden_size=64, num_hidden_layers=4, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, cca_time0=2, cca_time1=2, moe_intermediate_size=32, num_experts=4,
    num_experts_per_tok=1, router_hidden_size=16, layer_types=["hybrid"] * 6, hidden_act="silu",
    attention_bias=False, lm_head_bias=False, sliding_window=None, tie_word_embeddings=True, rms_norm_eps=1e-5,
    max_position_embeddings=128, partial_rotary_factor=0.5,
    rope_parameters={"hybrid": dict(partial_rotary_factor=0.5, rope_theta=100.0, rope_type="default"),
                     "rope_type": "default"},
    expert_parallel=dict(chips=2, rank=1, routed_experts=8, router_outputs=9, first_expert_held=4),
    router_bias=dict(seed=5, scale=0.01),
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=1e-7),
    program=dict(remat=False, remat_keeps_attention=False, scan_unroll=8),
)
WALKS = {
    "static_loop": dict(remat=False, scan_unroll=8),
    "scan": dict(remat=False, scan_unroll=1),
    "remat": dict(remat=True, scan_unroll=8),
    "remat_that_keeps_attention": dict(remat=True, remat_keeps_attention=True, scan_unroll=8),
    "remat_in_the_scan": dict(remat=True, remat_keeps_attention=True, scan_unroll=1),
}


def _weights(seed: int, config=CONFIG):
    return off_start(REFERENCE.make_weights(seed, config), seed)


_batch = batches(300, SEQ)


def _counters(counters, config) -> None:
    # a biased choice that takes no expert in some position, an expert held here in some, one held elsewhere in some
    positions = config["num_hidden_layers"] * 2 * SEQ
    assert 0 < int(counters["moe_skipped"]) < positions and int(counters["moe_dropped"]) == 0
    assert 0 < int(counters["moe_rows_held"]) < positions - int(counters["moe_skipped"])


# -- the shares of an expert-parallel layer: 2 chips hold 4 of 8 each; the skip choice adds nothing on any ----


def _expert_layer() -> ExpertLayer:
    from torchft_tpu.ops.rmsnorm import rms_norm

    seed, positions = 9, 2 * 64
    weights = _weights(seed, dict(CONFIG, num_experts=8, expert_parallel=None))
    w = jax.tree.map(lambda leaf: leaf[1], weights["layers"])
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, 64, 64)), jnp.float32) * 0.05
    state = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
    bias = jnp.asarray(REFERENCE.router_bias(CONFIG)[1])
    s = REFERENCE.sizes_of(dict(CONFIG, num_experts=8, expert_parallel=None))
    assert (s["held"], s["experts"], s["first"]) == (8, 8, 0)
    plain = dict(w, mlp_merge=jnp.asarray([[1.0], [0.0], [1.0], [0.0]]) * jnp.ones((4, 64)))  # x + y: y = merged - x

    def uncut(x):  # the whole sublayer before its merge, and the state it hands on
        outs = [REFERENCE._experts(seq, plain, st, bias, s, "float32") for seq, st in zip(x, state)]
        return jnp.stack([y - seq for (y, _), seq in zip(outs, x)]), jnp.stack([carried for _, carried in outs])

    def share(first, count, _, x):  # on its normed input; the skip choice is the ninth output
        held = slice(first, first + count)
        return moe_layer(rms_norm(x, w["mlp_norm"], 1e-5), w["router"], w["w_gate"][held], w["w_up"][held], w["w_down"][held],
                         top_k=1, capacity_factor=None, norm_topk=False, score="softmax", route_bias=bias, router_state=state,
                         skip=True, rms_eps=1e-5, held_first=first, dtype=jnp.float32)

    def facts(stats, want_state, _) -> None:
        np.testing.assert_allclose(np.asarray(stats[0]["router_state"]), np.asarray(want_state), rtol=1e-5, atol=1e-6)
        # skipped + held here + held on the other chips = positions, on every chip
        skipped = int(stats[0]["skipped"])
        assert 0 < skipped < positions and all(int(st["skipped"]) == skipped for st in stats)
        assert all(st["tokens_per_expert"].shape == (8,) for st in stats)
        assert int(jnp.sum(stats[0]["tokens_per_expert"])) == positions - skipped

    return ExpertLayer((x,), 8, share, uncut, positions, sin=50.0, atol=1e-6, grad_rtol=1e-3, facts=facts)


# -- the tree, the configuration, the adapter --------------------------------------------------


def _tree_facts(cfg, ours) -> None:
    """At the published widths, with no `lm_head` and the router a subtree."""
    assert set(ours) == {"embed", "final_norm", "layers"} and ours["embed"].shape == (131_136, 2048)
    assert set(ours["layers"]["router"]) == {"down", "down_bias", "carry", "norm", "w1", "b1", "w2", "b2", "w3"}
    assert ours["layers"]["router"]["w3"].shape == (4, 256, 17) and ours["layers"]["w_gate"].shape == (4, 8, 2048, 2048)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(ours))
    assert n_params == BENCH.flops("cca_moe_lm").total_params(PUBLISHED) == 696_250_376


def _published_facts(cfg, _) -> None:
    kind = cfg.layers[0]
    assert (cfg.d_model, cfg.n_layers, cfg.n_kv_heads, cfg.d_head, cfg.d_ff) == (2048, 4, 2, 128, 2048)
    assert all(k == kind for k in cfg.layers) and (kind.mixer, kind.n_heads, kind.rotary_fraction) == ("cca", 8, 0.5)
    assert kind.rope_theta == 5e6 and kind.sparse and kind.window is None
    assert (cfg.moe_experts, cfg.n_router_outputs, cfg.moe_held, cfg.moe_top_k) == (16, 17, (0, 8), 1)
    assert cfg.moe_router_state == 256 and cfg.moe_skip and cfg.scaled_merge and cfg.tied_head
    assert cfg.moe_score == "softmax" and not cfg.moe_norm_topk and cfg.moe_aux_coef == 0.0
    assert PROGRAM.router_bias(PUBLISHED).shape == (4, 17)
    # every number of the catalog's row under the same key, the three cuts listed
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert PUBLISHED["published"] == {"num_hidden_layers": 40, "num_experts": 16, "vocab_size": 262_272}
    assert len(PUBLISHED["layer_types"]) == 40 and PUBLISHED["router_hidden_size"] == 256


REFUSALS = [
    ("two_kinds_of_layer", dict(layer_types=["hybrid", "hybrid_sliding", "hybrid", "hybrid"]), "one kind"),
    ("two_experts_a_token", dict(num_experts_per_tok=2), "one expert"),
    ("sliding_window", dict(sliding_window=4096), "no window"),
    ("untied_head", dict(tie_word_embeddings=False), "embedding itself"),
    ("yarn", dict(rope_parameters={"hybrid": dict(partial_rotary_factor=0.5, rope_theta=100.0, rope_type="yarn")}), "rope_type"),
    ("a_kernel_of_4", dict(cca_time1=4), "kernel 2"),
    ("attention_bias", dict(attention_bias=True), "no bias"),
]


# -- the tree without a head leaf through ft_step, a heal's transport and the checkpoint ----


def _tiny() -> Tiny:
    def tree_facts(tree) -> None:
        assert set(tree) == {"embed", "final_norm", "layers"} and isinstance(tree["layers"]["router"], dict)

    def facts(moved, summaries, step, after) -> None:
        assert {"['embed']", "['layers']['cca_conv1']", "['layers']['router']['carry']", "['layers']['attn_merge']"} <= moved
        summary = summaries[-1]
        assert summary["moe_dropped"] == 0 and summary["moe_skipped"] >= 0
        assert summary["moe_skipped"] + summary["moe_rows_held"] <= summary["moe_assignments"] == 4 * 2 * SEQ

    return tiny_of_the_small_model("cca_moe_lm", CONFIG, _batch(0), tree_facts, facts)


ARCH = Architecture(
    name="cca_moe_lm", configs={"share": CONFIG, "two_layers": dict(CONFIG, num_hidden_layers=2)}, sizes=SIZES, seq=SEQ,
    variants=dict(WALKS, as_published={}), leaf_cases=[Case(walk, "share", walk, 3) for walk in WALKS],
    # the loss to 1e-6, every leaf to 2e-5 of its norm (the reference against itself in float64 differs by as much)
    leaf_tolerance=2e-5, loss_tolerance=1e-6, off_start=True, counters=_counters,
    # the reference WITHOUT the value shift, either convolution, the q-k mean or the carried state (which crosses one
    # boundary here): every one moves some leaf by a third of its norm or more, so no limit of `correct` that separates
    # rounding from fp8 lets it through
    pieces=[Piece(piece, "reference", piece) for piece in REFERENCE.LEFT_OUT], pieces_at=("two_layers", 4), piece_floor=0.3,
    chips=[2, 4, 1], expert_layer=_expert_layer,
    published="zaya1-8b", tree_facts=_tree_facts, published_facts=_published_facts,
    refusals=REFUSALS, refusal_config="share", through=("ft_step", "heal", "disk_checkpoint"), tiny=_tiny,
)


# -- the compressed mixer, piece by piece, against loops ---------------------------


def _mixer_weights(rng, H, G, D, E):
    """A layer's mixer weights at their trivial values: the convolutions pass
    their input through, the temperature is one."""
    C = H + G
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    return {
        "wq": normal(E, H * D) / 8, "wk": normal(E, G * D) / 8, "wv": normal(E, G * D) / 8,
        "cca_conv0": jnp.stack([jnp.zeros(C * D), jnp.ones(C * D)]), "cca_bias0": jnp.zeros(C * D),
        "cca_conv1": jnp.stack([jnp.zeros((C, D, D)), jnp.broadcast_to(jnp.eye(D), (C, D, D))], axis=1),
        "cca_bias1": jnp.zeros((C, D)), "cca_temp": jnp.ones(G),
    }, normal


def _loop_qkv(h, w, H, G, D):
    """`_cca_qkv` before RoPE, written out position by position and head by
    head for one sequence h [S, E]: q [H, S, D], k and v [G, S, D]."""
    S, per = h.shape[0], H // G
    q0, k0, v0 = (h @ w[n] for n in ("wq", "wk", "wv"))
    zero = jnp.zeros(D)
    head = lambda a, t, i: a[t, i * D:(i + 1) * D] if t >= 0 else zero  # noqa: E731
    z = lambda t, c: head(q0, t, c) if c < H else head(k0, t, c - H)  # noqa: E731
    taps, b0 = w["cca_conv0"].reshape(2, H + G, D), w["cca_bias0"].reshape(H + G, D)
    z0 = lambda t, c: taps[1, c] * z(t, c) + taps[0, c] * z(t - 1, c) + b0[c] if t >= 0 else zero  # noqa: E731
    z1 = lambda t, c: z0(t, c) @ w["cca_conv1"][c, 1] + z0(t - 1, c) @ w["cca_conv1"][c, 0] + w["cca_bias1"][c]  # noqa: E731
    mu = lambda t, j: 0.5 * (head(q0, t, j) + head(k0, t, j // per))  # noqa: E731
    unit = lambda a: a * D ** 0.5 / jnp.sqrt(jnp.sum(a * a))  # noqa: E731
    q = jnp.stack([jnp.stack([unit(z1(t, j) + mu(t, j)) for t in range(S)]) for j in range(H)])
    k = jnp.stack([jnp.stack([w["cca_temp"][g] * unit(z1(t, H + g) + sum(mu(t, j) for j in range(g * per, (g + 1) * per)) / per)
                              for t in range(S)]) for g in range(G)])
    v = jnp.stack([jnp.stack([head(v0, t if g < G // 2 else t - 1, g) for t in range(S)]) for g in range(G)])
    return q, k, v


def _piece(piece: str, w, normal, H, G, D):
    C = H + G
    if piece == "conv0":
        return dict(w, cca_conv0=normal(2, C * D), cca_bias0=normal(C * D))
    if piece == "conv1":
        return dict(w, cca_conv1=normal(C, 2, D, D) / 4, cca_bias1=normal(C, D))
    if piece == "norm":
        return dict(w, cca_temp=1.0 + 0.5 * normal(G))
    return w  # "shift" and "mean" have no weight of their own: they are in every case


@pytest.mark.parametrize("piece", ["shift", "conv0", "conv1", "mean", "norm"])
def test_a_piece_of_the_compressed_mixer_against_a_written_out_loop(piece) -> None:
    """Forward and the gradient of every weight and of the input.  RoPE is
    taken out by theta 1e30 at fraction 0.5 (angles of zero but for the first
    pair, whose turn a norm-preserving check cannot see: the first pair is
    compared through its squared sum)."""
    H, G, D, E, S = 4, 2, 8, 16, 6
    rng = np.random.default_rng(7)
    w, normal = _mixer_weights(rng, H, G, D, E)
    w = _piece(piece, w, normal, H, G, D)
    h = normal(1, S, E)
    kind = LayerKind("layers", True, H, 1e30, rotary_fraction=0.5, mixer="cca")
    cfg = TransformerConfig(vocab_size=32, d_model=E, n_layers=1, n_heads=H, n_kv_heads=G, head_dim=D, d_ff=8,
                            max_seq=S, dtype=jnp.float32, pattern=(kind,), moe_experts=2, moe_capacity_factor=None)
    positions = jnp.zeros((1, S), jnp.int32)  # position zero everywhere: RoPE is the identity

    @jax.jit
    def program(h, w):
        return tuple(a[0].transpose(1, 0, 2) for a in _cca_qkv(cfg, kind, h, w, positions))  # [S, heads, D] -> the loop's head-major

    @jax.jit
    def loop(h, w):
        return _loop_qkv(h[0], w, H, G, D)

    for got, want in zip(program(h, w), loop(h, w)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
    mix = [normal(H, S, D), normal(G, S, D), normal(G, S, D)]
    scalar = lambda f: lambda h, w: sum(jnp.sum(a * m) for a, m in zip(f(h, w), mix))  # noqa: E731
    got, want = jax.jit(jax.grad(scalar(program), argnums=(0, 1)))(h, w), jax.jit(jax.grad(scalar(loop), argnums=(0, 1)))(h, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
    if piece == "shift":  # the second half of the KV heads reads the position before, the first the position itself
        _, _, v = program(h, w)
        flat = (h[0] @ w["wv"]).reshape(S, G, D)
        np.testing.assert_allclose(np.asarray(v[0]), np.asarray(flat[:, 0]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(v[1, 1:]), np.asarray(flat[:-1, 1]), rtol=1e-6)
        assert not np.any(np.asarray(v[1, 0]))


def test_a_top_one_gate_is_the_probability_and_the_bias_never_enters_it() -> None:
    from torchft_tpu.models.moe import route

    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((1, 32, 9)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(9) * 0.2, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, _, gate, idx = route(None, None, 1, False, score="softmax", bias=bias, logits=logits)
    want = np.argmax(np.asarray(probs + bias), axis=-1)
    assert np.array_equal(np.asarray(idx[..., 0]), want) and np.any(want != np.argmax(np.asarray(probs), axis=-1))
    np.testing.assert_array_equal(np.asarray(gate[..., 0]), np.take_along_axis(np.asarray(probs), want[..., None], -1)[..., 0])
    # the gate's gradient reaches the logits, the bias has none
    g = jax.grad(lambda l, b: jnp.sum(route(None, None, 1, False, score="softmax", bias=b, logits=l)[2]), argnums=(0, 1))(logits, bias)
    assert float(jnp.max(jnp.abs(g[0]))) > 0 and not np.any(np.asarray(g[1]))


# -- the tied head ------------------------------------------------------------------


def test_the_tied_embedding_gradient_is_the_untied_model_s_two_leaves_summed() -> None:
    config = dict(CONFIG, num_hidden_layers=2)
    cfg = PROGRAM.transformer_config(config)
    untied = dataclasses.replace(cfg, tied_head=False)
    params = _weights(6, config)
    bias, batch = jnp.asarray(PROGRAM.router_bias(config)), _batch(6, seq_len=32)
    assert "lm_head" not in params and "lm_head" not in param_axes(cfg) and "lm_head" in param_axes(untied)
    tied = jax.jit(jax.grad(lambda p: loss_and_counters(p, batch, cfg, router_bias=bias)[0]))(params)
    both = jax.jit(jax.grad(lambda p: loss_and_counters(p, batch, untied, router_bias=bias)[0]))(
        dict(params, lm_head=params["embed"].T))
    np.testing.assert_allclose(np.asarray(tied["embed"]), np.asarray(both["embed"] + both["lm_head"].T),
                               rtol=1e-5, atol=1e-8)
    for name in ("attn_merge", "wq", "cca_conv1"):
        np.testing.assert_allclose(np.asarray(tied["layers"][name]), np.asarray(both["layers"][name]), rtol=1e-5, atol=1e-9)


def test_a_pipelined_loss_refuses_a_tied_head() -> None:
    from torchft_tpu.parallel.pipeline import pipeline_loss_fn

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=32, max_seq=8,
                            tied_head=True)
    with pytest.raises(AssertionError, match="untied"):
        pipeline_loss_fn({}, {"tokens": jnp.zeros((2, 8), jnp.int32)}, cfg, None, num_microbatches=1)



# -- the six configurations the benchmark had keep their trees -------------------------


@pytest.mark.parametrize("name", ["internlm2-1.8b", "mistral-7b", "olmoe-1b-7b", "moonlight-16b-a3b",
                                  "keye-vl-2.0-30b-a3b", "laguna-xs.2"])
def test_a_configuration_the_benchmark_had_keeps_its_tree(name) -> None:
    """`init_params` grew a mixer, merges, a router subtree and a tied head:
    for the six configurations that have none of them the tree is the one
    their (unchanged) reference files make — names, shapes, an `lm_head`."""
    config = BENCH.config(name)
    cfg = BENCH.program(config["architecture"]).transformer_config(config)
    ours = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    theirs = jax.eval_shape(lambda: BENCH.reference(config["architecture"]).make_weights(1, config))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs) and "lm_head" in ours
    assert [l.shape for l in jax.tree.leaves(ours)] == [l.shape for l in jax.tree.leaves(theirs)]
    assert not (cfg.tied_head or cfg.scaled_merge or cfg.moe_skip or cfg.moe_router_state)
    # latent attention is a kind's since PR 48: the model's `mla_kv_rank` writes it into every layer's kind
    assert all(kind.mixer == ("mla" if cfg.mla_kv_rank else "attention") for kind in cfg.layers)
