"""Kimi-Linear-shaped models (Kimi Delta Attention 3 : 1 with unrotated latent
attention, a leading dense layer, bias-corrected sigmoid routing beside a
shared expert, three layer stacks of which two are sparse) through the program,
on the CPU at small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/kda_mla_moe_lm.py``).  What only this
architecture has is tested here: each piece of ``kda_mix`` against a written-out
loop, and the latent kind against the model-level latent attention.
"""

import contextlib
import dataclasses

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
from torchft_tpu.models.moe import moe_layer
from torchft_tpu.models.attention import _mla_qkv
from torchft_tpu.models.kda import _causal_conv, _kda_mixer, _l2
from torchft_tpu.models.transformer import loss_and_counters
from torchft_tpu.ops import delta_attention

REFERENCE = BENCH.reference("kda_mla_moe_lm")
PROGRAM = BENCH.program("kda_mla_moe_lm")
PUBLISHED = BENCH.config("kimi-linear-48b-a3b")

SEQ = 40
SIZES = """40 positions under chunks of 16 (`_small_chunks`: the model's chunk of 64 would be one chunk): two chunks and a
half, so the scan crosses a chunk's edge twice and ends inside one.  The cut's layers 1-5 (KDA dense, KDA, KDA, latent,
KDA): every kind, a stack of one dense layer, a sparse stack in two runs around the latent layer.  KDA at 2 heads of 16
under kernel-4 convolutions, latent attention at 2 heads of 16 + 8 / 16 over rank 32, 8 router outputs of which this
chip holds experts 2-5, 2 a token, a shared expert.  The lists keep the published 27 entries.  Float32 throughout."""
CONFIG = dict(
    PUBLISHED, vocab_size=300, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_attention_heads=2, num_key_value_heads=2,
    num_experts=4, num_experts_per_token=2, model_max_length=128,
    linear_attn_config=dict(PUBLISHED["linear_attn_config"], head_dim=16, num_heads=2),
    expert_parallel=dict(chips=2, rank=0, router_outputs=8, first_expert_held=2),
    router_bias=dict(seed=5, scale=0.05),
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


WALKS = {
    "static_loop": dict(remat=False, scan_unroll=8),
    "scan": dict(remat=False, scan_unroll=1),
    "remat": dict(remat=True, scan_unroll=8),
    "remat_that_keeps_attention": dict(remat=True, remat_keeps_attention=True, scan_unroll=8),
    "remat_in_the_scan": dict(remat=True, remat_keeps_attention=True, scan_unroll=1),
}
STACKS = ("kda_dense", "kda_layers", "mla_layers", "embed", "final_norm", "lm_head")


def _counters(counters, config) -> None:
    assert int(counters["moe_dropped"]) == 0 and int(counters["moe_assignments"]) == 4 * 2 * 2 * SEQ
    assert 0 < int(counters["moe_rows_held"]) < int(counters["moe_assignments"])
    assert 0.0 < float(counters["kda_alpha_mean"]) < 1.0


# -- a model without a piece is another model: the program without the decay (alpha = 1), the beta k k^T term, the
# convolutions' earlier taps, the q / k norm or the output gate, or with its latent layer rotated, is not the reference's


def _without(piece):
    import torchft_tpu.models.kda as model

    def how(cfg, weights):
        changes, kda = [], delta_attention.kda
        if piece == "decay":
            changes = [(delta_attention, "kda", lambda q, k, v, g, beta, **kw: kda(q, k, v, jnp.zeros_like(g), beta, **kw))]
        elif piece == "delta_term":  # S_t = Diag(alpha) S_{t-1} + beta k v^T: gated linear attention
            def plain(q, k, v, g, beta, **kw):
                def step(state, xs):
                    qt, kt, vt, gt, bt = xs
                    state = state * jnp.exp(gt)[..., None] + (bt[..., None] * kt)[..., None] * vt[..., None, :]
                    return state, jnp.einsum("bhk,bhkv->bhv", qt, state)
                xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta))
                return jnp.moveaxis(jax.lax.scan(step, jnp.zeros(q.shape[:2] + (q.shape[3], v.shape[3])), xs)[1], 0, 2)
            changes = [(delta_attention, "kda", plain)]
        elif piece == "convolution":
            changes = [(model, "_causal_conv", lambda z, taps: taps[-1] * z)]
        elif piece == "qk_norm":
            changes = [(model, "_l2", lambda x: x)]
        elif piece == "output_gate":  # a gate that is always 1/2: the bias pushed far out of the weights' reach is not it
            weights = dict(weights)
            for stack in ("kda_dense", "kda_layers"):
                weights[stack] = dict(weights[stack], kda_g_down=jnp.zeros_like(weights[stack]["kda_g_down"]),
                                      kda_g_bias=jnp.zeros_like(weights[stack]["kda_g_bias"]))
        elif piece == "rotated_latent_layer":
            cfg = dataclasses.replace(cfg, pattern=tuple(
                dataclasses.replace(kind, rotary_fraction=1.0) if kind.mixer == "mla" else kind for kind in cfg.pattern))
        return patched(*changes), cfg, weights

    return Piece(piece, "program", how)


PIECES = ("decay", "delta_term", "convolution", "qk_norm", "output_gate", "rotated_latent_layer")


# -- the shares add up: 32 chips hold one of 32 experts each -----------------------------------------


def _expert_layer() -> ExpertLayer:
    hidden, ffn, experts, k = 32, 16, 32, 4
    rng = np.random.default_rng(4)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)  # noqa: E731
    w = dict(router=draw(hidden, experts), w_gate=draw(experts, hidden, ffn), w_up=draw(experts, hidden, ffn),
             w_down=draw(experts, ffn, hidden), shared_gate=draw(hidden, ffn), shared_up=draw(hidden, ffn),
             shared_down=draw(ffn, hidden))
    bias = jnp.asarray(rng.standard_normal(experts) * 0.05, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 24, hidden)), jnp.float32)
    s = dict(experts=experts, held=experts, first=0, top_k=k, route_scale=2.446, aux_coef=0.0)

    def uncut(x):
        return jnp.stack([REFERENCE._experts(seq, w, bias, s, "float32")[0] for seq in x]), None

    def share(first, count, with_shared, x):
        held = slice(first, first + count)
        return moe_layer(x, w["router"], w["w_gate"][held], w["w_up"][held], w["w_down"][held], top_k=k,
                         capacity_factor=None, norm_topk=True, score="sigmoid", route_bias=bias, route_scale=2.446,
                         held_first=first, shared=(w["shared_gate"], w["shared_up"], w["shared_down"]) if with_shared else None,
                         dtype=jnp.float32)

    return ExpertLayer((x,), experts, share, uncut, 2 * 24 * k, sin=5.0, grad_rtol=1e-3, shared=True)


# -- the tree, the configuration, the adapter --------------------------------------------------


def _tree_facts(cfg, ours) -> None:
    """Three stacks, two of them sparse; `A_log` and `dt_bias` are float32 leaves
    of 32 and 4,096 elements a layer, and the decay's initialisation is the
    published one on both sides."""
    assert [(s, k.mixer, k.sparse, n) for s, (k, n) in cfg.stacks.items()] == [
        ("kda_dense", "kda", False, 1), ("kda_layers", "kda", True, 3), ("mla_layers", "mla", True, 1)]
    assert [kind.mixer for kind in cfg.layers] == ["kda", "kda", "kda", "mla", "kda"]
    theirs = jax.eval_shape(lambda: REFERENCE.make_weights(1, PUBLISHED))
    assert [l.dtype for l in jax.tree.leaves(ours)] == [l.dtype for l in jax.tree.leaves(theirs)]
    assert ours["kda_layers"]["A_log"].shape == (3, 32) and ours["kda_layers"]["dt_bias"].shape == (3, 4096)
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(ours)) == 602_449_792 == BENCH.flops("kda_mla_moe_lm").total_params(PUBLISHED)
    small = PROGRAM.transformer_config(CONFIG)
    for tree in (init_params(jax.random.PRNGKey(2), small), REFERENCE.make_weights(2, CONFIG)):
        rate, steps = np.exp(np.asarray(tree["kda_layers"]["A_log"])), np.asarray(jax.nn.softplus(tree["kda_layers"]["dt_bias"]))
        assert 1.0 <= rate.min() and rate.max() <= 16.0 and 0.001 * 0.999 <= steps.min() and steps.max() <= 0.1 * 1.001


def _published_facts(cfg, _) -> None:
    assert (cfg.d_model, cfg.dense_d_ff, cfg.d_ff, cfg.vocab_size) == (2304, 9216, 1024, 20480)
    assert (cfg.kda_head_dim, cfg.kda_conv, cfg.mla_kv_rank, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim) == (128, 4, 512, 128, 64, 128)
    assert all(kind.n_heads == 32 and kind.rotary_fraction == 0.0 for kind in cfg.pattern)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_route_scale, cfg.moe_shared_experts) == (256, 8, (0, 8), 2.446, 1)
    assert cfg.moe_score == "sigmoid" and cfg.moe_norm_topk and cfg.rms_eps == 1e-5 and not cfg.tied_head
    assert PROGRAM.router_bias(PUBLISHED).shape == (4, 256)
    # every number of the catalog's row under the same key, the three cuts listed
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert PUBLISHED["published"] == {"num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163_840}
    linear = PUBLISHED["linear_attn_config"]
    assert len(linear["kda_layers"]) == 20 and linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert set(PROGRAM.kernel_names()) >= {"attn", "ce", "gmm", "kda"} and PROGRAM.kernel_names()["kda"]("x.tpuft_kda_bwd.3")


REFUSALS = [
    ("q_lora_rank", dict(q_lora_rank=1536), "low-rank query"),
    ("num_expert_group", dict(num_expert_group=8), "one group"),
    ("topk_group", dict(topk_group=4), "one group"),
    ("a_softmax_router", dict(moe_router_activation_func="softmax"), "sigmoid"),
    ("a_rotated_latent_layer", dict(mla_use_nope=False), "without rotation"),
    ("extra_prediction_layers", dict(num_nextn_predict_layers=1), "extra prediction"),
    ("tied_head", dict(tie_word_embeddings=True), "untied"),
    ("a_layer_in_neither_list", dict(linear_attn_config=dict(CONFIG["linear_attn_config"], kda_layers=[1, 2, 5])), "layer 3 is in neither"),
    ("a_layer_in_both_lists", dict(linear_attn_config=dict(CONFIG["linear_attn_config"], full_attn_layers=[3, 4])), "layer 3 is in neither or both"),
]


# -- the three-stack tree through ft_step, a heal's transport and the checkpoint -----------------


def _tiny() -> Tiny:
    def tree_facts(tree) -> None:
        assert set(tree) == {"embed", "final_norm", "lm_head", "kda_dense", "kda_layers", "mla_layers"}
        assert tree["kda_layers"]["A_log"].shape == (3, 2) and tree["kda_layers"]["A_log"].dtype == jnp.float32

    def facts(moved, summaries, step, after) -> None:
        assert {"['embed']", "['kda_dense']['A_log']", "['kda_layers']['dt_bias']", "['kda_layers']['kda_conv_k']",
                "['mla_layers']['wkv_b']", "['kda_layers']['router']", "['mla_layers']['router']"} <= moved
        summary = summaries[-1]
        assert summary["moe_dropped"] == 0 and 0 < summary["moe_rows_held"] < summary["moe_assignments"] == 4 * 2 * 2 * SEQ
        assert 0.0 < summary["kda_alpha_mean"] < 1.0

    return tiny_of_the_small_model("kda_mla_moe_lm", CONFIG, _batch(0), tree_facts, facts)


ARCH = Architecture(
    name="kda_mla_moe_lm", configs={"share": CONFIG}, sizes=SIZES, seq=SEQ, variants=dict(WALKS, as_published={}),
    leaf_cases=[Case(f"{walk}-{stack}", "share", walk, 1, stack=stack) for walk in WALKS for stack in STACKS],
    # what differs is the order of sums — the chunk form against the recurrence position by position, the grouped experts
    # against the masked loop: every leaf to 2e-4 of its largest entry (the deepest leaves sum 80 positions through five
    # layers in another order); a missing term is 1e-2 or more (the pieces)
    leaf_error="max", leaf_tolerance=2e-4, loss_tolerance=1e-6, off_start=True, counters=_counters, stacks=STACKS,
    tracing=_chunks_of_16,  # the fixture's patch does not reach a cached call made once
    pieces=[_without(piece) for piece in PIECES], pieces_at=("share", 2), piece_floor=2e-2,
    chips=[32, 8, 1], expert_layer=_expert_layer,
    published="kimi-linear-48b-a3b", tree_facts=_tree_facts, published_facts=_published_facts,
    refusals=REFUSALS, refusal_config="share", through=("ft_step", "heal", "disk_checkpoint"), tiny=_tiny,
)


# -- the pieces of kda_mix against written-out loops ----------------------------------------


def test_the_convolution_s_shifts_at_the_sequence_s_start() -> None:
    """c_t = sum_i w_i z_{t-3+i} with zeros before the first position: the
    first three outputs see one, two and three positions."""
    rng = np.random.default_rng(0)
    z, taps = rng.standard_normal((2, 9, 5)).astype(np.float32), rng.standard_normal((4, 5)).astype(np.float32)
    want = np.zeros_like(z)
    for b in range(2):
        for t in range(9):
            for i in range(4):
                if t - 3 + i >= 0:
                    want[b, t] += taps[i] * z[b, t - 3 + i]
    np.testing.assert_allclose(np.asarray(_causal_conv(jnp.asarray(z), jnp.asarray(taps))), want, rtol=1e-6, atol=1e-6)
    assert np.allclose(want[:, 0], taps[3] * z[:, 0])  # the first position sees itself alone


def test_the_l2_norm_is_a_head_s() -> None:
    x = np.random.default_rng(1).standard_normal((2, 5, 3, 8)).astype(np.float32)
    want = x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(_l2(jnp.asarray(x))), want, rtol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(want, axis=-1), 1.0, rtol=1e-5)


def test_the_whole_mixer_against_a_written_out_loop() -> None:
    """`_kda_mixer` — projections, convolutions with SiLU, norms, decay, beta,
    the recurrence, the gated head norm — against numpy loops over positions,
    heads and channels at 11 positions x 2 heads of 4."""
    cfg = TransformerConfig(vocab_size=32, d_model=12, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=16, dtype=jnp.float32,
                            kda_head_dim=4, rms_eps=1e-5, pattern=(LayerKind("layers", False, 2, 1e4, mixer="kda"),))
    w = jax.tree.map(lambda a: a[0], init_params(jax.random.PRNGKey(3), cfg)["layers"])
    rng = np.random.default_rng(3)
    w = dict(w, kda_g_bias=jnp.asarray(rng.standard_normal(8), jnp.float32), kda_norm=jnp.asarray(1 + 0.3 * rng.standard_normal(4), jnp.float32))
    h = rng.standard_normal((1, 11, 12)).astype(np.float32)
    got, alpha = _kda_mixer(cfg, cfg.pattern[0], None, jnp.asarray(h), w)
    n = {k: np.asarray(v, np.float64) for k, v in w.items()}
    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731
    S, H, D = 11, 2, 4
    proj = {name: h[0].astype(np.float64) @ n[name] for name in ("wq", "wk", "wv")}
    conv = {}
    for name, taps in (("wq", "kda_conv_q"), ("wk", "kda_conv_k"), ("wv", "kda_conv_v")):
        out = np.zeros((S, H * D))
        for t in range(S):
            for i in range(4):
                if t - 3 + i >= 0:
                    out[t] += n[taps][i] * proj[name][t - 3 + i]
        conv[name] = silu(out).reshape(S, H, D)
    a = h[0] @ n["kda_a_down"] @ n["kda_a_up"] + n["dt_bias"]
    g = (-np.repeat(np.exp(n["A_log"]), D) * np.log1p(np.exp(a))).reshape(S, H, D)
    beta = 1 / (1 + np.exp(-(h[0] @ n["kda_beta"])))
    gate = 1 / (1 + np.exp(-(h[0] @ n["kda_g_down"] @ n["kda_g_up"] + n["kda_g_bias"])))
    want = np.zeros((S, H * D))
    for head in range(H):
        state = np.zeros((D, D))
        for t in range(S):
            q = conv["wq"][t, head] / np.sqrt((conv["wq"][t, head] ** 2).sum() + 1e-6) * D ** -0.5
            k = conv["wk"][t, head] / np.sqrt((conv["wk"][t, head] ** 2).sum() + 1e-6)
            state = np.exp(g[t, head])[:, None] * state
            state = state + beta[t, head] * np.outer(k, conv["wv"][t, head] - k @ state)
            o = state.T @ q
            o = o / np.sqrt((o * o).mean() + 1e-5) * n["kda_norm"]
            want[t, head * D:(head + 1) * D] = o * gate[t, head * D:(head + 1) * D]
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(alpha), np.exp(g).mean(), rtol=1e-5)



# -- the latent kind --------------------------------------------------------------------------


def test_the_latent_kind_with_rotation_is_the_model_level_latent_attention_bit_for_bit() -> None:
    """Moonlight's path: `mla_kv_rank` on the model writes the latent mixer
    into its one kind; a pattern of that kind, rotated, computes the same bits,
    and without rotation another result."""
    base = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=48, max_seq=32, dtype=jnp.float32,
                remat=False, mla_kv_rank=16, mla_nope_dim=8, mla_rope_dim=4, mla_v_dim=8, rope_theta=5e4)
    model = TransformerConfig(**base)
    assert [kind.mixer for kind in model.layers] == ["mla", "mla"]
    kind = LayerKind("layers", False, 2, 5e4, mixer="mla")
    as_kind = TransformerConfig(**base, pattern=(kind, kind))
    nope = TransformerConfig(**base, pattern=(dataclasses.replace(kind, rotary_fraction=0.0),) * 2)
    params = init_params(jax.random.PRNGKey(0), model)
    assert jax.tree.structure(params) == jax.tree.structure(init_params(jax.random.PRNGKey(0), as_kind))
    batch = _batch(0, vocab=64, seq_len=32)
    losses = [jax.jit(jax.value_and_grad(lambda p, c=c: loss_and_counters(p, batch, c)[0]))(params) for c in (model, as_kind, nope)]
    assert float(losses[0][0]) == float(losses[1][0])
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(jax.tree.leaves(losses[0][1]), jax.tree.leaves(losses[1][1])))
    assert float(losses[2][0]) != float(losses[0][0])
    # without rotation the 64 "rope" columns are content: the key's are the projection's own, every head's alike
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32))
    w = jax.tree.map(lambda a: a[0], params["layers"])
    positions = jnp.broadcast_to(jnp.arange(32), (1, 32))
    q, k, v = _mla_qkv(nope, nope.pattern[0], h, w, positions)
    latent = h @ w["wkv_a"]
    assert np.array_equal(np.asarray(k[:, :, 0, 8:]), np.asarray(latent[..., 16:])) and np.array_equal(np.asarray(k[:, :, 0, 8:]), np.asarray(k[:, :, 1, 8:]))
    assert np.array_equal(np.asarray(q), np.asarray((h @ w["wq"]).reshape(1, 32, 2, 12)))
