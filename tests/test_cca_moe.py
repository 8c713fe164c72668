"""ZAYA1-shaped models (attention inside a compressed latent, a router that is
an MLP with a state carried from layer to layer, one expert a token or none,
learned residual merges, a head that is the embedding) through the program, on
the CPU at small sizes.

The program (``models/transformer.py`` with a ``LayerKind`` whose mixer is
"cca"; ``models/moe.py``'s ``state_router_logits`` and ``skip``) against the
benchmark's plain float32 reference (``benchmark/reference/cca_moe_lm.py``,
which shares no code with it) on seeded random weights; each piece of the
compressed mixer against a written-out loop; the shares of an expert-parallel
layer against the uncut layer; the tied head against the untied model's two
leaves; the counters; the adapter's refusals; and the tree without ``lm_head``
through ``ft_step``, a heal's transport and the disk checkpoint.
"""

import dataclasses
import json
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

from benchmark.spec import Benchmark  # noqa: E402
from torchft_tpu.models import LayerKind, TransformerConfig, init_params  # noqa: E402
from torchft_tpu.models.moe import moe_layer  # noqa: E402
from torchft_tpu.models.transformer import _cca_qkv, loss_and_counters, param_axes  # noqa: E402
from torchft_tpu.parallel import TrainStep, ft_init_mesh  # noqa: E402

BENCH = Benchmark(ROOT)
REFERENCE = BENCH.reference("cca_moe_lm")
PROGRAM = BENCH.program("cca_moe_lm")
PUBLISHED = BENCH.config("zaya1-8b")

SEQ = 64
# The cut's 4 layers in small, float32 throughout: 8 query heads on 2 KV heads
# of 16, RoPE on half a head, a router state of 16 over 8 experts and the skip
# choice, of which this chip holds experts 4-7; the carried state crosses three
# boundaries.  `layer_types` keeps a longer list: the first four entries count.
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


def _weights(seed: int, config=CONFIG):
    """The reference's weights with every leaf moved off its start (a tenth of
    its spread, or 0.1 where it starts constant): biases, merges, temperature
    and the carried state's weight then take part in every product."""
    weights = REFERENCE.make_weights(seed, config)
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda l: l + 0.1 * (float(jnp.std(l)) or 1.0) * jnp.asarray(rng.standard_normal(l.shape), jnp.float32), weights)


def _batch(seed: int, vocab: int = 300, seq_len: int = SEQ, sequences: int = 2):
    tokens = np.random.default_rng(seed).integers(0, vocab, size=(sequences, seq_len)).astype(np.int32)
    return {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(np.roll(tokens, -1, axis=1))}


def _program(config, weights, batch):
    return jax.jit(jax.value_and_grad(PROGRAM.loss(config), has_aux=True))(weights, batch)


WALKS = {
    "static_loop": dict(remat=False, scan_unroll=8),
    "scan": dict(remat=False, scan_unroll=1),
    "remat": dict(remat=True, scan_unroll=8),
    "remat_that_keeps_attention": dict(remat=True, remat_keeps_attention=True, scan_unroll=8),
    "remat_in_the_scan": dict(remat=True, remat_keeps_attention=True, scan_unroll=1),
}


@pytest.mark.parametrize("walk", list(WALKS))
def test_loss_and_every_gradient_leaf_against_the_plain_reference(walk) -> None:
    """Float32 on both sides, so what differs is the order of sums: the loss to
    1e-6, every leaf's gradient to 2e-5 of its norm (the reference against
    itself in float64 differs by as much; a piece of the mathematics left out
    reads 0.5 and more, `test_a_model_without_a_piece_is_another_model`)."""
    config = dict(CONFIG, program=dict(CONFIG["program"], **WALKS[walk]))
    weights, batch = _weights(3), _batch(3)
    (loss, counters), grads = _program(config, weights, batch)
    want_loss, want = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], config)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for path, got in jax.tree_util.tree_leaves_with_path(grads):
        ref = np.asarray(jax.tree_util.tree_reduce(lambda a, k: a[k.key], path, want), np.float64)
        assert np.linalg.norm(np.asarray(got, np.float64) - ref) <= 2e-5 * np.linalg.norm(ref), jax.tree_util.keystr(path)
    # a biased choice that takes no expert in some position, an expert held here in some, one held elsewhere in some
    positions = 4 * 2 * SEQ
    assert 0 < int(counters["moe_skipped"]) < positions and int(counters["moe_dropped"]) == 0
    assert 0 < int(counters["moe_rows_held"]) < positions - int(counters["moe_skipped"])


@pytest.mark.parametrize("piece", REFERENCE.LEFT_OUT)
def test_a_model_without_a_piece_is_another_model(piece) -> None:
    """The reference computed WITHOUT the value shift, either convolution, the
    q-k mean or the carried state, in the program's place: every one moves the
    gradients by a third of their norm or more, so no limit of `correct` that
    separates rounding from fp8 lets it through."""
    from benchmark import compare

    config = dict(CONFIG, num_hidden_layers=2)  # the carried state crosses one boundary
    weights, batch = _weights(4, config), _batch(4, sequences=1, seq_len=32)
    _, want = REFERENCE.one_sequence_fn(config)(weights, batch["tokens"][0], batch["targets"][0])
    _, got = REFERENCE.one_sequence_fn(config, "float32", piece)(weights, batch["tokens"][0], batch["targets"][0])
    indices = compare.sample_indices(4, weights)
    rel, _ = compare.grad_rel(compare.sample(got, indices), compare.sample(want, indices))
    assert rel > 0.3, (piece, rel)


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

    def program(h, w):
        return tuple(a[0] for a in _cca_qkv(cfg, kind, h, w, positions))

    def loop(h, w):
        return _loop_qkv(h[0], w, H, G, D)

    for got, want in zip(program(h, w), loop(h, w)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
    mix = [normal(H, S, D), normal(G, S, D), normal(G, S, D)]
    scalar = lambda f: lambda h, w: sum(jnp.sum(a * m) for a, m in zip(f(h, w), mix))  # noqa: E731
    got, want = jax.grad(scalar(program), argnums=(0, 1))(h, w), jax.grad(scalar(loop), argnums=(0, 1))(h, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
    if piece == "shift":  # the second half of the KV heads reads the position before, the first the position itself
        _, _, v = program(h, w)
        flat = (h[0] @ w["wv"]).reshape(S, G, D)
        np.testing.assert_allclose(np.asarray(v[0]), np.asarray(flat[:, 0]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(v[1, 1:]), np.asarray(flat[:-1, 1]), rtol=1e-6)
        assert not np.any(np.asarray(v[1, 0]))


# -- the shares of an expert-parallel layer ------------------------------------------


def _expert_layer(seed: int):
    weights = _weights(seed, dict(CONFIG, num_experts=8, expert_parallel=None))
    w = jax.tree.map(lambda leaf: leaf[1], weights["layers"])
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, SEQ, 64)), jnp.float32) * 0.05
    state = jnp.asarray(rng.standard_normal((2, SEQ, 16)), jnp.float32)
    bias = jnp.asarray(REFERENCE.router_bias(CONFIG)[1])
    return x, state, w, bias


def _share(x, state, w, bias, first, count):
    """The program's routed part of one expert sublayer for the experts
    ``first ... first + count - 1`` of 8 (the skip choice is the ninth output),
    on its normed input."""
    from torchft_tpu.ops.rmsnorm import rms_norm

    u = rms_norm(x, w["mlp_norm"], 1e-5)
    held = slice(first, first + count)
    return moe_layer(u, w["router"], w["w_gate"][held], w["w_up"][held], w["w_down"][held], top_k=1,
                     capacity_factor=None, norm_topk=False, score="softmax", route_bias=bias, router_state=state,
                     skip=True, rms_eps=1e-5, held_first=first, dtype=jnp.float32)


@pytest.mark.parametrize("chips", [2, 4, 1])
def test_the_shares_add_up_to_the_uncut_layer(chips) -> None:
    """What every chip of an expert-parallel layer computes of the routed
    experts (2 chips: 4 of 8 each; the skip choice adds nothing on any),
    summed over the chips, is what the uncut plain reference gives for the
    whole sublayer before its merge — values, the carried state and the
    gradient of the input — and the counters add up to the positions."""
    x, state, w, bias = _expert_layer(9)
    count = 8 // chips
    s = REFERENCE.sizes_of(dict(CONFIG, num_experts=8, expert_parallel=None))
    assert (s["held"], s["experts"], s["first"]) == (8, 8, 0)
    plain = dict(w, mlp_merge=jnp.asarray([[1.0], [0.0], [1.0], [0.0]]) * jnp.ones((4, 64)))  # x + y: y = merged - x

    def uncut(x):
        return jnp.stack([REFERENCE._experts(seq, plain, st, bias, s, "float32")[0] - seq for seq, st in zip(x, state)])

    def summed(x):
        return sum(_share(x, state, w, bias, r * count, count)[0] for r in range(chips))

    with jax.default_matmul_precision("highest"):
        want, got = jax.jit(uncut)(x), jax.jit(summed)(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6)
        dwant = jax.jit(jax.grad(lambda x: jnp.sum(jnp.sin(50 * uncut(x)))))(x)
        dgot = jax.jit(jax.grad(lambda x: jnp.sum(jnp.sin(50 * summed(x)))))(x)
        np.testing.assert_allclose(np.asarray(dgot), np.asarray(dwant), rtol=1e-3, atol=1e-6)
        stats = [_share(x, state, w, bias, r * count, count)[1] for r in range(chips)]
        want_state = jnp.stack([REFERENCE._experts(seq, plain, st, bias, s, "float32")[1] for seq, st in zip(x, state)])
    np.testing.assert_allclose(np.asarray(stats[0]["router_state"]), np.asarray(want_state), rtol=1e-5, atol=1e-6)
    # skipped + held here + held on the other chips = positions, on every chip; nothing is ever dropped
    positions = 2 * SEQ
    skipped = int(stats[0]["skipped"])
    assert 0 < skipped < positions and all(int(st["skipped"]) == skipped for st in stats)
    assert sum(int(st["rows_held"]) for st in stats) + skipped == positions == int(stats[0]["assignments"])
    assert all(int(st["dropped"]) == 0 and st["tokens_per_expert"].shape == (8,) for st in stats)
    assert int(jnp.sum(stats[0]["tokens_per_expert"])) == positions - skipped


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


def test_the_tree_is_the_reference_s() -> None:
    """Leaf names and shapes of `init_params` are those of the weights the
    benchmark makes, at the published widths, with no `lm_head` and the
    router a subtree."""
    cfg = PROGRAM.transformer_config(PUBLISHED)
    ours = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    theirs = jax.eval_shape(lambda: REFERENCE.make_weights(1, PUBLISHED))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [l.shape for l in jax.tree.leaves(ours)] == [l.shape for l in jax.tree.leaves(theirs)]
    assert set(ours) == {"embed", "final_norm", "layers"} and ours["embed"].shape == (131_136, 2048)
    assert set(ours["layers"]["router"]) == {"down", "down_bias", "carry", "norm", "w1", "b1", "w2", "b2", "w3"}
    assert ours["layers"]["router"]["w3"].shape == (4, 256, 17) and ours["layers"]["w_gate"].shape == (4, 8, 2048, 2048)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(ours))
    assert n_params == BENCH.flops("cca_moe_lm").total_params(PUBLISHED) == 696_250_376
    axes = jax.tree.leaves(param_axes(cfg), is_leaf=lambda a: isinstance(a, tuple))
    assert [len(a) for a in axes] == [l.ndim for l in jax.tree.leaves(ours)]  # dicts flatten by sorted key, both


def test_the_published_configuration_is_handed_over_whole() -> None:
    cfg = PROGRAM.transformer_config(PUBLISHED)
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


@pytest.mark.parametrize("change,message", [
    (dict(layer_types=["hybrid", "hybrid_sliding", "hybrid", "hybrid"]), "one kind"),
    (dict(num_experts_per_tok=2), "one expert"),
    (dict(sliding_window=4096), "no window"),
    (dict(tie_word_embeddings=False), "embedding itself"),
    (dict(rope_parameters={"hybrid": dict(partial_rotary_factor=0.5, rope_theta=100.0, rope_type="yarn")}), "rope_type"),
    (dict(cca_time1=4), "kernel 2"),
    (dict(attention_bias=True), "no bias"),
])
def test_the_adapter_raises_on_what_it_does_not_honour(change, message) -> None:
    with pytest.raises(ValueError, match=message):
        PROGRAM.transformer_config(dict(CONFIG, **change))


def test_a_pipelined_loss_refuses_a_tied_head() -> None:
    from torchft_tpu.parallel.pipeline import pipeline_loss_fn

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=32, max_seq=8,
                            tied_head=True)
    with pytest.raises(AssertionError, match="untied"):
        pipeline_loss_fn({}, {"tokens": jnp.zeros((2, 8), jnp.int32)}, cfg, None, num_microbatches=1)


# -- the tree without a head leaf through ft_step, a heal's transport and the checkpoint ----


def _records(path, event):
    with open(path, encoding="utf-8") as f:
        return [r for r in map(json.loads, f) if r.get("event") == event]


TINY = dataclasses.replace(PROGRAM.transformer_config(CONFIG), remat=True, remat_keeps_attention=True, scan_unroll=1)


@pytest.mark.parametrize("through", ["ft_step", "heal", "disk_checkpoint"])
def test_a_tied_state_carrying_tree_goes_through(through, store, tmp_path, monkeypatch) -> None:  # noqa: F811
    params = init_params(jax.random.PRNGKey(5), TINY)
    assert set(params) == {"embed", "final_norm", "layers"} and isinstance(params["layers"]["router"], dict)
    leaves = jax.tree.leaves(params)
    if through == "ft_step":
        path = tmp_path / "stream.jsonl"
        monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
        client = MagicMock()
        client._quorum.return_value = make_quorum()
        client.should_commit.return_value = True
        manager, _, _ = make_manager(store, client_mock=client)
        ftmesh = ft_init_mesh({"data": 1}, devices=jax.devices()[:1])
        ftmesh.manager = manager
        bias = jnp.asarray(PROGRAM.router_bias(CONFIG))
        step = TrainStep(ftmesh, optax.adamw(1e-3), lambda p, b: loss_and_counters(p, b, TINY, router_bias=bias),
                         loss_has_counters=True, overlap_commit=False)
        opt = step.init_opt_state(params)
        batch = _batch(0)
        before = jax.tree.map(np.asarray, params)  # `ft_step` donates its arguments
        try:
            for _ in range(2):
                manager.start_quorum()
                params, opt, loss, committed = step.ft_step(params, opt, batch)
                assert committed and np.isfinite(float(loss))
        finally:
            manager.shutdown()
        assert jax.tree.structure(params) == jax.tree.structure(before)
        moved = {jax.tree_util.keystr(p) for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                                                              jax.tree.leaves(before)) if not np.array_equal(np.asarray(a), b)}
        assert {"['embed']", "['layers']['cca_conv1']", "['layers']['router']['carry']", "['layers']['attn_merge']"} <= moved
        summary = _records(path, "step_summary")[-1]
        assert summary["moe_dropped"] == 0 and summary["moe_skipped"] >= 0
        assert summary["moe_skipped"] + summary["moe_rows_held"] <= summary["moe_assignments"] == 4 * 2 * SEQ
    elif through == "heal":
        from torchft_tpu.checkpointing.http_transport import HTTPTransport

        donor, healer = HTTPTransport(timeout=30.0), HTTPTransport(timeout=30.0)
        try:
            donor.send_checkpoint([1], 7, {"params": params}, 30.0)
            back = healer.recv_checkpoint(0, donor.metadata(), 7, 30.0)["params"]
        finally:
            donor.shutdown()
            healer.shutdown()
        assert jax.tree.structure(back) == jax.tree.structure(params)
        assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(jax.tree.leaves(back), leaves))
    else:
        from torchft_tpu.checkpointing.disk import DiskCheckpointer
        from torchft_tpu.ddp import plan_buckets

        buckets = plan_buckets([(l.shape, l.dtype) for l in leaves], 1 << 14)
        assert sorted(i for b in buckets for i in b.indices) == list(range(len(leaves))) and len(buckets) > 2
        ckpt = DiskCheckpointer(str(tmp_path))
        try:
            ckpt.save(4, {"params": params})
            ckpt.wait()
            back = ckpt.restore(4)["params"]
        finally:
            ckpt.shutdown()
        assert jax.tree.structure(back) == jax.tree.structure(params)
        assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(jax.tree.leaves(back), leaves))


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
