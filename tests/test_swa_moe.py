"""Laguna-shaped models (window and full attention mixed, two head counts, a
head gate, sigmoid top-k routing beside a shared expert) through the program,
on the CPU at small sizes.

The program (``models/transformer.py`` with a layer ``pattern``: three kinds of
layer, each under a stack of its own) against the benchmark's plain float32
reference (``benchmark/reference/swa_moe_lm.py``, which shares no code with it)
on seeded random weights; YaRN's frequencies against the closed form and the
half-rotated RoPE against a written-out rotation; the shares of an
expert-parallel layer against the uncut layer; the adapter's refusals; and the
three-kind parameter tree through ``ft_step``, a heal's transport and the disk
checkpoint.
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

from benchmark.spec import Benchmark  # noqa: E402
from torchft_tpu.models import LayerKind, TransformerConfig, init_params  # noqa: E402
from torchft_tpu.models.moe import moe_layer  # noqa: E402
from torchft_tpu.models.transformer import _rope, _rotary, loss_and_counters, param_axes, yarn_frequencies  # noqa: E402
from torchft_tpu.parallel import TrainStep, ft_init_mesh  # noqa: E402

BENCH = Benchmark(ROOT)
REFERENCE = BENCH.reference("swa_moe_lm")
PROGRAM = BENCH.program("swa_moe_lm")
PUBLISHED = BENCH.config("laguna-xs.2")

SEQ, WINDOW = 96, 24
PERIOD = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"]
# The cut's 1 + 4 layers in small, float32 throughout: 6 query heads on full
# layers and 8 on window layers over 2 KV heads of 32, a window of 24 under 96
# positions, YaRN over half a head with a ramp of three pairs, 8 routed experts,
# 2 a token, one shared expert.  The lists keep their published length: the
# first `num_hidden_layers` entries count.
CONFIG = dict(
    architecture="swa_moe_lm", vocab_size=384, hidden_size=128, num_hidden_layers=5, num_attention_heads=6,
    num_attention_heads_per_layer=[6, 8, 8, 8] * 2, num_key_value_heads=2, head_dim=32, intermediate_size=256,
    moe_intermediate_size=64, shared_expert_intermediate_size=64, num_experts=8, num_experts_per_tok=2,
    moe_routed_scaling_factor=2.5, moe_apply_router_weight_on_input=False, gating=True, sliding_window=WINDOW,
    layer_types=PERIOD * 2, mlp_layer_types=["dense"] + ["sparse"] * 7, attention_bias=False,
    tie_word_embeddings=False, rms_norm_eps=1e-6, max_position_embeddings=128, aux_loss_alpha=0.001,
    rope_parameters={
        "full_attention": dict(rope_type="yarn", rope_theta=100.0, factor=4.0, original_max_position_embeddings=32,
                               beta_fast=4.0, beta_slow=1.0, attention_factor=1.14, partial_rotary_factor=0.5),
        "sliding_attention": dict(rope_type="default", rope_theta=1e4, partial_rotary_factor=1.0),
    },
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
    program=dict(remat=False, scan_unroll=8),
)
# The same model as one of the four chips that share each layer holds it:
# experts 2 and 3 of the router's 8.
SHARE = dict(CONFIG, num_experts=2, expert_parallel=dict(chips=4, rank=1, router_outputs=8, first_expert_held=2))
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


def _kinds(cfg, **changes):
    """cfg with every layer kind changed by `changes[stack]` (a dict of fields)."""
    return dataclasses.replace(cfg, pattern=tuple(
        dataclasses.replace(kind, **changes.get(kind.stack, {})) for kind in cfg.pattern))


FULL = ("dense_layers", "layers")
# What the program would compute with one part of the published mathematics
# left out or put in the wrong layers: each has to fail the comparison that
# the whole passes.
OMISSIONS = {
    "as_published": lambda cfg: cfg,
    "window_layers_over_the_whole_triangle": lambda cfg: _kinds(cfg, window_layers=dict(window=None)),
    "full_layers_under_the_window": lambda cfg: _kinds(cfg, **{s: dict(window=WINDOW) for s in FULL}),
    "plain_rope_on_full_layers": lambda cfg: _kinds(cfg, **{s: dict(yarn=None) for s in FULL}),
    "full_layers_rotated_over_the_whole_head": lambda cfg: _kinds(cfg, **{s: dict(rotary_fraction=1.0) for s in FULL}),
    "without_the_attention_factor": lambda cfg: _kinds(cfg, **{
        s: dict(yarn=cfg.stacks[s][0].yarn[:4] + (1.0,)) for s in FULL}),
    "window_layers_at_the_full_layers_theta": lambda cfg: _kinds(cfg, window_layers=dict(rope_theta=100.0)),
    "without_the_scaling_factor": lambda cfg: dataclasses.replace(cfg, moe_route_scale=1.0),
    "without_the_balance_loss": lambda cfg: dataclasses.replace(cfg, moe_aux_coef=0.0),
}


@pytest.mark.parametrize("config", [CONFIG, SHARE], ids=["every_expert_held", "a_share_of_the_experts"])
@pytest.mark.parametrize("omission", list(OMISSIONS))
def test_loss_and_every_gradient_leaf_against_the_plain_reference(omission, config) -> None:
    seed = 11
    cfg = OMISSIONS[omission](PROGRAM.transformer_config(config))
    weights, batch = REFERENCE.make_weights(seed, config), _batch(seed)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p, b: loss_and_counters(p, b, cfg), has_aux=True))(weights, batch)
    want_loss, want = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], config)
    leaf, rel = _worst_leaf(grads, want)
    loss_rel = abs(float(loss) - float(want_loss)) / float(want_loss)
    if omission == "as_published":
        assert rel < LEAF_TOLERANCE and loss_rel < LOSS_TOLERANCE, (leaf, rel, loss_rel)
        assert jax.tree.structure(grads) == jax.tree.structure(weights)
        assert int(counters["moe_dropped"]) == 0
        assert np.asarray(counters["moe_tokens_per_expert"]).sum(axis=1).tolist() == [2 * SEQ * 2] * 4
    else:
        assert rel > 3 * LEAF_TOLERANCE, f"{omission}: the comparison did not see it ({leaf} {rel}, loss {loss_rel})"


@pytest.mark.parametrize("keeps", [False, True], ids=["remat", "remat_that_keeps_attention"])
def test_rematerialised_layers_give_the_gradients_of_the_stored_ones(keeps) -> None:
    """`remat`, with and without both kinds' attention output kept: what is
    recomputed is not computed differently."""
    cfg = PROGRAM.transformer_config(SHARE)
    weights, batch = REFERENCE.make_weights(4, SHARE), _batch(4)

    def grads(cfg):
        return jax.jit(jax.value_and_grad(lambda p, b: loss_and_counters(p, b, cfg)[0]))(weights, batch)

    loss, stored = grads(cfg)
    again_loss, again = grads(dataclasses.replace(cfg, remat=True, remat_keeps_attention=keeps))
    assert float(again_loss) == float(loss)
    leaf, rel = _worst_leaf(again, stored)
    assert rel < 1e-6, (leaf, rel)


def test_the_tree_has_a_stack_a_kind_of_layer() -> None:
    """Three kinds of layer, three stacked subtrees, each at its own head
    count; the program's own initialiser gives the tree the reference's weights
    have, shape for shape, and `param_axes` names every leaf."""
    cfg = PROGRAM.transformer_config(SHARE)
    assert {s: (k.n_heads, k.window, k.sparse, n) for s, (k, n) in cfg.stacks.items()} == {
        "dense_layers": (6, None, False, 1), "window_layers": (8, WINDOW, True, 3), "layers": (6, None, True, 1)}
    own = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    made = jax.eval_shape(lambda: REFERENCE.make_weights(1, SHARE))
    assert jax.tree.structure(own) == jax.tree.structure(made)
    assert [a.shape for a in jax.tree.leaves(own)] == [a.shape for a in jax.tree.leaves(made)]
    assert own["window_layers"]["wq"].shape == (3, 128, 8 * 32) and own["layers"]["wq"].shape == (1, 128, 6 * 32)
    assert own["window_layers"]["attn_gate"].shape == (3, 128, 8) and own["dense_layers"]["w_gate"].shape == (1, 128, 256)
    assert own["layers"]["w_gate"].shape == (1, 2, 128, 64) and own["layers"]["router"].shape == (1, 128, 8)
    axes = param_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))) == \
        jax.tree.structure(jax.tree.map(lambda a: 0, own))


def test_the_published_configuration_is_handed_over_whole() -> None:
    cfg = PROGRAM.transformer_config(PUBLISHED)
    kinds = cfg.layers
    assert [(k.stack, k.n_heads, k.window) for k in kinds] == [
        ("dense_layers", 48, None), ("window_layers", 64, 512), ("window_layers", 64, 512),
        ("window_layers", 64, 512), ("layers", 48, None)]
    assert kinds[0].yarn == (64.0, 4096, 64.0, 1.0, 1.4158883083359672) and kinds[0].rotary_fraction == 0.5
    assert (kinds[0].rope_theta, kinds[1].rope_theta, kinds[1].yarn, kinds[1].rotary_fraction) == (5e5, 1e4, None, 1.0)
    assert (cfg.d_model, cfg.n_kv_heads, cfg.d_head, cfg.d_ff, cfg.dense_d_ff) == (2048, 8, 128, 512, 8192)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_route_scale, cfg.moe_shared_experts) == (
        256, 8, (0, 32), 2.5, 1)
    assert cfg.moe_score == "sigmoid" and cfg.attn_head_gate and cfg.vocab_size == 12544
    flops = BENCH.flops("swa_moe_lm")
    shapes = jax.eval_shape(lambda: REFERENCE.make_weights(1, PUBLISHED))
    assert flops.total_params(PUBLISHED) == sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == 691_623_936
    # every number of the published file that the cut does not name is the catalog's
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert len(PUBLISHED["layer_types"]) == len(PUBLISHED["num_attention_heads_per_layer"]) == 40


@pytest.mark.parametrize("change,message", [
    (dict(layer_types=["full_attention"] * 8), "not the period"),
    (dict(layer_types=["sliding_attention", "full_attention"] * 4), "not the period"),
    (dict(rope_parameters=dict(CONFIG["rope_parameters"], full_attention=dict(
        CONFIG["rope_parameters"]["full_attention"], rope_type="linear"))), "no rope_type"),
    (dict(rope_parameters=dict(CONFIG["rope_parameters"], sliding_attention=dict(
        CONFIG["rope_parameters"]["sliding_attention"], rope_type="llama3"))), "no rope_type"),
    (dict(moe_apply_router_weight_on_input=True), "experts' outputs"),
    (dict(mlp_layer_types=["dense"] * 8), "no stack"),
], ids=["all_full", "period_shifted", "linear_rope", "llama3_rope", "gates_on_the_input", "dense_window_layers"])
def test_the_adapter_raises_on_what_it_does_not_honour(change, message) -> None:
    with pytest.raises(ValueError, match=message):
        PROGRAM.transformer_config(dict(CONFIG, **change))


# -- the two RoPEs ----------------------------------------------------------------


@pytest.mark.parametrize("theta,rot,factor,original,fast,slow", [
    (5e5, 64, 64.0, 4096, 64.0, 1.0), (100.0, 16, 4.0, 32, 4.0, 1.0), (1e4, 128, 8.0, 2048, 32.0, 1.0)],
    ids=["published", "the_small_model", "another"])
def test_yarn_frequencies_against_the_closed_form(theta, rot, factor, original, fast, slow) -> None:
    """Pair i turns by theta**(-2i/rot) below the correction dimension of
    beta_fast, by that over `factor` above beta_slow's, and by the linear blend
    between; float64, the model's and the reference's own."""
    got = yarn_frequencies(theta, rot, factor, original, fast, slow)
    assert got.dtype == np.float64 and got.shape == (rot // 2,)
    low = max(math.floor(rot * math.log(original / (fast * 2 * math.pi)) / (2 * math.log(theta))), 0)
    high = min(math.ceil(rot * math.log(original / (slow * 2 * math.pi)) / (2 * math.log(theta))), rot - 1)
    for i in range(rot // 2):
        plain = theta ** (-2 * i / rot)
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        assert math.isclose(got[i], (1 - r) * plain + r * plain / factor, rel_tol=1e-13), i
    np.testing.assert_allclose(got, REFERENCE.yarn_inv_freq(theta, rot, factor, original, fast, slow), rtol=1e-13)
    if rot == 64:  # the published numbers: pairs 0-5 as they were, 16-31 slowed 64 times
        assert (low, high) == (5, 16)
        plain = theta ** (-2 * np.arange(32) / 64)
        assert (got[:6] == plain[:6]).all() and np.allclose(got[16:], plain[16:] / 64, rtol=1e-15)


@pytest.mark.parametrize("kind", ["full", "window"])
def test_rope_against_a_written_out_rotation(kind) -> None:
    """The published kinds at head_dim 128: a full layer turns the first 64
    columns (pair (i, i + 32)) at YaRN's frequencies with cos and sin times the
    attention factor and passes the other 64; a window layer turns all 128
    (pair (i, i + 64)) at theta 1e4."""
    cfg = PROGRAM.transformer_config(PUBLISHED)
    layer = cfg.layers[0 if kind == "full" else 1]
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1, 5, 2, 128))
    positions = np.asarray([[0, 1, 7, 300, 16000]])
    got = np.asarray(_rotary(jnp.asarray(x, jnp.float32), jnp.asarray(positions, jnp.int32), layer), np.float64)
    want = x.copy()
    if kind == "full":
        freqs, factor, half = yarn_frequencies(5e5, 64, 64.0, 4096, 64.0, 1.0), 1.4158883083359672, 32
    else:
        freqs, factor, half = [1e4 ** (-2 * i / 128) for i in range(64)], 1.0, 64
    for p, position in enumerate(positions[0]):
        for i in range(half):
            c, s = math.cos(position * freqs[i]) * factor, math.sin(position * freqs[i]) * factor
            a, b = x[0, p, :, i], x[0, p, :, i + half]
            want[0, p, :, i], want[0, p, :, i + half] = a * c - b * s, a * s + b * c
    # float32 angles: at position 16,000 the fastest pair's angle carries 1e-3 of absolute rounding
    np.testing.assert_allclose(got, want, atol=5e-3)
    np.testing.assert_allclose(got[0, :3], want[0, :3], atol=1e-5)
    if kind == "full":
        assert (got[..., 64:] == x[..., 64:].astype(np.float32)).all()


def _two_halves(x, positions, inv_freq, factor, rot):
    """The two-halves form `_rope` and `_rotary` had before PR 39, as plain
    `jax.numpy`: the rotated part split at its middle, turned, joined again."""
    half = rot // 2
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * factor)[:, :, None, :]
    sin = (jnp.sin(angles) * factor)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, xf[..., rot:]], axis=-1).astype(x.dtype)


_YARN = (64.0, 4096, 64.0, 1.0, 1.4158883083359672)
# (the call under test, the two-halves form of the same turn, the array's shape): a window layer's 128 columns, a full
# layer's 64 of 128 under YaRN, latent attention's 64 rotary columns (every head's, and the one key all heads share)
# and the indexer's 64-wide heads
_WINDOW_KIND, _FULL_KIND = LayerKind("window_layers", True, 64, 1e4, window=512), LayerKind("layers", True, 48, 5e5, rotary_fraction=0.5, yarn=_YARN)
_ROPE_CALLS = {
    "full_128": (lambda x, p: _rotary(x, p, _WINDOW_KIND),
                 lambda x, p: _two_halves(x, p, 1e4 ** (-jnp.arange(0, 64, dtype=jnp.float32) / 64), 1.0, 128), (2, 24, 3, 128)),
    "yarn_64_of_128": (lambda x, p: _rotary(x, p, _FULL_KIND),
                       lambda x, p: _two_halves(x, p, jnp.asarray(yarn_frequencies(5e5, 64, *_YARN[:4]), jnp.float32), _YARN[4], 64), (2, 24, 3, 128)),
    "latent_64": (lambda x, p: _rope(x, p, 5e4), lambda x, p: _two_halves(x, p, 5e4 ** (-jnp.arange(0, 32, dtype=jnp.float32) / 32), 1.0, 64), (2, 24, 3, 64)),
    "one_key_64": (lambda x, p: _rope(x, p, 1e4), lambda x, p: _two_halves(x, p, 1e4 ** (-jnp.arange(0, 32, dtype=jnp.float32) / 32), 1.0, 64), (2, 24, 1, 64)),
}


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("call", list(_ROPE_CALLS))
def test_rope_is_the_two_halves_form_bit_for_bit(call, dtype) -> None:
    """`_rope` / `_rotary` against the two-halves form: positions up to 32,767
    with an offset a sequence, values and the gradient of a seeded scalar.
    Operation by operation (no jit: each primitive rounds alone) they agree
    to the bit, at either width — the same two float32 products and one sum
    an element.  Under jit XLA:CPU contracts a product into the sum, and which
    of the two depends on the order they are written in (the two-halves form
    differs from ITSELF run operation by operation in the same way), so there
    a rounding of one float32 product is allowed, and a unit in the last
    place of bf16 where that tips the last rounding.  What is not finite in
    a column that passes through stays where it was, and reaches no other
    column."""
    new, old, shape = _ROPE_CALLS[call]
    rng = np.random.default_rng(39)
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    positions = jnp.asarray(np.arange(shape[1])[None, :] * 1423 + np.asarray([[35], [32_767 - 23 * 1423]]), jnp.int32)
    assert int(positions.max()) == 32_767 and int(positions[0, 0]) == 35
    weight = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def scalar(f):
        return lambda x: (f(x, positions).astype(jnp.float32) * weight).sum()

    with jax.disable_jit():
        got, want = new(x, positions), old(x, positions)
        grad_got, grad_want = jax.grad(scalar(new))(x), jax.grad(scalar(old))(x)
    assert got.dtype == want.dtype == dtype and grad_got.dtype == dtype
    assert (_bits(got) == _bits(want)).all() and (_bits(grad_got) == _bits(grad_want)).all()
    assert float(jnp.abs(got.astype(jnp.float32) - x.astype(jnp.float32)).max()) > 0.5  # it turned something
    jit_got, jit_want = jax.jit(new)(x, positions), jax.jit(old)(x, positions)
    jit_grad_got, jit_grad_want = jax.jit(jax.grad(scalar(new)))(x), jax.jit(jax.grad(scalar(old)))(x)
    # a contracted product is not rounded before the sum: the two differ by a rounding of that PRODUCT (the sum may
    # have cancelled to far less), and then by a unit of the dtype where that moves the last rounding
    product = np.spacing(np.float32(1.5 * max(float(jnp.abs(x.astype(jnp.float32)).max()), float(jnp.abs(weight).max()))))
    for a, b in ((jit_got, jit_want), (jit_grad_got, jit_grad_want), (jit_got, got), (jit_grad_got, grad_got)):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        ulp = np.spacing(np.maximum(np.abs(a32), np.abs(b32))) * 2 ** 16 if dtype == jnp.bfloat16 else 0.0
        assert (np.abs(a32 - b32) <= ulp + product).all()
    if call == "yarn_64_of_128":
        odd = x.at[0, 3, 1, 70].set(jnp.inf).at[1, 5, 0, 127].set(jnp.nan).at[0, 0, 2, 64].set(-jnp.inf).at[1, 1, 1, 100].set(-0.0)
        with jax.disable_jit():
            assert (_bits(new(odd, positions)) == _bits(old(odd, positions))).all()
        for out in (new(odd, positions), jax.jit(new)(odd, positions)):
            assert (_bits(out[..., 64:]) == _bits(odd[..., 64:])).all()
            assert np.isfinite(np.asarray(out[..., :64], np.float32)).all()
        cot = jax.grad(lambda x: (new(x, positions).astype(jnp.float32) * jnp.asarray(odd, jnp.float32)).sum())(x)
        assert np.isfinite(np.asarray(cot[..., :64], np.float32)).all() and not np.isfinite(np.asarray(cot[..., 64:], np.float32)).all()


def _primitives(jaxpr, found):
    """Every equation of `jaxpr` and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


@pytest.mark.parametrize("direction", ["forward", "gradient"])
@pytest.mark.parametrize("call", list(_ROPE_CALLS))
def test_a_head_is_never_cut_into_halves(call, direction) -> None:
    """Nothing splits, slices or joins q along its column axis, forward or
    backward, at 128 columns or at 64: the halves change places by `pad`s
    that move the whole axis and a `select_n`, inside one `custom_vjp`.
    Forward the pads move x after its cast to float32 (on the chip the
    product before them then hands over its float32 result, as it did to the
    two-halves form); backward they move the cotangent in its own dtype.
    The two-halves form, walked the same way, shows its `concatenate`."""
    new, old, shape = _ROPE_CALLS[call]
    x = jnp.zeros(shape, jnp.bfloat16)
    positions = jnp.zeros(shape[:2], jnp.int32)

    def walked(f):
        fn = (lambda x: f(x, positions)) if direction == "forward" else jax.grad(lambda x: f(x, positions).astype(jnp.float32).sum())
        eqns = _primitives(jax.make_jaxpr(fn)(x).jaxpr, [])
        on_columns = [e for e in eqns if e.primitive.name in ("concatenate", "split", "slice", "dynamic_slice", "gather")
                      and any(getattr(v.aval, "ndim", 0) == 4 and v.aval.shape[2] == shape[2] for v in e.invars)]
        return eqns, [e.primitive.name for e in eqns], on_columns

    eqns, names, on_columns = walked(new)
    assert not on_columns, [str(e) for e in on_columns]
    assert "concatenate" not in names and "split" not in names
    assert "select_n" in names and (direction == "gradient" or any(n.startswith("custom_vjp") for n in names))
    pads = [e for e in eqns if e.primitive.name == "pad"]
    # a gradient's jaxpr holds the forward's two pads as well (the residuals are the tables, so XLA drops them)
    assert {str(e.invars[0].aval.dtype) for e in pads[-2:]} == ({"float32"} if direction == "forward" else {"bfloat16"}) and len(pads) >= 2
    _, old_names, old_on_columns = walked(old)
    assert "concatenate" in old_names and old_on_columns


def test_a_model_of_whole_heads_is_the_two_halves_model(monkeypatch) -> None:
    """A small model at the Laguna cell's head width (128 columns: a window
    layer turned whole, a full layer turned over 64 of 128 under YaRN, K and
    V repeated to the query heads) gives the loss and the gradients it gives
    with `_turn` put back to the two-halves form: the same float32 sums, so
    equal to the rounding of a product XLA:CPU contracts in one and not the
    other."""
    from torchft_tpu.models import transformer

    cfg = TransformerConfig(vocab_size=64, d_model=64, n_layers=2, n_heads=2, n_kv_heads=1, head_dim=128, d_ff=64, max_seq=32,
                            dtype=jnp.float32, attn_head_gate=True, scan_unroll=2,
                            pattern=(LayerKind("window_layers", False, 2, 1e4, window=8),
                                     LayerKind("layers", False, 2, 5e5, rotary_fraction=0.5, yarn=(4.0, 16, 4.0, 1.0, 1.1))))
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    def loss_and_grads():
        return jax.jit(jax.value_and_grad(lambda p: loss_and_counters(p, batch, cfg)[0]))(params)

    loss, grads = loss_and_grads()
    calls = []
    monkeypatch.setattr(transformer, "_turn", lambda *a: calls.append(a[0].shape) or _two_halves(*a))
    old_loss, old_grads = loss_and_grads()
    assert sorted(set(calls)) == [(2, 32, 1, 128), (2, 32, 2, 128)]
    np.testing.assert_allclose(float(loss), float(old_loss), rtol=1e-6)
    for new, old in zip(jax.tree.leaves(grads), jax.tree.leaves(old_grads)):
        np.testing.assert_allclose(np.asarray(new), np.asarray(old), rtol=1e-4, atol=1e-5 * float(jnp.abs(old).max()))


# -- one chip's share of an expert-parallel layer ---------------------------------


def _layer_inputs(seed=7, tokens=64, hidden=64, inner=16, n_exp=256):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5  # noqa: E731
    x = jax.random.normal(ks[0], (2, tokens // 2, hidden), jnp.float32)
    w = dict(router=normal(ks[1], (hidden, n_exp), hidden), w_gate=normal(ks[2], (n_exp, hidden, inner), hidden),
             w_up=normal(ks[3], (n_exp, hidden, inner), hidden), w_down=normal(ks[4], (n_exp, inner, hidden), inner),
             shared_gate=normal(ks[5], (hidden, inner), hidden), shared_up=normal(ks[6], (hidden, inner), hidden),
             shared_down=normal(ks[7], (inner, hidden), inner))
    return x, w


def _share(x, w, first, count, shared=False):
    return moe_layer(
        x, w["router"], w["w_gate"][first:first + count], w["w_up"][first:first + count],
        w["w_down"][first:first + count], top_k=8, capacity_factor=None, norm_topk=True, score="sigmoid",
        route_scale=2.5, held_first=first, dtype=jnp.float32,
        shared=(w["shared_gate"], w["shared_up"], w["shared_down"]) if shared else None)


@pytest.mark.parametrize("chips", [8, 16, 4, 1])
def test_the_shares_add_up_to_the_uncut_layer(chips) -> None:
    """The router's published 256 outputs and 8 a token at small widths: what
    every chip of an expert-parallel layer computes of the routed experts (8
    chips: 32 each), summed over the chips, plus the shared expert counted
    once, is what the uncut plain reference gives for the whole layer — values
    and the gradient of the input."""
    x, w = _layer_inputs()
    count = 256 // chips
    s = REFERENCE.sizes_of(dict(CONFIG, num_experts=256, num_experts_per_tok=8))
    assert (s["held"], s["experts"], s["first"], s["top_k"]) == (256, 256, 0, 8)

    def uncut(x):
        return jnp.stack([REFERENCE._experts(seq, w, s, "float32")[0] for seq in x])

    def summed(x):
        routed = sum(_share(x, w, r * count, count)[0] for r in range(chips))
        shared = (jax.nn.silu(x @ w["shared_gate"]) * (x @ w["shared_up"])) @ w["shared_down"]
        return routed + shared

    with jax.default_matmul_precision("highest"):
        want, got = uncut(x), summed(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
        dwant = jax.grad(lambda x: jnp.sum(jnp.sin(uncut(x))))(x)
        dgot = jax.grad(lambda x: jnp.sum(jnp.sin(summed(x))))(x)
        np.testing.assert_allclose(np.asarray(dgot), np.asarray(dwant), rtol=1e-4, atol=1e-5)
    # a share with the shared expert is that share plus the shared expert
    assert float(jnp.max(jnp.abs(_share(x, w, 0, count, shared=True)[0] - _share(x, w, 0, count)[0]))) > 0.1
    # the counters: the shares' held rows are all the assignments, none dropped
    stats = [_share(x, w, r * count, count)[1] for r in range(chips)]
    assert sum(int(st["rows_held"]) for st in stats) == int(stats[0]["assignments"]) == 64 * 8
    assert all(int(st["dropped"]) == 0 for st in stats)


# -- the three-kind tree through ft_step, a heal's transport and the checkpoint ----


def _records(path, event):
    with open(path, encoding="utf-8") as f:
        return [r for r in map(json.loads, f) if r.get("event") == event]


# A pattern of three kinds that is not Laguna's: window layers first, a dense
# feed-forward in the middle, no experts held apart.
TINY = TransformerConfig(
    vocab_size=128, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32, dense_d_ff=96, max_seq=32,
    dtype=jnp.float32, remat=True, remat_keeps_attention=True, scan_unroll=1, attn_head_gate=True,
    moe_experts=4, moe_top_k=2, moe_capacity_factor=None, moe_score="sigmoid", moe_aux_coef=0.001,
    pattern=(LayerKind("near", True, 8, 1e4, window=8),
             LayerKind("middle", False, 4, 5e5, rotary_fraction=0.5, yarn=(4.0, 16, 4.0, 1.0, 1.1)),
             LayerKind("layers", True, 4, 1e4), LayerKind("layers", True, 4, 1e4)),
)


@pytest.mark.parametrize("through", ["ft_step", "heal", "disk_checkpoint"])
def test_a_three_kind_tree_goes_through(through, store, tmp_path, monkeypatch) -> None:  # noqa: F811
    params = init_params(jax.random.PRNGKey(5), TINY)
    assert set(params) == {"embed", "final_norm", "lm_head", "near", "middle", "layers"}
    assert params["near"]["wq"].shape == (1, 64, 128) and params["layers"]["wq"].shape == (2, 64, 64)
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
        step = TrainStep(ftmesh, optax.adamw(1e-3), lambda p, b: loss_and_counters(p, b, TINY),
                         loss_has_counters=True, overlap_commit=False)
        opt = step.init_opt_state(params)
        batch = _batch(0, dict(vocab_size=128), seq_len=32)
        before = jax.tree.map(np.asarray, params)  # `ft_step` donates its arguments
        try:
            for _ in range(2):
                manager.start_quorum()
                params, opt, loss, committed = step.ft_step(params, opt, batch)
                assert committed and np.isfinite(float(loss))
        finally:
            manager.shutdown()
        assert jax.tree.structure(params) == jax.tree.structure(before)
        assert all(not np.array_equal(np.asarray(a), b) for a, b in
                   zip(jax.tree.leaves(params["near"]), jax.tree.leaves(before["near"])) if a.ndim > 2)
        summary = _records(path, "step_summary")[-1]
        assert summary["moe_dropped"] == 0 and summary["moe_tokens_per_expert_max"] > 0
        # the scan over the run of two layers and the static loops beside it are one model
        unrolled = jax.jit(lambda p, b: loss_and_counters(p, b, dataclasses.replace(TINY, scan_unroll=8))[0])(params, batch)
        scanned = jax.jit(lambda p, b: loss_and_counters(p, b, TINY)[0])(params, batch)
        np.testing.assert_allclose(float(unrolled), float(scanned), rtol=1e-6)
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


def _scans(cfg) -> int:
    """How many `scan`s the decoder's trace holds."""
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, cfg.max_seq), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: loss_and_counters(p, {"tokens": t, "targets": t}, cfg)[0])(params, tokens)
    return sum(eqn.primitive.name == "scan" for eqn in jaxpr.jaxpr.eqns)


@pytest.mark.parametrize("scan_unroll,scans", [(1, 1), (2, 0), (8, 0)], ids=["unroll_1", "unroll_2", "unroll_8"])
def test_a_run_that_the_unroll_covers_is_a_static_loop(scan_unroll, scans) -> None:
    """Scan or loop is a property of a run's length, not of its stack's name:
    TINY's runs are one, one and two layers long, so only the run of two
    scans, and only where `scan_unroll` does not cover it."""
    assert _scans(dataclasses.replace(TINY, remat=False, scan_unroll=scan_unroll)) == scans


@pytest.mark.parametrize("dense,scan_unroll,scans", [(0, 1, 1), (1, 1, 1), (2, 1, 2), (1, 4, 0), (0, 2, 1)],
                         ids=["one_kind", "a_leading_dense_layer", "two_leading_dense_layers", "unrolled", "partly_unrolled"])
def test_leading_dense_layers_are_runs_like_any_other(dense, scan_unroll, scans) -> None:
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=2, n_kv_heads=2, d_ff=16, dense_d_ff=48, max_seq=16,
        dtype=jnp.float32, scan_unroll=scan_unroll, moe_experts=4, moe_top_k=2, moe_capacity_factor=None,
        moe_dense_layers=dense)
    assert [(s, n) for s, (_, n) in cfg.stacks.items()] == [("dense_layers", dense)] * bool(dense) + [("layers", 4 - dense)]
    assert _scans(cfg) == scans


@pytest.mark.parametrize("scan_unroll", [1, 4], ids=["scan", "static_loop"])
def test_a_choice_bias_goes_by_the_place_among_the_sparse_layers(scan_unroll) -> None:
    """`router_bias` has a row a sparse layer, in the layers' order in the
    model WHATEVER their stack (since PR 48; before it a pattern with two
    sparse stacks refused a bias): a model of one sparse kind takes it in the
    stack's order, leading dense layers or not, and TINY's three sparse
    layers in two stacks (`near`, then two of `layers` after a dense one) each
    take their own row."""
    batch = _batch(0, dict(vocab_size=128), seq_len=32)
    one_sparse_stack = dataclasses.replace(TINY, pattern=TINY.pattern[1:] + (TINY.pattern[-1],), remat=False,
                                           scan_unroll=scan_unroll)
    params = init_params(jax.random.PRNGKey(1), one_sparse_stack)
    bias = jnp.zeros((3, 4), jnp.float32).at[:, 0].set(100.0)  # every token's first choice is expert 0
    _, counters = loss_and_counters(params, batch, one_sparse_stack, router_bias=bias)
    assert np.asarray(counters["moe_tokens_per_expert"])[:, 0].tolist() == [2 * 32] * 3
    two_sparse_stacks = dataclasses.replace(TINY, scan_unroll=scan_unroll)
    bias = jnp.zeros((3, 4), jnp.float32).at[jnp.arange(3), jnp.arange(3)].set(100.0)  # sparse layer j's is expert j
    _, counters = loss_and_counters(init_params(jax.random.PRNGKey(1), two_sparse_stacks), batch, two_sparse_stacks,
                                    router_bias=bias)
    per_expert = np.asarray(counters["moe_tokens_per_expert"])
    assert [per_expert[j, j] for j in range(3)] == [2 * 32] * 3


def test_the_stacks_draw_their_weights_as_they_did_before_the_pattern() -> None:
    """The last kind's stack draws from the layers' key itself and a kind
    before it from a key folded out of it: "layers" and "dense_layers" of a
    model with a leading dense layer get the weights they always got."""
    from torchft_tpu.models.transformer import _init_layers

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2, d_ff=16, dense_d_ff=48,
                            max_seq=16, moe_experts=4, moe_top_k=2, moe_capacity_factor=None, moe_dense_layers=1)
    key = jax.random.PRNGKey(7)
    k_layers = jax.random.split(key, 3)[1]
    params = init_params(key, cfg)
    dense, own = cfg.layers[0], cfg.layers[-1]
    for got, want in ((params["layers"], _init_layers(k_layers, cfg, 2, own)),
                      (params["dense_layers"], _init_layers(jax.random.fold_in(k_layers, 1), cfg, 1, dense))):
        assert all(np.array_equal(np.asarray(got[name]), np.asarray(want[name])) for name in want)
    three = init_params(key, TINY)  # three stacks: no two share a key
    assert not np.array_equal(np.asarray(three["near"]["wk"][0]), np.asarray(three["middle"]["wk"][0]))
