"""Laguna-shaped models (window and full attention mixed, two head counts, a
head gate, sigmoid top-k routing beside a shared expert; a layer ``pattern`` of
three kinds, each under a stack of its own) through the program, on the CPU at
small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/swa_moe_lm.py``).  What only this
architecture has is tested here: YaRN's frequencies against the closed form, the
half-rotated RoPE against a written-out rotation and the two-halves form, runs
of layers as scans or static loops, a choice bias by the place among the sparse layers.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, HELD, REMAT, Architecture, ExpertLayer, Tiny, batches, equations, in_the_scan, omission_cases, pytest_generate_tests, store,
    test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_rematerialised_layers_give_the_gradients_of_the_stored_ones, test_the_adapter_raises_on_what_it_does_not_honour,
    test_the_published_configuration_is_handed_over_whole, test_the_shares_add_up_to_the_uncut_layer,
    test_the_tree_goes_through, test_the_tree_is_the_reference_s)
from torchft_tpu.models import LayerKind, TransformerConfig, init_params
from torchft_tpu.models.moe import moe_layer
from torchft_tpu.models.rope import _rope, _rotary, yarn_frequencies
from torchft_tpu.models.transformer import loss_and_counters

REFERENCE = BENCH.reference("swa_moe_lm")
PROGRAM = BENCH.program("swa_moe_lm")
PUBLISHED = BENCH.config("laguna-xs.2")

SEQ, WINDOW = 96, 24
PERIOD = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"]
SIZES = """96 positions under a window of 24: a window layer's band is under half of the triangle, and YaRN's original
length of 32 is passed three times over.  The cut's 1 + 4 layers: a dense full layer, the period's three window layers, a
sparse full layer — the least with every kind, the three stacks one, three and one layer long.  6 query heads on full
layers and 8 on window layers over 2 KV heads of 32, YaRN over half a head with a ramp of three pairs, 8 routed experts,
2 a token, one shared expert.  The lists keep their published length: the first `num_hidden_layers` entries count.
Float32 throughout."""
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


_batch = batches(CONFIG["vocab_size"], SEQ)


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


def _counters(counters, config) -> None:
    assert int(counters["moe_dropped"]) == 0
    assert np.asarray(counters["moe_tokens_per_expert"]).sum(axis=1).tolist() == [2 * SEQ * 2] * 4


def _tree_facts(cfg, own) -> None:
    """Three kinds of layer, three stacked subtrees, each at its own head count."""
    assert {s: (k.n_heads, k.window, k.sparse, n) for s, (k, n) in cfg.stacks.items()} == {
        "dense_layers": (6, None, False, 1), "window_layers": (8, WINDOW, True, 3), "layers": (6, None, True, 1)}
    assert own["window_layers"]["wq"].shape == (3, 128, 8 * 32) and own["layers"]["wq"].shape == (1, 128, 6 * 32)
    assert own["window_layers"]["attn_gate"].shape == (3, 128, 8) and own["dense_layers"]["w_gate"].shape == (1, 128, 256)
    assert own["layers"]["w_gate"].shape == (1, 2, 128, 64) and own["layers"]["router"].shape == (1, 128, 8)


def _published_facts(cfg, _) -> None:
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


REFUSALS = [
    ("all_full", dict(layer_types=["full_attention"] * 8), "not the period"),
    ("period_shifted", dict(layer_types=["sliding_attention", "full_attention"] * 4), "not the period"),
    ("linear_rope", dict(rope_parameters=dict(CONFIG["rope_parameters"], full_attention=dict(
        CONFIG["rope_parameters"]["full_attention"], rope_type="linear"))), "no rope_type"),
    ("llama3_rope", dict(rope_parameters=dict(CONFIG["rope_parameters"], sliding_attention=dict(
        CONFIG["rope_parameters"]["sliding_attention"], rope_type="llama3"))), "no rope_type"),
    ("gates_on_the_input", dict(moe_apply_router_weight_on_input=True), "experts' outputs"),
    ("dense_window_layers", dict(mlp_layer_types=["dense"] * 8), "no stack"),
]


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
        eqns = equations(jax.make_jaxpr(fn)(x).jaxpr)
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
    from torchft_tpu.models import rope

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
    monkeypatch.setattr(rope, "_turn", lambda *a: calls.append(a[0].shape) or _two_halves(*a))
    old_loss, old_grads = loss_and_grads()
    assert sorted(set(calls)) == [(2, 32, 1, 128), (2, 32, 2, 128)]
    np.testing.assert_allclose(float(loss), float(old_loss), rtol=1e-6)
    for new, old in zip(jax.tree.leaves(grads), jax.tree.leaves(old_grads)):
        np.testing.assert_allclose(np.asarray(new), np.asarray(old), rtol=1e-4, atol=1e-5 * float(jnp.abs(old).max()))


# -- one chip's share of an expert-parallel layer: the router's published 256 outputs and 8 a token at small widths
# (8 chips: 32 each), the shared expert counted once ---------------------------------------------------------------


def _expert_layer(tokens=64, hidden=64, inner=16, n_exp=256) -> ExpertLayer:
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    normal = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5  # noqa: E731
    x = jax.random.normal(ks[0], (2, tokens // 2, hidden), jnp.float32)
    w = dict(router=normal(ks[1], (hidden, n_exp), hidden), w_gate=normal(ks[2], (n_exp, hidden, inner), hidden),
             w_up=normal(ks[3], (n_exp, hidden, inner), hidden), w_down=normal(ks[4], (n_exp, inner, hidden), inner),
             shared_gate=normal(ks[5], (hidden, inner), hidden), shared_up=normal(ks[6], (hidden, inner), hidden),
             shared_down=normal(ks[7], (inner, hidden), inner))
    s = REFERENCE.sizes_of(dict(CONFIG, num_experts=256, num_experts_per_tok=8))
    assert (s["held"], s["experts"], s["first"], s["top_k"]) == (256, 256, 0, 8)

    def share(first, count, with_shared, x):
        return moe_layer(
            x, w["router"], w["w_gate"][first:first + count], w["w_up"][first:first + count],
            w["w_down"][first:first + count], top_k=8, capacity_factor=None, norm_topk=True, score="sigmoid",
            route_scale=2.5, held_first=first, dtype=jnp.float32,
            shared=(w["shared_gate"], w["shared_up"], w["shared_down"]) if with_shared else None)

    def uncut(x):
        return jnp.stack([REFERENCE._experts(seq, w, s, "float32")[0] for seq in x]), None

    return ExpertLayer((x,), n_exp, share, uncut, tokens * 8, shared=True)


# -- the three-kind tree through ft_step, a heal's transport and the checkpoint ----

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


def _tiny() -> Tiny:
    def params():
        tree = init_params(jax.random.PRNGKey(5), TINY)
        assert set(tree) == {"embed", "final_norm", "lm_head", "near", "middle", "layers"}
        assert tree["near"]["wq"].shape == (1, 64, 128) and tree["layers"]["wq"].shape == (2, 64, 64)
        return tree

    data = _batch(0, 128, 32)

    def facts(moved, summaries, step, after) -> None:
        assert {f"['near']['{name}']" for name, leaf in after["near"].items() if leaf.ndim > 2} <= moved
        assert summaries[-1]["moe_dropped"] == 0 and summaries[-1]["moe_tokens_per_expert_max"] > 0
        # the scan over the run of two layers and the static loops beside it are one model
        unrolled = jax.jit(lambda p, b: loss_and_counters(p, b, dataclasses.replace(TINY, scan_unroll=8))[0])(after, data)
        scanned = jax.jit(lambda p, b: loss_and_counters(p, b, TINY)[0])(after, data)
        np.testing.assert_allclose(float(unrolled), float(scanned), rtol=1e-6)

    return Tiny(params, lambda p, b: loss_and_counters(p, b, TINY), lambda i: data, 2, facts)


ARCH = Architecture(
    name="swa_moe_lm", configs=dict(zip(HELD, (CONFIG, SHARE))), sizes=SIZES, seq=SEQ, variants=dict(in_the_scan(OMISSIONS), **REMAT),
    leaf_cases=omission_cases(OMISSIONS, 11),
    # Both sides compute in float32 on the CPU, so they differ by the order of their sums alone: every leaf agrees to
    # under 1e-5 of its norm.  The least of the named omissions moves its leaf by far more, so 3e-5 passes the one and
    # fails the others.
    leaf_tolerance=3e-5, loss_tolerance=1e-6, counters=_counters,
    remat=("a_share_of_the_experts", 4, tuple(REMAT)),
    chips=[8, 16, 4, 1], expert_layer=_expert_layer,
    published="laguna-xs.2", tree_config="a_share_of_the_experts", tree_facts=_tree_facts, published_facts=_published_facts,
    refusals=REFUSALS, refusal_config="every_expert_held", through=("ft_step", "heal", "disk_checkpoint"), tiny=_tiny,
)


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
    batch = _batch(0, 128, 32)
    one_sparse_stack = dataclasses.replace(TINY, pattern=TINY.pattern[1:] + (TINY.pattern[-1],), remat=False,
                                           scan_unroll=scan_unroll)
    params = init_params(jax.random.PRNGKey(1), one_sparse_stack)
    bias = jnp.zeros((3, 4), jnp.float32).at[:, 0].set(100.0)  # every token's first choice is expert 0
    _, counters = jax.jit(lambda p, b: loss_and_counters(p, b, one_sparse_stack, router_bias=bias))(params, batch)
    assert np.asarray(counters["moe_tokens_per_expert"])[:, 0].tolist() == [2 * 32] * 3
    two_sparse_stacks = dataclasses.replace(TINY, scan_unroll=scan_unroll)
    bias = jnp.zeros((3, 4), jnp.float32).at[jnp.arange(3), jnp.arange(3)].set(100.0)  # sparse layer j's is expert j
    _, counters = jax.jit(lambda p, b: loss_and_counters(p, b, two_sparse_stacks, router_bias=bias))(
        init_params(jax.random.PRNGKey(1), two_sparse_stacks), batch)
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
