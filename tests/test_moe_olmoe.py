"""OLMoE-shaped models (dropless sorted routing, ``ops/grouped_matmul.py``, the
QK-norm, both auxiliary losses) through the program, on the CPU at small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/moe_lm.py``).  What only this
architecture has is tested here: the sorted path against the capacity path and
what a loss without counters lowers to (the grouped matmul: `tests/test_grouped_matmul.py`).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, HELD, Architecture, Case, Tiny, batches, in_the_scan, pytest_generate_tests, records, store,
    test_loss_and_every_gradient_leaf_against_the_plain_reference, test_the_tree_goes_through)
from torchft_tpu.models import TransformerConfig, init_params, loss_fn
from torchft_tpu.models.moe import moe_layer
from torchft_tpu.models.transformer import loss_and_counters
from torchft_tpu.parallel import TrainStep, ft_init_mesh

REFERENCE = BENCH.reference("moe_lm")
PROGRAM = BENCH.program("moe_lm")

SEQ = 128
SIZES = """128 positions, the small model's whole `max_position_embeddings`; 256 positions x 2 choices over 8 experts
fill every expert.  Two layers, the least with a layer after a layer, 8 experts, 2 a token.  Float32 throughout."""
CONFIG = dict(
    architecture="moe_lm", vocab_size=384, hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, intermediate_size=128, num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
    max_position_embeddings=128, rope_theta=1e4, rms_norm_eps=1e-5, router_aux_loss_coef=0.01,
    router_z_loss_coef=0.001, clip_qkv=None,
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
    program=dict(remat=False, scan_unroll=2),
)

_batch = batches(CONFIG["vocab_size"], SEQ)


# What the program would compute with one part of the published mathematics
# left out: each has to fail the comparison that the whole passes.
OMISSIONS = {
    "as_published": {},
    "without_the_qk_norm": {"qk_norm": False},
    "top_k_renormalised": {"moe_norm_topk": True},
    "without_the_balance_loss": {"moe_aux_coef": 0.0},
    "without_the_z_loss": {"moe_z_coef": 0.0},
    "the_fixed_epsilon": {"rms_eps": 1e-6},
}
QK_NORM = ("q_norm", "k_norm")


def _weights(weights, variant):
    # At unit-scale activations 1e-5 against 1e-6 is 5e-6 relative: seen
    # only where the norm's input is small, as after a shrunken embedding.
    return dict(weights, embed=weights["embed"] * 0.02) if variant == "the_fixed_epsilon" else weights


def _prune(tree, variant):
    """A tree without the two norm leaves, as the program without the QK-norm expects it."""
    if variant != "without_the_qk_norm":
        return tree
    return dict(tree, layers={k: v for k, v in tree["layers"].items() if k not in QK_NORM})


def _layer_weights(key, n_exp=8, hidden=128, inner=128):
    kr, kg, ku, kd = jax.random.split(key, 4)
    normal = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5  # noqa: E731
    return (normal(kr, (hidden, n_exp), hidden), normal(kg, (n_exp, hidden, inner), hidden),
            normal(ku, (n_exp, hidden, inner), hidden), normal(kd, (n_exp, inner, hidden), inner))


@pytest.mark.parametrize("norm_topk", [False, True])
def test_sorted_path_matches_the_capacity_path_where_nothing_is_dropped(norm_topk) -> None:
    """Capacity 8x the even share is never reached: the two paths are the
    same function, value, statistics and every gradient."""
    weights = _layer_weights(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 128), jnp.float32)

    def run(capacity_factor):
        def f(x, *w):
            y, stats = moe_layer(x, *w, top_k=2, capacity_factor=capacity_factor, norm_topk=norm_topk, dtype=jnp.float32)
            return jnp.sum(y * jnp.cos(jnp.arange(128.0))) + stats["balance"] + stats["z"], stats
        (value, stats), grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, *weights)
        return value, stats, grads

    sorted_value, sorted_stats, sorted_grads = run(None)
    value, stats, grads = run(8.0)
    assert int(stats["dropped"]) == 0 and int(sorted_stats["dropped"]) == 0
    np.testing.assert_array_equal(np.asarray(stats["tokens_per_expert"]), np.asarray(sorted_stats["tokens_per_expert"]))
    np.testing.assert_allclose(float(sorted_value), float(value), rtol=2e-4)  # a sum that cancels
    for a, b in zip(sorted_grads, grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_nothing_is_dropped_under_a_router_skewed_to_one_expert() -> None:
    """Every position's first choice is expert 0 — 4x the capacity the dense
    dispatch would give it at factor 1.25 — and every one is served."""
    router, w_gate, w_up, w_down = _layer_weights(jax.random.PRNGKey(0))
    router = (router * 0.01).at[:, 0].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 256, 128), jnp.float32)) + 0.1
    y, stats = jax.jit(lambda x: moe_layer(x, router, w_gate, w_up, w_down, top_k=2, capacity_factor=None, norm_topk=False,
                                           dtype=jnp.float32))(x)
    counts = np.asarray(stats["tokens_per_expert"])
    assert counts[0] == 256 and counts.sum() == 512 and int(stats["dropped"]) == 0
    _, bound = jax.jit(lambda x: moe_layer(x, router, w_gate, w_up, w_down, top_k=2, capacity_factor=1.25, norm_topk=False,
                                           dtype=jnp.float32))(x)
    assert int(bound["dropped"]) > 100  # the path this one replaces on one device
    xf = x.reshape(-1, 128)
    probs = jax.nn.softmax(xf @ router, axis=-1)
    gates, chosen = jax.lax.top_k(probs, 2)
    expert = lambda e, t: (jax.nn.silu(xf[t] @ w_gate[e]) * (xf[t] @ w_up[e])) @ w_down[e]  # noqa: E731
    for t in (0, 17, 255):
        manual = sum(float(gates[t, j]) * np.asarray(expert(int(chosen[t, j]), t)) for j in range(2))
        np.testing.assert_allclose(np.asarray(y)[0, t], manual, rtol=2e-4, atol=2e-5)


def test_an_expert_that_receives_no_rows_has_a_zero_gradient_and_the_loss_is_finite() -> None:
    cfg = dataclasses.replace(PROGRAM.transformer_config(CONFIG), n_layers=1, scan_unroll=2)
    params = init_params(jax.random.PRNGKey(2), cfg)
    # Expert 5's router column far below the others: no position takes it.
    params["layers"]["router"] = params["layers"]["router"].at[:, :, 5].set(0.0).at[:, 0, 5].set(-1e4)
    params["embed"] = params["embed"].at[:, 0].set(5.0)  # channel 0 positive at every position
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p, b: loss_and_counters(p, b, cfg), has_aux=True))(params, _batch(4))
    counts = np.asarray(counters["moe_tokens_per_expert"])
    assert counts.shape == (1, 8) and counts[0, 5] == 0 and counts.sum() == 2 * 128 * 2
    assert np.isfinite(float(loss)) and int(counters["moe_dropped"]) == 0
    for name in ("w_gate", "w_up", "w_down"):
        leaf = np.asarray(grads["layers"][name])
        assert np.all(np.isfinite(leaf)) and not leaf[0, 5].any()
        assert all(leaf[0, e].any() for e in range(8) if counts[0, e] > 0)


# -- TrainStep's counters ------------------------------------------------------


def _tiny() -> Tiny:
    """Two ft_steps under a real Manager: the first step's counters are in
    the second step's `step_summary`, named for the step they were counted
    in, and the hand-over is a sub-span of the frame."""
    cfg = dataclasses.replace(PROGRAM.transformer_config(CONFIG), n_layers=1)

    def facts(moved, summaries, step, after) -> None:
        counts = np.asarray(step.last_counters["moe_tokens_per_expert"])
        assert counts.shape == (1, 8) and counts.sum() == 2 * SEQ * 2
        assert "moe_tokens_per_expert_max" not in summaries[0]
        for summary in summaries[1:]:
            assert summary["moe_tokens_per_expert_mean"] == 64.0 and summary["moe_dropped"] == 0
            assert 64 <= summary["moe_tokens_per_expert_max"] <= 512
        subs = [s for r in records(os.environ["TPUFT_METRICS_PATH"], "subspan") for s in r["spans"]]
        notes = [s for s in subs if s["name"] == "counters_note"]
        assert len(notes) == 2 and all(s["parent"] == "ft_step" for s in notes)
        # the split form keeps them too, and grads() still returns (loss, grads)
        loss, grads = step.grads(after, _batch(9))
        assert loss.shape == () and jax.tree.structure(grads) == jax.tree.structure(after)
        assert int(step.last_counters["moe_dropped"]) == 0

    return Tiny(lambda: init_params(jax.random.PRNGKey(0), cfg), lambda p, b: loss_and_counters(p, b, cfg), _batch, 3, facts)


ARCH = Architecture(
    name="moe_lm", configs={HELD[0]: CONFIG}, sizes=SIZES, seq=SEQ, variants=in_the_scan(OMISSIONS),
    leaf_cases=[Case(name, HELD[0], name, 11, name == "as_published") for name in OMISSIONS],
    # Both sides compute in float32 on the CPU, so they differ by the order of their sums alone: every leaf agrees to
    # under 1e-5 of its norm (measured 1e-6).  The least of the named omissions moves its leaf by 3e-4 (the z-loss, on
    # the router), so 3e-5 passes the one and fails the others.
    leaf_tolerance=3e-5, loss_tolerance=1e-6, weights=_weights, weights_vary=("the_fixed_epsilon",), prune=_prune,
    through=("ft_step",), tiny=_tiny,
)


def test_a_loss_without_counters_lowers_to_the_program_it_always_did() -> None:
    """`TrainStep` of a scalar loss: the gradient program's StableHLO is, to
    the character, that of `jax.jit(jax.value_and_grad(loss))` under the
    mesh — what it was before counters existed, so the dense cells'
    programs stay hits of the persistent compile cache."""
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
                            max_seq=64, remat=False, scan_unroll=2)
    ftmesh = ft_init_mesh({"data": 1}, devices=jax.devices()[:1])
    loss = lambda p, b: loss_fn(p, b, cfg)  # noqa: E731
    step = TrainStep(ftmesh, optax.adamw(1e-3), loss)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(0, 128, 64)

    def value_and_grad(params, batch):
        with jax.sharding.use_abstract_mesh(ftmesh.mesh.abstract_mesh):
            return jax.value_and_grad(loss)(params, batch)

    before = jax.jit(value_and_grad).lower(params, batch).as_text()
    assert step.lower_grads(params, batch).as_text() == before
    out = step.grads(params, batch)
    assert len(out) == 2 and out[0].shape == () and step.last_counters is None


def test_dense_configurations_trace_to_the_same_operations_with_the_new_options_at_rest() -> None:
    """`rms_eps` defaults to the 1e-6 the program always used, and a dense
    model carries no QK-norm, no router and no counters."""
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128, max_seq=64)
    assert cfg.rms_eps == 1e-6 and not cfg.qk_norm and cfg.moe_capacity_factor == 1.25 and cfg.moe_norm_topk
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert set(params["layers"]) == {"attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up", "w_down"}
    loss, counters = loss_and_counters(params, _batch(0, 128, 64), cfg)
    assert counters == {} and np.isfinite(float(loss))
