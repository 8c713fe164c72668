"""OLMoE-shaped models through the program, on the CPU at small sizes.

The program (``models/transformer.py`` with dropless sorted routing,
``ops/grouped_matmul.py``, the QK-norm, both auxiliary losses) against the
benchmark's plain float32 reference (``benchmark/reference/moe_lm.py``, which
shares no code with it) on seeded random weights; the sorted path against the
capacity path; the grouped matmul against a per-expert loop; and
``TrainStep``'s counters.
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
from torchft_tpu.models import TransformerConfig, init_params, loss_fn  # noqa: E402
from torchft_tpu.models.moe import moe_layer  # noqa: E402
from torchft_tpu.models.transformer import loss_and_counters  # noqa: E402
from torchft_tpu.ops.grouped_matmul import grouped_matmul, padded_group_sizes  # noqa: E402
from torchft_tpu.parallel import TrainStep, ft_init_mesh  # noqa: E402

BENCH = Benchmark(ROOT)
REFERENCE = BENCH.reference("moe_lm")
PROGRAM = BENCH.program("moe_lm")

# A 2-layer, 8-expert, top-2 model of OLMoE's shape, float32 throughout.
CONFIG = dict(
    architecture="moe_lm", vocab_size=384, hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, intermediate_size=128, num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
    max_position_embeddings=128, rope_theta=1e4, rms_norm_eps=1e-5, router_aux_loss_coef=0.01,
    router_z_loss_coef=0.001, clip_qkv=None,
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
    program=dict(remat=False, scan_unroll=2),
)
# Both sides compute in float32 on the CPU, so they differ by the order of
# their sums alone: every leaf agrees to under 1e-5 of its norm (measured
# 1e-6).  The least of the named omissions moves its leaf by 3e-4 (the
# z-loss, on the router), so 3e-5 passes the one and fails the others.
LEAF_TOLERANCE = 3e-5
LOSS_TOLERANCE = 1e-6


def _batch(seed: int, config=CONFIG, sequences: int = 2, seq_len: int = 128):
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


@pytest.mark.parametrize("omission", list(OMISSIONS))
def test_loss_and_every_gradient_leaf_against_the_plain_reference(omission) -> None:
    seed = 11
    cfg = dataclasses.replace(PROGRAM.transformer_config(CONFIG), **OMISSIONS[omission])
    weights = REFERENCE.make_weights(seed, CONFIG)
    if omission == "the_fixed_epsilon":
        # At unit-scale activations 1e-5 against 1e-6 is 5e-6 relative: seen
        # only where the norm's input is small, as after a shrunken embedding.
        weights = dict(weights, embed=weights["embed"] * 0.02)
    batch = _batch(seed)
    if not cfg.qk_norm:  # a tree without the two norm leaves, as the program then expects it
        layers = {k: v for k, v in weights["layers"].items() if k not in ("q_norm", "k_norm")}
        program_weights = dict(weights, layers=layers)
    else:
        program_weights = weights
    loss, grads = jax.jit(jax.value_and_grad(lambda p, b: loss_fn(p, b, cfg)))(program_weights, batch)
    want_loss, want = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], CONFIG)
    if not cfg.qk_norm:
        want = dict(want, layers={k: v for k, v in want["layers"].items() if k not in ("q_norm", "k_norm")})
    leaf, rel = _worst_leaf(grads, want)
    loss_rel = abs(float(loss) - float(want_loss)) / float(want_loss)
    if omission == "as_published":
        assert rel < LEAF_TOLERANCE and loss_rel < LOSS_TOLERANCE, (leaf, rel, loss_rel)
    else:
        assert rel > 3 * LEAF_TOLERANCE, f"{omission}: the comparison did not see it ({leaf} {rel}, loss {loss_rel})"


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
        (value, stats), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, *weights)
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
    y, stats = moe_layer(x, router, w_gate, w_up, w_down, top_k=2, capacity_factor=None, norm_topk=False, dtype=jnp.float32)
    counts = np.asarray(stats["tokens_per_expert"])
    assert counts[0] == 256 and counts.sum() == 512 and int(stats["dropped"]) == 0
    _, bound = moe_layer(x, router, w_gate, w_up, w_down, top_k=2, capacity_factor=1.25, norm_topk=False, dtype=jnp.float32)
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


def _rows(counts, tile, k, key):
    """Rows in the kernels' layout: each group padded to whole tiles (one at
    least), zeros in the padding."""
    sizes = np.asarray(padded_group_sizes(jnp.asarray(counts, jnp.int32), tile))
    total = int(sizes.sum()) + tile  # one tile past the last group
    real = np.zeros(total, bool)
    for start, count in zip(np.concatenate([[0], np.cumsum(sizes)[:-1]]), counts):
        real[start:start + count] = True
    return jax.random.normal(key, (total, k), jnp.float32) * real[:, None], jnp.asarray(sizes), real


def _per_expert_loop(lhs, rhs, sizes):
    out, start = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32), 0
    for g, size in enumerate(np.asarray(sizes)):
        out = out.at[start:start + size].set(lhs[start:start + size] @ rhs[g])
        start += size
    return out


@pytest.mark.parametrize("interpret", [False, True], ids=["ragged_dot", "kernels_interpreted"])
@pytest.mark.parametrize("k,n", [(256, 128), (128, 384)])
def test_grouped_matmul_matches_a_per_expert_loop(interpret, k, n) -> None:
    """Forward and both gradients, with a group of no rows, one of a few and
    one of several tiles; `interpret` runs the three pallas kernels."""
    counts, tile = [130, 0, 5, 300], 128
    lhs, sizes, real = _rows(counts, tile, k, jax.random.PRNGKey(0))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (4, k, n), jnp.float32) * k ** -0.5
    weight = jax.random.normal(jax.random.PRNGKey(2), (lhs.shape[0], n)) * real[:, None]

    def ours(l, r):
        return grouped_matmul(l, r, sizes, row_tile=tile, interpret=interpret)

    np.testing.assert_allclose(np.asarray(ours(lhs, rhs)), np.asarray(_per_expert_loop(lhs, rhs, sizes)), atol=2e-4)
    got = jax.grad(lambda l, r: jnp.sum(ours(l, r) * weight), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda l, r: jnp.sum(_per_expert_loop(l, r, sizes) * weight), argnums=(0, 1))(lhs, rhs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)
    assert got[1].dtype == rhs.dtype and not np.asarray(got[1])[1].any()  # the group without rows


def test_grouped_matmul_rounds_wide_matrices_once_and_returns_their_gradient_unrounded() -> None:
    """bf16 rows against f32 matrices: the product is the bf16 one, the
    matrices' gradient comes back in f32 from the f32 accumulator."""
    counts, tile = [200, 56], 128
    lhs, sizes, real = _rows(counts, tile, 128, jax.random.PRNGKey(3))
    lhs = lhs.astype(jnp.bfloat16)
    rhs = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 128), jnp.float32)
    out = grouped_matmul(lhs, rhs, sizes, row_tile=tile, interpret=True)
    assert out.dtype == jnp.bfloat16
    want = _per_expert_loop(lhs.astype(jnp.float32), rhs.astype(jnp.bfloat16).astype(jnp.float32), sizes)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), rtol=1e-2, atol=1e-1)
    drhs = jax.grad(lambda r: jnp.sum(grouped_matmul(lhs, r, sizes, row_tile=tile, interpret=True).astype(jnp.float32)))(rhs)
    assert drhs.dtype == jnp.float32
    exact = np.asarray(lhs, np.float32)[:256].T @ np.ones((256, 128), np.float32)
    np.testing.assert_allclose(np.asarray(drhs)[0], exact, rtol=1e-5, atol=1e-4)  # no bf16 rounding of the sums


# -- TrainStep's counters ------------------------------------------------------


def _records(path, event):
    with open(path, encoding="utf-8") as f:
        return [r for r in map(json.loads, f) if r.get("event") == event]


def test_counters_land_in_the_next_steps_summary(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    """Two ft_steps under a real Manager: the first step's counters are in
    the second step's `step_summary`, named for the step they were counted
    in, and the hand-over is a sub-span of the frame."""
    path = tmp_path / "stream.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    client = MagicMock()
    client._quorum.return_value = make_quorum()
    client.should_commit.return_value = True
    manager, _, _ = make_manager(store, client_mock=client)
    cfg = dataclasses.replace(PROGRAM.transformer_config(CONFIG), n_layers=1)
    ftmesh = ft_init_mesh({"data": 1}, devices=jax.devices()[:1])
    ftmesh.manager = manager
    step = TrainStep(ftmesh, optax.sgd(0.01), lambda p, b: loss_and_counters(p, b, cfg),
                     loss_has_counters=True, overlap_commit=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = step.init_opt_state(params)
    try:
        for i in range(3):
            manager.start_quorum()
            params, opt, loss, committed = step.ft_step(params, opt, _batch(i))
            assert committed and np.isfinite(float(loss))
        counts = np.asarray(step.last_counters["moe_tokens_per_expert"])
    finally:
        manager.shutdown()
    assert counts.shape == (1, 8) and counts.sum() == 2 * 128 * 2
    first, second, third = _records(path, "step_summary")
    assert "moe_tokens_per_expert_max" not in first
    for summary in (second, third):
        assert summary["counters_step"] == summary["step"] - 1
        assert summary["moe_tokens_per_expert_mean"] == 64.0 and summary["moe_dropped"] == 0
        assert 64 <= summary["moe_tokens_per_expert_max"] <= 512
    subs = [s for r in _records(path, "subspan") for s in r["spans"]]
    notes = [s for s in subs if s["name"] == "counters_note"]
    assert len(notes) == 2 and all(s["parent"] == "ft_step" for s in notes)
    # the split form keeps them too, and grads() still returns (loss, grads)
    loss, grads = step.grads(params, _batch(9))
    assert loss.shape == () and jax.tree.structure(grads) == jax.tree.structure(params)
    assert int(step.last_counters["moe_dropped"]) == 0


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
    batch = _batch(0, dict(vocab_size=128), 2, 64)

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
    loss, counters = loss_and_counters(params, _batch(0, dict(vocab_size=128), 2, 64), cfg)
    assert counters == {} and np.isfinite(float(loss))
