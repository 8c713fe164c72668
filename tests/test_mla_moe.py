"""Moonlight-shaped models (DeepSeek-V3 family: latent attention, a leading
dense layer, the bias-corrected sigmoid router, a shared expert, one chip's
share of the routed experts on the dropless path) through the program, on the
CPU at small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/mla_moe_lm.py``).  What only this
architecture has is tested here: the router against a hand-written one on ties,
the dropless path's row moves against their plain statements (the attention
kernels at its unequal head widths: `tests/test_attention_unequal_widths.py`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, HELD, REMAT, Architecture, ExpertLayer, Tiny, batches, equations, in_the_scan, omission_cases, pytest_generate_tests, store,
    test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_rematerialised_layers_give_the_gradients_of_the_stored_ones, test_the_shares_add_up_to_the_uncut_layer,
    test_the_tree_goes_through, test_the_tree_is_the_reference_s)
from torchft_tpu.models.moe import _dropless_ffn, _take_rows, _tokens_of_rows, held_rows, moe_layer, route
from torchft_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, padded_group_sizes

REFERENCE = BENCH.reference("mla_moe_lm")
PROGRAM = BENCH.program("mla_moe_lm")

SEQ = 128
SIZES = """128 positions, the small model's whole `max_position_embeddings`.  One dense and two sparse layers: the
leading dense stack and a sparse stack of more than one layer, the least with both.  4 heads of 32 + 16 | 32 over a
latent of 64, 8 routed experts, 2 a token, one shared expert of twice their width.  Float32 throughout."""
CONFIG = dict(
    architecture="mla_moe_lm", vocab_size=384, hidden_size=128, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    kv_lora_rank=64, q_lora_rank=None, intermediate_size=256, moe_intermediate_size=64, n_routed_experts=8,
    n_shared_experts=2, num_experts_per_tok=2, norm_topk_prob=True, scoring_func="sigmoid", topk_method="noaux_tc",
    n_group=1, topk_group=1, routed_scaling_factor=2.446, seq_aux=True, aux_loss_alpha=0.001,
    max_position_embeddings=128, rope_theta=5e4, rms_norm_eps=1e-5,
    router_bias=dict(seed=31, scale=0.05),
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
    program=dict(remat=False, scan_unroll=4),
)
# The same model as one of the four chips that share each layer holds it:
# experts 2 and 3 of the router's 8.
SHARE = dict(CONFIG, n_routed_experts=2, expert_parallel=dict(chips=4, rank=1, router_outputs=8, first_expert_held=2))


_batch = batches(CONFIG["vocab_size"], SEQ)


# What the program would compute with one part of the published mathematics
# left out: each has to fail the comparison that the whole passes.
OMISSIONS = {
    "as_published": {},
    "without_the_choice_bias": {"router_bias": None},
    "without_the_scaling_factor": {"moe_route_scale": 1.0},
    "top_k_not_renormalised": {"moe_norm_topk": False},
    "without_the_balance_loss": {"moe_aux_coef": 0.0},
    "the_fixed_epsilon": {"rms_eps": 1e-6},
}


def _weights(weights, variant):
    # At unit-scale activations 1e-5 against 1e-6 is 5e-6 relative: seen
    # only where the norm's input is small, as after a shrunken embedding.
    return dict(weights, embed=weights["embed"] * 0.02) if variant == "the_fixed_epsilon" else weights


def _counters(counters, config) -> None:
    assert int(counters["moe_dropped"]) == 0
    assert np.asarray(counters["moe_tokens_per_expert"]).sum(axis=1).tolist() == [2 * SEQ * 2] * 2


def _tree_facts(cfg, own) -> None:
    """The leading dense layer is stacked apart from the sparse ones; the
    router's bias is a leaf of neither."""
    assert own["dense_layers"]["w_gate"].shape == (1, 128, 256)
    assert own["layers"]["w_gate"].shape == (2, 2, 128, 64) and own["layers"]["router"].shape == (2, 128, 8)
    assert own["layers"]["shared_up"].shape == (2, 128, 128) and own["layers"]["wkv_a"].shape == (2, 128, 64 + 16)
    assert not any("bias" in jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(own))


# -- the router ------------------------------------------------------------------


def _hand_route(logits, bias, k, scale):
    """Sigmoid scores, the k largest of score + bias (the lower expert id
    first among equals), gates the chosen scores renormalised times scale."""
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    choice = scores + bias
    idx = np.stack([np.lexsort((np.arange(choice.shape[-1]), -row))[:k] for row in choice])
    gates = np.take_along_axis(scores, idx, axis=-1)
    return idx, gates / gates.sum(-1, keepdims=True) * scale


@pytest.mark.parametrize("with_bias", [False, True], ids=["no_bias", "bias"])
def test_route_against_a_hand_written_one_on_ties(with_bias) -> None:
    """Logits drawn from five values, so that most positions have ties at the
    k-th place; the bias breaks some and makes others.  A bias changes the
    choice and never a gate."""
    rng = np.random.default_rng(5)
    values = np.array([-1.0, -0.5, 0.0, 0.5, 1.0], np.float32)
    logits = values[rng.integers(0, 5, size=(64, 16))]
    bias = (np.array([0.0, 0.25])[rng.integers(0, 2, size=16)]).astype(np.float32) if with_bias else None
    # x @ router == logits exactly: x one-hot rows, router the logits.
    x = jnp.eye(64, dtype=jnp.float32)[None]
    _, probs, gates, idx = route(x, jnp.asarray(logits), 4, True, score="sigmoid",
                                 bias=None if bias is None else jnp.asarray(bias), scale=2.446)
    want_idx, want_gates = _hand_route(logits, 0.0 if bias is None else bias, 4, 2.446)
    ties = sum(len(set(np.round(row, 6))) < 16 for row in logits)
    assert ties == 64
    np.testing.assert_array_equal(np.asarray(idx[0]), want_idx)
    np.testing.assert_allclose(np.asarray(gates[0]), want_gates, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates[0]).sum(-1), 2.446, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(probs[0]).sum(-1), 1.0, rtol=1e-6)
    if with_bias:
        plain_idx, _ = _hand_route(logits, 0.0, 4, 2.446)
        assert (plain_idx != want_idx).any()


def _gathered_route(x, router, k, bias, scale):
    """`route`'s sigmoid branch as it was before the gates were picked by
    comparison: the chosen scores by `take_along_axis`."""
    logits = jnp.einsum("bse,ex->bsx", x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, idx = jax.lax.top_k(choice, k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20) * scale, idx


def _primitives(jaxpr) -> set:
    return {eqn.primitive.name for eqn in equations(jaxpr)}


@pytest.mark.parametrize("with_bias", [False, True], ids=["no_bias", "bias"])
def test_sigmoid_gates_are_picked_by_comparison_and_equal_the_gathered_ones(with_bias) -> None:
    """The chosen scores as a masked sum over the experts: bit for bit what
    `take_along_axis` gathers (one term is not zero), the same gradient (the
    k indices are distinct, so the scatter-add it replaces added nothing
    twice), and neither direction gathers or scatters a scalar."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], (2, 48, 32), jnp.float32)
    router = jax.random.normal(ks[1], (32, 64), jnp.float32) * 32 ** -0.5
    bias = jax.random.normal(ks[2], (64,), jnp.float32) * 0.05 if with_bias else None
    ct = jax.random.normal(ks[3], (2, 48, 6), jnp.float32)

    def picked(x, router):
        return route(x, router, 6, True, score="sigmoid", bias=bias, scale=2.446)[2:]

    def gathered(x, router):
        return _gathered_route(x, router, 6, bias, 2.446)

    (got, got_idx), got_vjp = jax.vjp(picked, x, router)
    (want, want_idx), want_vjp = jax.vjp(gathered, x, router)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(want_idx))
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    ct = (ct, np.zeros(got_idx.shape, jax.dtypes.float0))
    for a, b in zip(got_vjp(ct), want_vjp(ct)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)

    moved = {"gather", "scatter", "scatter-add"}
    forward = _primitives(jax.make_jaxpr(picked)(x, router).jaxpr)
    backward = _primitives(jax.make_jaxpr(lambda x, r: jax.vjp(picked, x, r)[1](ct))(x, router).jaxpr)
    assert "top_k" in forward and not forward & moved, forward & moved
    assert not backward & moved, backward & moved
    # the oracle is the form that does: the check can see one
    assert "gather" in _primitives(jax.make_jaxpr(gathered)(x, router).jaxpr)
    assert "scatter-add" in _primitives(jax.make_jaxpr(lambda x, r: jax.vjp(gathered, x, r)[1](ct))(x, router).jaxpr)


# -- one chip's share of an expert-parallel layer ---------------------------------

def _layer_inputs(seed=7, tokens=96, hidden=128, inner=64, n_exp=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, shape, fan: jax.random.normal(k, shape, jnp.float32) * fan ** -0.5  # noqa: E731
    x = jax.random.normal(ks[0], (2, tokens // 2, hidden), jnp.float32)
    w = dict(router=normal(ks[1], (hidden, n_exp), hidden), w_gate=normal(ks[2], (n_exp, hidden, inner), hidden),
             w_up=normal(ks[3], (n_exp, hidden, inner), hidden), w_down=normal(ks[4], (n_exp, inner, hidden), inner),
             shared_gate=normal(ks[5], (hidden, 2 * inner), hidden), shared_up=normal(ks[6], (hidden, 2 * inner), hidden),
             shared_down=normal(ks[7], (2 * inner, hidden), 2 * inner))
    bias = jnp.asarray(np.random.default_rng(seed).standard_normal(n_exp).astype(np.float32) * 0.05)
    return x, w, bias


def _share(x, w, bias, first, count, shared=False, **kwargs):
    return moe_layer(
        x, w["router"], w["w_gate"][first:first + count], w["w_up"][first:first + count],
        w["w_down"][first:first + count], top_k=3, capacity_factor=None, norm_topk=True, score="sigmoid",
        route_bias=bias, route_scale=2.446, held_first=first, dtype=jnp.float32,
        shared=(w["shared_gate"], w["shared_up"], w["shared_down"]) if shared else None, **kwargs)


def _expert_layer() -> ExpertLayer:
    x, w, bias = _layer_inputs()
    s = REFERENCE.sizes_of(dict(CONFIG, num_experts_per_tok=3))
    assert (s["held"], s["experts"], s["first"]) == (8, 8, 0)

    def share(first, count, with_shared, x):
        return _share(x, w, bias, first, count, shared=with_shared)

    def uncut(x):
        return jnp.stack([REFERENCE._experts(seq, w, bias, s, "float32")[0] for seq in x]), None

    return ExpertLayer((x,), 8, share, uncut, 96 * 3, shared=True)


def test_a_share_whose_buffer_is_full_counts_what_it_drops() -> None:
    """A row buffer of a tenth of the even share: the assignments to HELD
    experts beyond it are counted as dropped (those to experts held elsewhere
    never are), and what has a row is computed as before."""
    x, w, bias = _layer_inputs(tokens=1024)
    _, full = jax.jit(lambda x: _share(x, w, bias, 2, 2))(x)
    y, tight = jax.jit(lambda x: _share(x, w, bias, 2, 2, held_rows_factor=0.1))(x)
    rows = held_rows(1024 * 3, 8, 2, 0.1)
    assert rows == 384 and held_rows(1024 * 3, 8, 8, 0.1) == 1024 * 3 + 8 * 128  # every expert held: no bound
    assert int(full["dropped"]) == 0 and int(tight["rows_held"]) == int(full["rows_held"]) > rows
    assert 0 < int(tight["dropped"]) <= int(tight["rows_held"])
    assert bool(jnp.all(jnp.isfinite(y)))


def _t_major_ffn(xf, gate_vals, gate_idx, w_gate, w_up, w_down, *, n_exp, first, rows_factor):
    """`_dropless_ffn` as it was before the k choices led: the same
    assignment -> row table, a token's rows gathered as [T, k, E] and
    weighted over the middle axis, and JAX's own derivatives (a scatter-add
    for each gather) in place of the hand-written ones."""
    tokens, k = gate_idx.shape
    count, n_assign = w_gate.shape[0], tokens * k
    rows = held_rows(n_assign, n_exp, count, rows_factor, ROW_TILE)
    mine = (gate_idx.reshape(n_assign)[:, None] == jnp.arange(first, first + count)[None, :]).astype(jnp.int32)
    arrived = jnp.cumsum(mine, axis=0)
    sizes = padded_group_sizes(arrived[-1], ROW_TILE)
    dest = jnp.sum((jnp.cumsum(sizes) - sizes)[None, :] * mine, axis=1) + jnp.sum(arrived * mine, axis=1) - 1
    here = jnp.sum(mine, axis=1) > 0
    dropped = jnp.sum((here & (dest >= rows)).astype(jnp.int32))
    dest = jnp.where(here & (dest < rows), dest, rows + jnp.arange(n_assign))
    row_token = jnp.full((rows,), tokens, jnp.int32).at[dest].set(jnp.arange(n_assign, dtype=jnp.int32) // k)
    xs = jnp.take(xf, row_token, axis=0, mode="clip")
    hidden = jax.nn.silu(grouped_matmul(xs, w_gate, sizes, row_tile=ROW_TILE)) * grouped_matmul(
        xs, w_up, sizes, row_tile=ROW_TILE)
    out = grouped_matmul(hidden, w_down, sizes, row_tile=ROW_TILE)
    picked = jnp.take(out, dest.reshape(tokens, k), axis=0, mode="fill", fill_value=0)  # [T, k, E]
    return jnp.einsum("tke,tk->te", picked, gate_vals), jnp.sum(arrived[-1]), dropped


@pytest.mark.parametrize("held", ["every_expert_held", "an_eighth_with_rows_past_the_buffer"])
@pytest.mark.parametrize("k", [6, 8])
def test_k_major_row_moves_match_the_t_major_form(k, held) -> None:
    """The layer's output and its gradients with respect to the
    activations, the gates and the three expert matrices, against the
    [T, k, E] form differentiated by JAX: at 6 choices (no multiple of a
    tile's 8 rows: the k axis leads) and at 8 (it does not), with every
    assignment in a row and with a share whose buffer is too small for
    some."""
    n_exp, tokens, hidden, inner = 16, 512, 64, 32
    picked = jax.eval_shape(lambda: _take_rows(jnp.zeros((8, hidden)), jnp.zeros((tokens, k), jnp.int32), True))
    assert picked.shape == ((k, tokens, hidden) if k == 6 else (tokens, k, hidden))
    first, count, factor = (0, n_exp, 2.0) if held == "every_expert_held" else (4, 2, 0.25)
    ks = jax.random.split(jax.random.PRNGKey(36 + k), 7)
    normal = lambda key, shape, fan: jax.random.normal(key, shape, jnp.float32) * fan ** -0.5  # noqa: E731
    xf = jax.random.normal(ks[0], (tokens, hidden), jnp.float32)
    w = (normal(ks[1], (count, hidden, inner), hidden), normal(ks[2], (count, hidden, inner), hidden),
         normal(ks[3], (count, inner, hidden), inner))
    gate_vals, gate_idx = jax.lax.top_k(jax.nn.sigmoid(jax.random.normal(ks[4], (tokens, n_exp), jnp.float32)), k)
    ct = jax.random.normal(ks[5], (tokens, hidden), jnp.float32)

    def k_major(xf, gate_vals, *w):
        # the fourth result is ReLU's count of live units: None under SiLU
        return _dropless_ffn(xf, gate_vals, gate_idx, *w, n_exp=n_exp, first=first, rows_factor=factor, mesh=None)[:3]

    def t_major(xf, gate_vals, *w):
        return _t_major_ffn(xf, gate_vals, gate_idx, *w, n_exp=n_exp, first=first, rows_factor=factor)

    with jax.default_matmul_precision("highest"):
        (got, got_held, got_dropped), got_vjp = jax.vjp(k_major, xf, gate_vals, *w)
        (want, want_held, want_dropped), want_vjp = jax.vjp(t_major, xf, gate_vals, *w)
        zero = np.zeros((), jax.dtypes.float0)
        got_grads, want_grads = got_vjp((ct, zero, zero)), want_vjp((ct, zero, zero))
    assert int(got_held) == int(want_held) and int(got_dropped) == int(want_dropped)
    if held == "every_expert_held":
        assert int(got_held) == tokens * k and int(got_dropped) == 0
    else:
        assert 0 < int(got_dropped) < int(got_held) < tokens * k
    assert float(jnp.max(jnp.abs(want))) > 0.1
    # float32 rounding: the order of a sum's addends is all that differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    for name, a, b in zip(("xf", "gate_vals", "w_gate", "w_up", "w_down"), got_grads, want_grads):
        assert float(jnp.max(jnp.abs(b))) > 0.1, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5, err_msg=name)


def _row_table(gate_idx, first, count, n_rows, row_tile):
    """The assignment -> row table written out on the host: the assignments
    to held expert `first`, `first + 1`, ... in turn, each expert's rows from
    a tile boundary on; an assignment to any other choice, or past the
    buffer's end, has no row and a `dest` of its own past the end, and a row
    none landed in carries `T * k`."""
    n_assign = gate_idx.size
    expert, assignment = np.asarray(gate_idx).reshape(n_assign), np.arange(n_assign)
    dest, start = np.full(n_assign, -1), 0
    for e in range(first, first + count):
        mine = np.flatnonzero(expert == e)
        dest[mine] = start + np.arange(mine.size)
        start += -(-mine.size // row_tile) * row_tile
    has_row = (dest >= 0) & (dest < n_rows)
    dest = np.where(has_row, dest, n_rows + assignment)
    row_assignment = np.full(n_rows, n_assign)
    row_assignment[dest[has_row]] = assignment[has_row]
    return dest.reshape(gate_idx.shape).astype(np.int32), row_assignment.astype(np.int32), has_row.reshape(gate_idx.shape)


# (choices a token, the router's outputs, first held, held, buffer factor, the last output takes no expert)
COMBINES = {
    "top6_of_64_with_8_held_k_major": (6, 64, 0, 8, 2.0, False),
    "top8_of_128_with_16_held": (8, 128, 16, 16, 2.0, False),
    "top8_of_16_all_held": (8, 16, 0, 16, 2.0, False),
    "top1_of_17_with_8_held_and_the_choice_that_takes_none": (1, 17, 0, 8, 2.0, True),
    "top6_of_64_with_8_held_and_a_buffer_too_small": (6, 64, 8, 8, 0.25, False),
}


@pytest.mark.parametrize("case", list(COMBINES))
def test_the_combines_gradients_against_a_plain_statement_of_it(case) -> None:
    """`_tokens_of_rows`' own derivatives — the gates' taken on the row side
    and scattered through `row_assignment`, the rows' a gather of the
    cotangent — against JAX's of `y[t] = sum_j gates[t, j] * rows[dest[t, j]]`
    in float32 (zero where `dest` is past the buffer), on bf16 rows and
    cotangents as the program has them: a gate's gradient to float32
    rounding, exactly zero where its assignment has no row; the rows'
    cotangent bit for bit the float32 product rounded once."""
    k, outputs, first, count, factor, skip = COMBINES[case]
    tokens, width, row_tile = 256, 64, 8
    n_exp, n_assign = outputs - int(skip), tokens * k
    every_row_exists = count == n_exp and not skip
    n_rows = held_rows(n_assign, n_exp, count, factor, row_tile)
    ks = jax.random.split(jax.random.PRNGKey(45 + k + outputs), 4)
    gates, gate_idx = jax.lax.top_k(jax.nn.sigmoid(jax.random.normal(ks[0], (tokens, outputs), jnp.float32)), k)
    dest, row_assignment, has_row = _row_table(gate_idx, first, count, n_rows, row_tile)
    assert np.any(row_assignment == n_assign) and bool(has_row.all()) == every_row_exists  # some rows stay empty
    if "too_small" in case:  # assignments to held experts that found the buffer full
        held = (np.asarray(gate_idx) >= first) & (np.asarray(gate_idx) < first + count)
        assert 0 < int(np.sum(held & ~has_row)) < int(np.sum(held))
    rows = jax.random.normal(ks[1], (n_rows, width), jnp.float32).astype(jnp.bfloat16)
    dy = jax.random.normal(ks[2], (tokens, width), jnp.float32).astype(jnp.bfloat16)
    dest, row_assignment = jnp.asarray(dest), jnp.asarray(row_assignment)

    def plain(rows32, gates):
        picked = jnp.take(rows32, dest, axis=0, mode="fill", fill_value=0)  # [T, k, E]
        return jnp.sum(picked * gates[:, :, None], axis=1)

    want, want_vjp = jax.vjp(plain, rows.astype(jnp.float32), gates)
    want_drows, want_dgates = want_vjp(dy.astype(jnp.float32))
    got, got_vjp = jax.vjp(lambda r, g: _tokens_of_rows(r, g, dest, row_assignment, every_row_exists, False), rows, gates)
    got_drows, got_dgates = got_vjp(dy)

    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=2 ** -7, atol=2 ** -7)
    assert got_dgates.shape == (tokens, k) and got_dgates.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want_dgates)))
    assert scale > 1.0
    np.testing.assert_allclose(np.asarray(got_dgates), np.asarray(want_dgates), rtol=1e-5, atol=1e-5 * scale)
    assert np.array_equal(np.asarray(got_dgates) != 0, has_row)  # the same positions, exact zeros elsewhere
    # A row's cotangent: its token's, weighted by its gate in float32 and rounded once (zero where none landed).
    row_gate = jnp.take(gates.reshape(-1), row_assignment, mode="fill", fill_value=0)
    rounded_once = (jnp.take(dy, row_assignment // k, axis=0, mode="clip").astype(jnp.float32) * row_gate[:, None]).astype(rows.dtype)
    assert got_drows.dtype == rows.dtype and np.array_equal(np.asarray(got_drows, np.float32), np.asarray(rounded_once, np.float32))
    np.testing.assert_array_equal(np.asarray(want_drows.astype(jnp.bfloat16), np.float32), np.asarray(got_drows, np.float32))


# -- the two-kind tree through TrainStep, the exchange's plan and the checkpoint ---


def _tiny() -> Tiny:
    """`moe_rows_held` and `moe_assignments` ride the next step's summary
    beside the counters every sparse model has, through the benchmark's own
    programs file."""
    def facts(moved, summaries, step, after) -> None:
        for summary in summaries[1:]:
            assert summary["moe_assignments"] == 2 * SEQ * 2 * 2 and summary["moe_dropped"] == 0
            assert 0 < summary["moe_rows_held"] < summary["moe_assignments"]
            assert summary["moe_tokens_per_expert_mean"] == 2 * SEQ * 2 / 8
        # every expert held: the two counters of a share are not there
        _, counters = jax.jit(PROGRAM.loss(CONFIG))(REFERENCE.make_weights(2, CONFIG), _batch(0))
        assert set(counters) == {"moe_tokens_per_expert", "moe_dropped"}

    return Tiny(lambda: REFERENCE.make_weights(2, SHARE), PROGRAM.loss(SHARE), _batch, 3, facts)


ARCH = Architecture(
    name="mla_moe_lm", configs=dict(zip(HELD, (CONFIG, SHARE))), sizes=SIZES, seq=SEQ, variants=dict(in_the_scan(OMISSIONS), **REMAT),
    leaf_cases=omission_cases(OMISSIONS, 11),
    # Both sides compute in float32 on the CPU, so they differ by the order of their sums alone: every leaf agrees to
    # under 1e-5 of its norm.  The least of the named omissions moves its leaf by far more (the balance loss, on the
    # router: 1e-3), so 3e-5 passes the one and fails the others.
    leaf_tolerance=3e-5, loss_tolerance=1e-6, weights=_weights, weights_vary=("the_fixed_epsilon",), counters=_counters,
    remat=("a_share_of_the_experts", 4, tuple(REMAT)),
    chips=[8, 4, 2, 1], expert_layer=_expert_layer, tree_config="a_share_of_the_experts", tree_facts=_tree_facts,
    through=("ft_step", "disk_checkpoint"), tiny=_tiny,
)
