"""Moonlight-shaped models (DeepSeek-V3 family) through the program, on the
CPU at small sizes.

The program (``models/transformer.py`` with latent attention, a leading dense
layer, the bias-corrected sigmoid router, a shared expert and one chip's
share of the routed experts on the dropless path) against the benchmark's
plain float32 reference (``benchmark/reference/mla_moe_lm.py``, which shares
no code with it) on seeded random weights; the attention kernels in interpret
mode at unequal head widths; the shares of an expert-parallel layer against
the uncut layer; the router against a hand-written one on ties; and the
two-kind parameter tree through the bucket plan, the disk checkpoint and
``TrainStep``'s counters.
"""

import dataclasses
import functools
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
from torchft_tpu.models import init_params  # noqa: E402
from torchft_tpu.models.moe import _dropless_ffn, _take_rows, _tokens_of_rows, held_rows, moe_layer, route  # noqa: E402
from torchft_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, padded_group_sizes  # noqa: E402
from torchft_tpu.models.transformer import loss_and_counters, param_axes  # noqa: E402
from torchft_tpu.parallel import TrainStep, ft_init_mesh  # noqa: E402

BENCH = Benchmark(ROOT)
REFERENCE = BENCH.reference("mla_moe_lm")
PROGRAM = BENCH.program("mla_moe_lm")

# One dense and two sparse layers of Moonlight's shape, float32 throughout:
# 4 heads of 32 + 16 | 32, a latent of 64, 8 routed experts, 2 a token, one
# shared expert of twice their width.
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
# Both sides compute in float32 on the CPU, so they differ by the order of
# their sums alone: every leaf agrees to under 1e-5 of its norm.  The least
# of the named omissions moves its leaf by far more (the balance loss, on the
# router: 1e-3), so 3e-5 passes the one and fails the others.
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
    "without_the_choice_bias": {"bias": None},
    "without_the_scaling_factor": {"moe_route_scale": 1.0},
    "top_k_not_renormalised": {"moe_norm_topk": False},
    "without_the_balance_loss": {"moe_aux_coef": 0.0},
    "the_fixed_epsilon": {"rms_eps": 1e-6},
}


@pytest.mark.parametrize("config", [CONFIG, SHARE], ids=["every_expert_held", "a_share_of_the_experts"])
@pytest.mark.parametrize("omission", list(OMISSIONS))
def test_loss_and_every_gradient_leaf_against_the_plain_reference(omission, config) -> None:
    seed = 11
    changed = dict(OMISSIONS[omission])
    bias = changed.pop("bias", jnp.asarray(REFERENCE.router_bias(config)))
    cfg = dataclasses.replace(PROGRAM.transformer_config(config), **changed)
    weights = REFERENCE.make_weights(seed, config)
    if omission == "the_fixed_epsilon":
        # At unit-scale activations 1e-5 against 1e-6 is 5e-6 relative: seen
        # only where the norm's input is small, as after a shrunken embedding.
        weights = dict(weights, embed=weights["embed"] * 0.02)
    batch = _batch(seed)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p, b: loss_and_counters(p, b, cfg, router_bias=bias), has_aux=True))(weights, batch)
    want_loss, want = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], config)
    leaf, rel = _worst_leaf(grads, want)
    loss_rel = abs(float(loss) - float(want_loss)) / float(want_loss)
    if omission == "as_published":
        assert rel < LEAF_TOLERANCE and loss_rel < LOSS_TOLERANCE, (leaf, rel, loss_rel)
        assert jax.tree.structure(grads) == jax.tree.structure(weights)
        assert int(counters["moe_dropped"]) == 0
        assert np.asarray(counters["moe_tokens_per_expert"]).sum(axis=1).tolist() == [2 * 128 * 2] * 2
    else:
        assert rel > 3 * LEAF_TOLERANCE, f"{omission}: the comparison did not see it ({leaf} {rel}, loss {loss_rel})"


@pytest.mark.parametrize("keeps_attention", [False, True], ids=["remat", "remat_that_keeps_attention"])
def test_rematerialised_layers_give_the_gradients_of_the_stored_ones(keeps_attention) -> None:
    """`remat`, with and without the policy that keeps each layer's attention
    output and row statistics: what is recomputed is not computed differently."""
    cfg = PROGRAM.transformer_config(SHARE)
    bias = jnp.asarray(REFERENCE.router_bias(SHARE))
    weights, batch = REFERENCE.make_weights(4, SHARE), _batch(4)

    def grads(cfg):
        return jax.jit(jax.value_and_grad(lambda p, b: loss_and_counters(p, b, cfg, router_bias=bias)[0]))(weights, batch)

    loss, stored = grads(cfg)
    again_loss, again = grads(dataclasses.replace(cfg, remat=True, remat_keeps_attention=keeps_attention))
    assert float(again_loss) == float(loss)
    leaf, rel = _worst_leaf(again, stored)
    assert rel < 1e-6, (leaf, rel)


def test_the_tree_has_leaves_of_two_kinds_and_no_bias() -> None:
    """The leading dense layer is stacked apart from the sparse ones; the
    program's own initialiser gives the tree the reference's weights have,
    shape for shape; the router's bias is a leaf of neither."""
    cfg = PROGRAM.transformer_config(SHARE)
    own = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    made = jax.eval_shape(lambda: REFERENCE.make_weights(1, SHARE))
    assert jax.tree.structure(own) == jax.tree.structure(made)
    assert [a.shape for a in jax.tree.leaves(own)] == [a.shape for a in jax.tree.leaves(made)]
    assert own["dense_layers"]["w_gate"].shape == (1, 128, 256)
    assert own["layers"]["w_gate"].shape == (2, 2, 128, 64) and own["layers"]["router"].shape == (2, 128, 8)
    assert own["layers"]["shared_up"].shape == (2, 128, 128) and own["layers"]["wkv_a"].shape == (2, 128, 64 + 16)
    assert not any("bias" in jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(own))
    axes = param_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, axes, is_leaf=lambda x: isinstance(x, tuple))) == \
        jax.tree.structure(own)


# -- attention at unequal head widths ------------------------------------------


@pytest.mark.parametrize("seq,two_pass", [(1024, False), (2560, False), (1024, True), (2560, True)],
                         ids=["one_pass_2_blocks", "one_pass_5_blocks", "two_pass_2_blocks", "two_pass_5_blocks"])
def test_attention_kernels_at_unequal_widths_in_interpret_mode(seq, two_pass, monkeypatch) -> None:
    """Query and key 256 wide (MLA's 192 padded to a lane multiple with zero
    columns), value 128: the kernels against the XLA formulation at the
    TRUE width of 192, forward and backward — the one-pass backward that
    every such row short of 32,768 positions takes, and the two-pass form
    with the row's VMEM budget cut under it."""
    from test_ops import ONE_PASS, TWO_PASS, pallas_call_names
    from torchft_tpu.ops import attention as fa

    if two_pass:
        monkeypatch.setattr(fa, "_DQ_ROW_VMEM_BUDGET", seq * 256 * 4 - 1)
    assert fa._dq_row_resident(seq, 256) != two_pass
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seq), 4)
    q, k = (jax.random.normal(kk, (2, seq, 192), jnp.float32) for kk in (k0, k1))
    v, g = (jax.random.normal(kk, (2, seq, 128), jnp.float32) for kk in (k2, k3))
    scale = 192 ** -0.5
    pad = [(0, 0), (0, 0), (0, 64)]
    qp, kp = jnp.pad(q, pad), jnp.pad(k, pad)
    want_o, want_lse = fa._fa_reference(q, k, v, scale, True)
    got_o, got_lse = fa._fa_pallas_call(qp, kp, v, scale, True, interpret=True)
    assert got_o.shape == (2, seq, 128)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_lse), np.asarray(want_lse), rtol=1e-5, atol=1e-5)
    want = fa._fa_bwd_xla(q, k, v, want_o, want_lse, g, scale, True)
    bwd = functools.partial(fa._fa_bwd_pallas, scale=scale, causal=True, interpret=True)
    assert pallas_call_names(bwd, qp, kp, v, got_o, got_lse, g) == (TWO_PASS if two_pass else ONE_PASS)
    got = bwd(qp, kp, v, got_o, got_lse, g)
    assert [a.shape for a in got] == [(2, seq, 256), (2, seq, 256), (2, seq, 128)]
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        a = np.asarray(a)
        if name != "dv":
            assert not a[..., 192:].any(), f"{name}: the padding columns carry a gradient"
            a = a[..., :192]
        np.testing.assert_allclose(a, np.asarray(b), rtol=2e-3, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "masked"])
@pytest.mark.parametrize("kv_group", [1, 8])
@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_the_kernels_walk_the_lower_triangle_at_unequal_widths(n, kv_group, masked) -> None:
    """Query and key 256 wide, value 128, n tiles a side: a grid step for
    each tile of the lower triangle, out, lse, dq, dk and dv the XLA
    formulation's (`test_ops.check_the_triangular_walk`)."""
    from test_ops import check_the_triangular_walk

    check_the_triangular_walk(n, 256, 128, kv_group, masked)


@pytest.mark.parametrize("program", ["dense_lm", "moe_lm", "mla_moe_lm"])
def test_the_one_pass_backward_is_booked_to_attention_by_its_name(program) -> None:
    """The benchmark attributes device time to attention by substring and
    `chip_smoke.has_kernel` by whole word: the one-pass kernel's name has to
    stay inside the first and is a name of its own to the second, and no
    `tpuft_fa_bwd_dq` is found in it (its absence from a trace is the
    evidence that the one-pass form ran)."""
    import chip_smoke

    op = "%tpuft_fa_bwd_dkdv_dq.7 = (bf16[32,8192,256]) custom-call(...), custom_call_target=\"tpu_custom_call\""
    assert BENCH.program(program).kernel_names()["attn"](op)
    assert "tpuft_fa_bwd_dq" not in op
    assert chip_smoke.has_kernel(op, "tpuft_fa_bwd_dkdv_dq") and "tpuft_fa_bwd_dkdv_dq" in chip_smoke.KERNELS
    assert not chip_smoke.has_kernel(op, "tpuft_fa_bwd_dkdv") and not chip_smoke.has_kernel(op, "tpuft_fa_bwd_dq")


def test_flash_attention_takes_a_value_width_of_its_own() -> None:
    """The public entry point off the TPU: [B, H, S, 48] queries and keys,
    [B, H, S, 32] values, the scale from the query's width, gradients of the
    operands' own shapes."""
    from torchft_tpu.ops import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k = (jax.random.normal(kk, (2, 4, 64, 48), jnp.float32) for kk in keys[:2])
    v = jax.random.normal(keys[2], (2, 4, 64, 32), jnp.float32)

    def plain(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 48 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    out = flash_attention(q, k, v)
    assert out.shape == (2, 4, 64, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain(q, k, v)), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


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


def _primitives(jaxpr, found=None) -> set:
    """Every primitive of a jaxpr and of the jaxprs inside its equations."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)  # a ClosedJaxpr holds one
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


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


@pytest.mark.parametrize("chips", [8, 4, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(chips) -> None:
    """What every chip of an expert-parallel layer computes of the routed
    experts, summed over the chips, plus the shared expert counted once, is
    what the uncut plain reference gives for the whole layer — values and
    the gradient of the input."""
    x, w, bias = _layer_inputs()
    count = 8 // chips
    s = REFERENCE.sizes_of(dict(CONFIG, num_experts_per_tok=3))
    assert (s["held"], s["experts"], s["first"]) == (8, 8, 0)

    def uncut(x):
        ys = [REFERENCE._experts(seq, w, bias, s, "float32")[0] for seq in x]
        return jnp.stack(ys)

    def summed(x):
        routed = sum(_share(x, w, bias, r * count, count)[0] for r in range(chips))
        shared = (jax.nn.silu(x @ w["shared_gate"]) * (x @ w["shared_up"])) @ w["shared_down"]
        return routed + shared

    with jax.default_matmul_precision("highest"):
        want, got = uncut(x), summed(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
        dwant = jax.grad(lambda x: jnp.sum(jnp.sin(uncut(x))))(x)
        dgot = jax.grad(lambda x: jnp.sum(jnp.sin(summed(x))))(x)
        np.testing.assert_allclose(np.asarray(dgot), np.asarray(dwant), rtol=1e-4, atol=1e-5)
    # a share with the shared expert is that share plus the shared expert
    with_shared = _share(x, w, bias, 0, count, shared=True)[0]
    alone = _share(x, w, bias, 0, count)[0]
    assert float(jnp.max(jnp.abs(with_shared - alone))) > 0.1
    # the counters: the shares' held rows are all the assignments, none dropped
    stats = [_share(x, w, bias, r * count, count)[1] for r in range(chips)]
    assert sum(int(st["rows_held"]) for st in stats) == int(stats[0]["assignments"]) == 96 * 3
    assert all(int(st["dropped"]) == 0 for st in stats)
    assert all(np.array_equal(st["tokens_per_expert"], stats[0]["tokens_per_expert"]) for st in stats)


def test_a_share_whose_buffer_is_full_counts_what_it_drops() -> None:
    """A row buffer of a tenth of the even share: the assignments to HELD
    experts beyond it are counted as dropped (those to experts held elsewhere
    never are), and what has a row is computed as before."""
    x, w, bias = _layer_inputs(tokens=1024)
    _, full = _share(x, w, bias, 2, 2)
    y, tight = _share(x, w, bias, 2, 2, held_rows_factor=0.1)
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
    got, got_vjp = jax.vjp(lambda r, g: _tokens_of_rows(r, g, dest, row_assignment, every_row_exists), rows, gates)
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


# -- the two-kind tree through the exchange's plan, the checkpoint and TrainStep ---


def test_bucket_plan_and_disk_checkpoint_carry_both_kinds_of_layer(tmp_path) -> None:
    from torchft_tpu.checkpointing.disk import DiskCheckpointer
    from torchft_tpu.ddp import plan_buckets

    weights = REFERENCE.make_weights(3, SHARE)
    leaves = jax.tree.leaves(weights)
    buckets = plan_buckets([(l.shape, l.dtype) for l in leaves], 1 << 18)
    placed = sorted(i for b in buckets for i in b.indices)
    assert placed == list(range(len(leaves))) and len(buckets) > 2
    ckpt = DiskCheckpointer(str(tmp_path))
    try:
        ckpt.save(4, {"params": weights})
        ckpt.wait()
        back = ckpt.restore(4)["params"]
    finally:
        ckpt.shutdown()
    assert jax.tree.structure(back) == jax.tree.structure(weights)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(jax.tree.leaves(back), leaves))


def _records(path, event):
    with open(path, encoding="utf-8") as f:
        return [r for r in map(json.loads, f) if r.get("event") == event]


def test_held_rows_land_in_the_step_summary(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    """ft_steps of the share under a real Manager, through the benchmark's own
    programs file: `moe_rows_held` and `moe_assignments` ride the next step's
    summary beside the counters every sparse model has."""
    path = tmp_path / "stream.jsonl"
    monkeypatch.setenv("TPUFT_METRICS_PATH", str(path))
    client = MagicMock()
    client._quorum.return_value = make_quorum()
    client.should_commit.return_value = True
    manager, _, _ = make_manager(store, client_mock=client)
    ftmesh = ft_init_mesh({"data": 1}, devices=jax.devices()[:1])
    ftmesh.manager = manager
    step = TrainStep(ftmesh, optax.adamw(1e-3), PROGRAM.loss(SHARE), loss_has_counters=True, overlap_commit=False)
    params = REFERENCE.make_weights(2, SHARE)
    opt = step.init_opt_state(params)
    try:
        for i in range(3):
            manager.start_quorum()
            params, opt, loss, committed = step.ft_step(params, opt, _batch(i))
            assert committed and np.isfinite(float(loss))
    finally:
        manager.shutdown()
    _, second, third = _records(path, "step_summary")
    for summary in (second, third):
        assert summary["moe_assignments"] == 2 * 128 * 2 * 2 and summary["moe_dropped"] == 0
        assert 0 < summary["moe_rows_held"] < summary["moe_assignments"]
        assert summary["moe_tokens_per_expert_mean"] == 2 * 128 * 2 / 8
    # every expert held: the two counters of a share are not there
    _, counters = jax.jit(PROGRAM.loss(CONFIG))(REFERENCE.make_weights(2, CONFIG), _batch(0))
    assert set(counters) == {"moe_tokens_per_expert", "moe_dropped"}
