"""Keye-shaped models (learned sparse attention over GQA with a per-head
QK-norm, softmax top-k experts, one chip's share of them) through the
program, on the CPU at small sizes.

The program (``models/transformer.py`` with an indexer in every layer,
``ops/sparse_attention.py``) against the benchmark's plain float32 reference
(``benchmark/reference/dsa_moe_lm.py``, which shares no code with it) on
seeded random weights, at more positions than ``topk`` so that the selection
bites; the exact count, causality and tie rule of the selection; the layer
with ``topk`` at the sequence length against the dense GQA layer the repo
already has; which loss term reaches which weight; the shares of the 8 chips
against the uncut layer under the softmax router; the five kernels in
interpret mode against the XLA formulation; rematerialisation; the padded
cross-entropy head; and the counters in the step summary.
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
from torchft_tpu.models.moe import moe_layer  # noqa: E402
from torchft_tpu.models.transformer import loss_and_counters  # noqa: E402
from torchft_tpu.ops import cross_entropy as ce  # noqa: E402
from torchft_tpu.ops import sparse_attention as sa  # noqa: E402
from torchft_tpu.parallel import TrainStep, ft_init_mesh  # noqa: E402

BENCH = Benchmark(ROOT)
REFERENCE = BENCH.reference("dsa_moe_lm")
PROGRAM = BENCH.program("dsa_moe_lm")

SEQ, TOPK = 96, 24
# Two layers of Keye's shape, float32 throughout: 4 query heads on 2 KV heads
# of 32 (hidden 64, so a head is NOT hidden / heads), an indexer of 3 heads of
# 16 that keeps 24 of up to 96 keys, 8 experts of 48, 2 a token.
CONFIG = dict(
    architecture="dsa_moe_lm", vocab_size=200, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, intermediate_size=128, moe_intermediate_size=48, num_experts=8,
    num_local_experts=8, num_experts_per_tok=2, norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    hidden_act="silu", attention_bias=False, tie_word_embeddings=False, sliding_window=None, use_sliding_window=False,
    rope_scaling=dict(mrope_section=[4, 6, 6], rope_type="default", type="default"),
    sa_config=dict(indexer_head_dim=16, indexer_num_heads=3, indexer_num_kv_heads=1, kv_chunk_size=512,
                   q_chunk_size=512, topk=TOPK),
    max_position_embeddings=128, rope_theta=1e7, rms_norm_eps=1e-6, router_aux_loss_coef=0.001, indexer_loss_coef=1.0,
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
    program=dict(remat=False, scan_unroll=4),
)
# One of the four chips that share each layer: experts 2 and 3 of the router's 8.
SHARE = dict(CONFIG, num_experts=2, num_local_experts=2,
             expert_parallel=dict(chips=4, rank=1, router_outputs=8, first_expert_held=2))
INDEXER = ("wi_q", "wi_k", "wi_k_norm", "wi_k_bias", "wi_w")
LEAF_TOLERANCE = 5e-5
LOSS_TOLERANCE = 2e-6


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


def _value_and_grad(cfg, weights, batch):
    return jax.jit(jax.value_and_grad(lambda p, b: loss_and_counters(p, b, cfg), has_aux=True))(weights, batch)


# What the program would compute with one part of section 1's mathematics
# left out: each has to fail the comparison that the whole passes.
OMISSIONS = {
    "as_published": {},
    "without_the_index_loss": {"dsa_loss_coef": 0.0},
    "without_the_balance_loss": {"moe_aux_coef": 0.0},
    "top_k_not_renormalised": {"moe_norm_topk": False},
    "without_the_per_head_qk_norm": {"qk_norm_per_head": False},
    "every_visible_key_kept": {"dsa_topk": SEQ},
    "one_key_fewer": {"dsa_topk": TOPK - 1},
}


@pytest.mark.parametrize("config", [CONFIG, SHARE], ids=["every_expert_held", "a_share_of_the_experts"])
@pytest.mark.parametrize("omission", list(OMISSIONS))
def test_loss_and_every_gradient_leaf_against_the_plain_reference(omission, config) -> None:
    seed = 7
    cfg = dataclasses.replace(PROGRAM.transformer_config(config), **OMISSIONS[omission])
    weights, batch = REFERENCE.make_weights(seed, config), _batch(seed)
    # a QK-norm weight of one leaves a gradient the omission would not show
    weights["layers"]["q_norm"] = weights["layers"]["q_norm"] * 1.5
    if omission == "without_the_per_head_qk_norm":
        params = {**weights, "layers": {k: v for k, v in weights["layers"].items() if k not in ("q_norm", "k_norm")}}
        want_loss, _ = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], config)
        (loss, _), _ = _value_and_grad(cfg, params, batch)
        assert abs(float(loss) - float(want_loss)) / float(want_loss) > 10 * LOSS_TOLERANCE
        return
    (loss, counters), grads = _value_and_grad(cfg, weights, batch)
    want_loss, want = REFERENCE.loss_and_grads(weights, batch["tokens"], batch["targets"], config)
    leaf, rel = _worst_leaf(grads, want)
    loss_rel = abs(float(loss) - float(want_loss)) / float(want_loss)
    if omission == "as_published":
        assert rel < LEAF_TOLERANCE and loss_rel < LOSS_TOLERANCE, (leaf, rel, loss_rel)
        assert jax.tree.structure(grads) == jax.tree.structure(weights)
        per_sequence = TOPK * (TOPK + 1) // 2 + (SEQ - TOPK) * TOPK
        assert int(counters["dsa_pairs_selected"]) == 2 * 2 * per_sequence
        assert int(counters["dsa_pairs_visible"]) == 2 * 2 * SEQ * (SEQ + 1) // 2
        assert float(counters["dsa_index_loss"]) > 0
        assert int(counters["moe_dropped"]) == 0
    else:
        assert rel > 3 * LEAF_TOLERANCE, f"{omission}: the comparison did not see it ({leaf} {rel}, loss {loss_rel})"


def _index_operands(seed, batch=2, seq=SEQ, heads=3, dim=16, ties=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.normal(ks[0], (batch, heads, seq, dim), jnp.float32)
    b = jax.random.normal(ks[1], (batch, seq, dim), jnp.float32)
    w = jax.random.normal(ks[2], (batch, seq, heads), jnp.float32)
    if ties:
        b = b.at[:, 10:40].set(b[:, 10:11])  # thirty keys with one index key: thirty equal scores a query
    return a, b.transpose(0, 2, 1), w


@pytest.mark.parametrize("ties", [False, True], ids=["distinct_scores", "tied_scores"])
@pytest.mark.parametrize("topk", [1, 24, 95, 96, 200])
def test_the_selection_is_exact_causal_and_breaks_ties_downwards(topk, ties) -> None:
    a, bt, w = _index_operands(3, ties=ties)
    scores = np.asarray(sa.index_scores(a, bt, w))
    keep = np.asarray(sa.selection_mask(jnp.asarray(scores), topk))
    t = np.arange(SEQ)
    assert (keep.sum(-1) == np.minimum(t + 1, topk)[None]).all()          # exactly min(t + 1, topk)
    assert not keep[:, t[:, None] < t[None, :]].any()                      # nothing ahead of the query
    for b in range(2):
        for q in (5, 30, 60, 95):
            order = sorted(range(q + 1), key=lambda s: (-scores[b, q, s], s))  # ties to the lower position
            assert set(np.flatnonzero(keep[b, q])) == set(order[:topk])
    # the reference's own selection is the same set
    for b in range(2):
        want = REFERENCE._selected(jnp.asarray(scores[b]), 0, topk)
        assert np.array_equal(np.asarray(want), keep[b])


def test_with_topk_at_the_sequence_length_the_layer_is_the_dense_gqa_layer() -> None:
    """Every visible key kept: the same loss, to rounding, as the model
    without an indexer, and the same gradient on every weight the two share."""
    cfg = dataclasses.replace(PROGRAM.transformer_config(CONFIG), dsa_topk=SEQ, dsa_loss_coef=0.0)
    dense = dataclasses.replace(cfg, dsa_index_heads=0)
    weights, batch = REFERENCE.make_weights(5, CONFIG), _batch(5)
    plain = {**weights, "layers": {k: v for k, v in weights["layers"].items() if k not in INDEXER}}
    (loss, _), grads = _value_and_grad(cfg, weights, batch)
    (dense_loss, _), dense_grads = _value_and_grad(dense, plain, batch)
    assert abs(float(loss) - float(dense_loss)) < 1e-6 * float(dense_loss)
    shared = {**grads, "layers": {k: v for k, v in grads["layers"].items() if k not in INDEXER}}
    leaf, rel = _worst_leaf(shared, dense_grads)
    assert rel < 1e-5, (leaf, rel)


@pytest.mark.parametrize("term", ["language_model_terms", "index_loss"])
def test_each_loss_term_reaches_its_own_weights_alone(term) -> None:
    """Cross-entropy and balance loss give the indexer's five leaves a zero
    gradient; the index loss gives every other leaf zero."""
    cfg = PROGRAM.transformer_config(SHARE)
    weights, batch = REFERENCE.make_weights(9, SHARE), _batch(9)

    def fn(p):
        whole, counters = loss_and_counters(p, batch, cfg)
        index = cfg.dsa_loss_coef * counters["dsa_index_loss"]
        return index if term == "index_loss" else whole - index

    grads = jax.jit(jax.grad(fn))(weights)
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        name = jax.tree_util.keystr(path)
        of_the_indexer = any(f"'{k}'" in name for k in INDEXER)
        norm = float(jnp.linalg.norm(leaf))
        if of_the_indexer == (term == "index_loss"):
            assert norm > 0, f"{name} has no gradient from the {term}"
        else:
            assert norm == 0.0, f"{name} has a gradient from the {term}: {norm}"
    # the reference agrees on who learns from what
    s = REFERENCE.sizes_of(SHARE)
    ref = jax.grad(lambda p: REFERENCE.loss(p, batch["tokens"][0], batch["targets"][0],
                                            dict(s, index_coef=0.0 if term != "index_loss" else 1.0,
                                                 aux_coef=s["aux_coef"] if term != "index_loss" else 0.0))
                   - (0.0 if term != "index_loss" else REFERENCE.loss(
                       p, batch["tokens"][0], batch["targets"][0], dict(s, index_coef=0.0, aux_coef=0.0))))(weights)
    for name in INDEXER:
        norm = float(jnp.linalg.norm(ref["layers"][name]))
        assert (norm > 0) == (term == "index_loss"), (name, norm)
    assert (float(jnp.linalg.norm(ref["layers"]["wq"])) > 1e-9) == (term != "index_loss")


@pytest.mark.parametrize("chips", [8, 4, 2, 1])
def test_the_softmax_shares_add_up_to_the_uncut_layer(chips) -> None:
    """What every chip of an expert-parallel layer computes of the routed
    experts under the SOFTMAX router with renormalised gates, summed over the
    chips, is what the uncut plain reference gives — values and the gradient
    of the input."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (2, 48, 64), jnp.float32)
    w = {"router": jax.random.normal(ks[1], (64, 8)) * 0.3, "w_gate": jax.random.normal(ks[2], (8, 64, 48)) * 0.12,
         "w_up": jax.random.normal(ks[3], (8, 64, 48)) * 0.12, "w_down": jax.random.normal(ks[4], (8, 48, 64)) * 0.14}
    count = 8 // chips
    s = REFERENCE.sizes_of(dict(CONFIG, num_experts_per_tok=3))

    def share(x, first):
        return moe_layer(x, w["router"], w["w_gate"][first:first + count], w["w_up"][first:first + count],
                         w["w_down"][first:first + count], top_k=3, capacity_factor=None, norm_topk=True,
                         score="softmax", held_first=first, dtype=jnp.float32)

    def uncut(x):
        return jnp.stack([REFERENCE._experts(seq, w, s, "float32")[0] for seq in x])

    def summed(x):
        return sum(share(x, r * count)[0] for r in range(chips))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(summed(x)), np.asarray(uncut(x)), rtol=1e-4, atol=1e-5)
        dwant = jax.grad(lambda x: jnp.sum(jnp.sin(uncut(x))))(x)
        dgot = jax.grad(lambda x: jnp.sum(jnp.sin(summed(x))))(x)
        np.testing.assert_allclose(np.asarray(dgot), np.asarray(dwant), rtol=1e-4, atol=1e-5)
    stats = [share(x, r * count)[1] for r in range(chips)]
    assert sum(int(st["rows_held"]) for st in stats) == int(stats[0]["assignments"]) == 96 * 3
    assert all(int(st["dropped"]) == 0 for st in stats)


# -- the kernels in interpret mode ------------------------------------------------


def _kernel_operands(seed=0, batch=1, heads=4, kv=2, seq=1024, d=128, j=3, di=64, ties=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (batch, heads, seq, d), bf)
    k = jax.random.normal(ks[1], (batch, kv, seq, d), bf)
    v = jax.random.normal(ks[2], (batch, kv, seq, d), bf)
    a = jax.random.normal(ks[3], (batch, j, seq, di), bf)
    b = jax.random.normal(ks[4], (batch, seq, di), bf)
    if ties:
        b = b.at[:, 100:140].set(b[:, 100:101])
    w = jax.random.normal(ks[5], (batch, seq, j), jnp.float32) * (j * di) ** -0.5
    g = jax.random.normal(ks[6], (batch, heads, seq, d), bf)
    return q, k, v, a, b.transpose(0, 2, 1), w, g


def _unpacked(mask, seq):
    """The packed lower triangle of (512, 512) tiles as a dense [B, S, S]."""
    tile, full, t = min(512, seq), np.zeros((mask.shape[0], seq, seq), np.int8), 0
    for i in range(seq // tile):
        for jj in range(i + 1):
            full[:, i * tile:(i + 1) * tile, jj * tile:(jj + 1) * tile] = np.asarray(mask[:, t])
            t += 1
    return full


@pytest.fixture(scope="module")
def kernel_run():
    """Every kernel once, in interpret mode, at 1,024 positions and topk 200
    with forty tied keys, and the XLA formulation beside it."""
    q, k, v, a, bt, w, g = _kernel_operands()
    topk, scale = 200, 128 ** -0.5
    tau, cut, z = sa._select_pallas(a, bt, w, topk, interpret=True)
    mask = sa._mask_pallas(a, bt, w, tau, cut, interpret=True)
    out, lse = sa._masked_flash_fwd(q, k, v, mask, scale, interpret=True)
    kl, da, dbt, dw = sa._index_loss_pallas(q, k, lse, a, bt, w, z, mask, scale, interpret=True)
    dq, dk, dv = sa._masked_flash_bwd(q, k, v, out, lse, g, mask, scale, interpret=True)
    xla_out, xla_loss, xla_selected = sa._dsa_xla(q, k, v, a, bt, w, topk, scale)
    return dict(locals())


def test_select_and_mask_kernels_give_lax_top_k_s_selection(kernel_run) -> None:
    r = kernel_run
    scores = sa.index_scores(r["a"], r["bt"], r["w"])
    want = np.asarray(sa.selection_mask(scores, r["topk"]))
    got = _unpacked(r["mask"], 1024) != 0
    assert np.array_equal(got, want)
    assert (got.sum(-1)[0] == np.minimum(np.arange(1024) + 1, r["topk"])).all()
    assert int(jnp.sum(r["mask"], dtype=jnp.int32)) == int(r["xla_selected"])
    z = jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(r["z"][..., 0]), np.asarray(z), atol=1e-5)


def test_masked_attention_kernels_against_the_xla_formulation(kernel_run) -> None:
    r = kernel_run
    np.testing.assert_allclose(np.asarray(r["out"], np.float32), np.asarray(r["xla_out"], np.float32), atol=0.03)
    g = r["g"].astype(jnp.float32)
    want = jax.grad(lambda q, k, v: jnp.sum(
        sa._dsa_xla(q, k, v, r["a"], r["bt"], r["w"], r["topk"], r["scale"])[0].astype(jnp.float32) * g),
        argnums=(0, 1, 2))(r["q"], r["k"], r["v"])
    for name, got, ref in zip("qkv", (r["dq"], r["dk"], r["dv"]), want):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.linalg.norm(got - ref) < 0.01 * np.linalg.norm(ref), name


def test_index_loss_kernel_gives_the_loss_and_its_gradient_in_one_pass(kernel_run) -> None:
    r = kernel_run
    assert abs(float(jnp.sum(r["kl"]) / 1024) - float(r["xla_loss"])) < 1e-5
    want = jax.grad(lambda a, bt, w: sa._dsa_xla(r["q"], r["k"], r["v"], a, bt, w, r["topk"], r["scale"])[1],
                    argnums=(0, 1, 2))(r["a"], r["bt"], r["w"])
    for name, got, ref in zip(("a", "bt", "w"), (r["da"], r["dbt"], r["dw"]), want):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.linalg.norm(got - ref) < 0.01 * np.linalg.norm(ref), name


@pytest.mark.parametrize("heads,kv", [(2, 2), (8, 1)], ids=["kv_group_1", "kv_group_8"])
@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_the_five_kernels_at_n_tiles_a_side_against_the_xla_formulation(n, heads, kv) -> None:
    """512 n positions: the selection kernels' mask is `lax.top_k`'s, the
    attention kernels under it (a step for each tile of the lower triangle,
    `kv_group` query heads reading one KV head in place) give `_dsa_xla`'s
    out, dq, dk, dv, and the index-loss kernel (256 x 512 tiles, walked the
    same way) its loss and the loss's gradient."""
    seq, topk, scale = 512 * n, 200, 128 ** -0.5
    q, k, v, a, bt, w, g = _kernel_operands(seed=n, heads=heads, kv=kv, seq=seq, j=2, ties=False)
    tau, cut, z = sa._select_pallas(a, bt, w, topk, interpret=True)
    mask = sa._mask_pallas(a, bt, w, tau, cut, interpret=True)
    assert mask.shape == (1, n * (n + 1) // 2, 512, 512)
    want_mask = sa.selection_mask(sa.index_scores(a, bt, w), topk)
    assert np.array_equal(_unpacked(mask, seq) != 0, np.asarray(want_mask))
    out, lse = sa._masked_flash_fwd(q, k, v, mask, scale, interpret=True)
    dq, dk, dv = sa._masked_flash_bwd(q, k, v, out, lse, g, mask, scale, interpret=True)
    kl, da, dbt, dw = sa._index_loss_pallas(q, k, lse, a, bt, w, z, mask, scale, interpret=True)
    gf = g.astype(jnp.float32)

    def both(q, k, v, a, bt, w):
        xla_out, xla_loss, _ = sa._dsa_xla(q, k, v, a, bt, w, topk, scale)
        return jnp.sum(xla_out.astype(jnp.float32) * gf), (xla_out, xla_loss)

    (_, (xla_out, xla_loss)), want = jax.value_and_grad(both, argnums=(0, 1, 2), has_aux=True)(q, k, v, a, bt, w)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(xla_out, np.float32), atol=0.03)
    assert abs(float(jnp.sum(kl) / seq) - float(xla_loss)) < 1e-5
    want += jax.grad(lambda a, bt, w: sa._dsa_xla(q, k, v, a, bt, w, topk, scale)[1], argnums=(0, 1, 2))(a, bt, w)
    for name, got, ref in zip(("q", "k", "v", "a", "bt", "w"), (dq, dk, dv, da, dbt, dw), want):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.linalg.norm(got - ref) < 0.01 * np.linalg.norm(ref), name


@pytest.mark.parametrize("seq", [4096, 8192, 32768])
def test_the_selection_kernels_grids_at_the_cells_lengths(seq) -> None:
    """`tpuft_dsa_mask` and `tpuft_dsa_index_loss` traced at the cells'
    lengths (nothing runs): a step for each 256 x 512 tile that holds a
    visible pair — key tiles 0 .. qi // 2 under query tile qi — and the
    row's sums are emitted at the last of them."""
    from test_ops import pallas_call_grids

    n = seq // 512
    visible = sum(qi // 2 + 1 for qi in range(seq // 256))
    assert visible == n * (n + 1)
    bf, f32 = jnp.bfloat16, jnp.float32
    a, bt, w = (jax.ShapeDtypeStruct(s, t) for s, t in (((1, 16, seq, 64), bf), ((1, 64, seq), bf), ((1, seq, 16), f32)))
    row = jax.ShapeDtypeStruct((1, seq, 1), jnp.int32)
    assert pallas_call_grids(sa._mask_pallas, a, bt, w, row, row) == {"tpuft_dsa_mask": (1, visible)}
    q, k = (jax.ShapeDtypeStruct((1, h, seq, 128), bf) for h in (32, 4))
    lse, z = jax.ShapeDtypeStruct((1, 32, seq), f32), jax.ShapeDtypeStruct((1, seq, 1), f32)
    mask = jax.ShapeDtypeStruct((1, n * (n + 1) // 2, 512, 512), jnp.int8)
    assert pallas_call_grids(lambda *ops: sa._index_loss_pallas(*ops, 0.088), q, k, lse, a, bt, w, z, mask) == {
        "tpuft_dsa_index_loss": (1, visible)}
    walk = sa._walk(seq)
    rows, cols = (np.asarray(t) for t in walk.tables)
    assert (cols <= rows // 2).all() and (np.diff(rows) >= 0).all() and rows[-1] == seq // 256 - 1
    assert all(int(walk.last_k(qi)) == qi // 2 for qi in (0, 1, 2, seq // 256 - 1))


def test_the_kernels_path_is_one_custom_vjp_with_the_right_partners(monkeypatch) -> None:
    """`sparse_attention` on the kernels' path (interpret mode under the
    gate): out's cotangent reaches q, k, v alone, the loss's a, b, w alone."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(sa._pallas_util, "on_tpu", lambda: True)
    q, k, v, a, bt, w, g = _kernel_operands(seed=2, seq=512, ties=False)
    args = (q, k, v, a, bt.transpose(0, 2, 1), w)

    def out_term(*args):
        return jnp.sum(sa.sparse_attention(*args, topk=100)[0].astype(jnp.float32) * g.astype(jnp.float32))

    def loss_term(*args):
        return sa.sparse_attention(*args, topk=100)[1]

    d_out = jax.grad(out_term, argnums=tuple(range(6)))(*args)
    d_loss = jax.grad(loss_term, argnums=tuple(range(6)))(*args)
    norms = lambda t: [float(jnp.linalg.norm(x.astype(jnp.float32))) for x in t]  # noqa: E731
    assert all(n > 0 for n in norms(d_out[:3])) and norms(d_out[3:]) == [0.0, 0.0, 0.0]
    assert norms(d_loss[:3]) == [0.0, 0.0, 0.0] and all(n > 0 for n in norms(d_loss[3:]))
    monkeypatch.setattr(sa._pallas_util, "on_tpu", lambda: False)
    want = jax.grad(loss_term, argnums=(3, 4, 5))(*args)
    for got, ref in zip(d_loss[3:], want):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.linalg.norm(got - ref) < 0.02 * np.linalg.norm(ref)


@pytest.mark.parametrize("keeps_attention", [False, True], ids=["remat", "remat_that_keeps_attention"])
def test_rematerialised_layers_give_the_gradients_of_the_stored_ones(keeps_attention) -> None:
    cfg = PROGRAM.transformer_config(SHARE)
    weights, batch = REFERENCE.make_weights(4, SHARE), _batch(4)
    (loss, _), stored = _value_and_grad(cfg, weights, batch)
    (again_loss, _), again = _value_and_grad(
        dataclasses.replace(cfg, remat=True, remat_keeps_attention=keeps_attention), weights, batch)
    # the same float32 sums; XLA:CPU contracts one product of RoPE's `x * cos + swapped * sin` into the sum, and
    # which one it picks differs between a layer under `jax.checkpoint` and one outside: a unit in the last place
    assert abs(float(again_loss) - float(loss)) <= float(np.spacing(np.float32(loss)))
    leaf, rel = _worst_leaf(again, stored)
    assert rel < 1e-6, (leaf, rel)


def test_a_head_width_no_block_divides_runs_the_padded_cross_entropy() -> None:
    """18,992 = 16 x 1,187 columns: zero columns pad the head to a multiple of
    512, the kernels take the padding's logits as -inf, and loss and gradients
    are those of the unpadded head."""
    assert ce.padded_vocab(18992) == 19456 and ce._block_v(19456, 2048) == 512 and ce.padded_vocab(20480) == 20480
    n, e, v = 256, 128, 1000
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x, w = jax.random.normal(ks[0], (n, e)), jax.random.normal(ks[1], (e, v)) * 0.1
    t = jax.random.randint(ks[2], (n,), 0, v)

    def plain(x, w):
        logits = x @ w
        return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, t[:, None], -1)[:, 0])

    want, dwant = jax.value_and_grad(plain, (0, 1))(x, w)
    got, dgot = jax.value_and_grad(lambda x, w: ce.fused_linear_cross_entropy_padded(x, w, t), (0, 1))(x, w)
    assert abs(float(got) - float(want)) < 1e-6
    for a, b in zip(dgot, dwant):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    padded = jnp.pad(w, ((0, 0), (0, ce.padded_vocab(v) - v)))
    lse = ce._ce_lse_pallas(x, padded, interpret=True, valid_v=v)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(jax.nn.logsumexp(x @ w, -1)), atol=1e-5)
    dl = ce._ce_dlogits_pallas(x, padded, t, lse, 1.0, interpret=True, valid_v=v)
    np.testing.assert_allclose(np.asarray(dl[:, :v]), np.asarray(jax.nn.softmax(x @ w, -1) - jax.nn.one_hot(t, v)), atol=1e-6)
    assert float(jnp.max(jnp.abs(dl[:, v:]))) == 0.0


def test_the_programs_file_raises_on_a_key_it_does_not_honour() -> None:
    for key, value in (("use_sliding_window", True), ("mlp_only_layers", [0]), ("attention_bias", True),
                       ("num_local_experts", 4)):
        with pytest.raises(ValueError):
            PROGRAM.transformer_config(dict(CONFIG, **{key: value}))
    with pytest.raises(ValueError):
        PROGRAM.transformer_config(dict(CONFIG, sa_config=dict(CONFIG["sa_config"], indexer_num_kv_heads=2)))
    names = PROGRAM.kernel_names()
    assert names["dsa_attn"]("tpuft_dsa_attn_bwd_dkdv_dq.3") and names["dsa_attn"]("tpuft_dsa_attn_fwd")
    assert names["dsa_index"]("tpuft_dsa_index_loss.1") and names["dsa_select"]("tpuft_dsa_mask.7")
    assert not names["attn"]("tpuft_dsa_attn_fwd") and not names["dsa_attn"]("tpuft_fa_fwd")


def _records(path, event):
    with open(path, encoding="utf-8") as f:
        return [r for r in map(json.loads, f) if r.get("event") == event]


def test_the_pair_counters_land_in_the_step_summary(store, tmp_path, monkeypatch) -> None:  # noqa: F811
    """ft_steps of the share under a real Manager, through the benchmark's own
    programs file: `dsa_pairs_selected`, `dsa_pairs_visible` and
    `dsa_index_loss` ride the next step's summary beside the experts'."""
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
    per_sequence = TOPK * (TOPK + 1) // 2 + (SEQ - TOPK) * TOPK
    for summary in (second, third):
        assert summary["dsa_pairs_selected"] == 2 * 2 * per_sequence
        assert summary["dsa_pairs_visible"] == 2 * 2 * SEQ * (SEQ + 1) // 2
        assert summary["dsa_index_loss"] > 0 and summary["moe_dropped"] == 0
        assert 0 < summary["moe_rows_held"] < summary["moe_assignments"]
