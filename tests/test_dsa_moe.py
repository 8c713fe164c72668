"""Keye-shaped models (learned sparse attention over GQA with a per-head
QK-norm, softmax top-k experts, one chip's share of them) through the
program, on the CPU at small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/dsa_moe_lm.py``).  What only this
architecture has is tested here: the exact count, causality and tie rule of the
selection; the layer with ``topk`` at the sequence length against the dense GQA
layer; which loss term reaches which weight.  The five kernels in interpret
mode against the XLA formulation: `tests/test_dsa_kernels_interpreted.py`.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, HELD, REMAT, Architecture, ExpertLayer, Tiny, batches, in_the_scan, omission_cases, pytest_generate_tests, store, worst_leaf,
    test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_rematerialised_layers_give_the_gradients_of_the_stored_ones, test_the_adapter_raises_on_what_it_does_not_honour,
    test_the_shares_add_up_to_the_uncut_layer, test_the_tree_goes_through)
from torchft_tpu.models.moe import moe_layer
from torchft_tpu.models.transformer import loss_and_counters
from torchft_tpu.ops import sparse_attention as sa

REFERENCE = BENCH.reference("dsa_moe_lm")
PROGRAM = BENCH.program("dsa_moe_lm")

SEQ, TOPK = 96, 24
SIZES = """96 positions and an indexer that keeps 24: the selection bites from the 25th position on, three quarters of
every sequence (the selection's own tests below walk positions 5, 30, 60 and 95 and topk 1, 24, 95, 96 and 200 on the same
96).  Two layers, the least with a layer after a layer.  4 query heads on 2 KV heads of 32 (hidden 64, so a head is NOT
hidden / heads), an indexer of 3 heads of 16, 8 experts of 48, 2 a token.  Float32 throughout."""
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
QK_NORM = ("q_norm", "k_norm")


_batch = batches(CONFIG["vocab_size"], SEQ)


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


def _weights(weights, variant):
    """A QK-norm weight of one leaves a gradient the omission would not show."""
    return {**weights, "layers": {**weights["layers"], "q_norm": weights["layers"]["q_norm"] * 1.5}}


def _prune(tree, variant):
    if variant != "without_the_per_head_qk_norm":
        return tree
    return {**tree, "layers": {k: v for k, v in tree["layers"].items() if k not in QK_NORM}}


def _counters(counters, config) -> None:
    per_sequence = TOPK * (TOPK + 1) // 2 + (SEQ - TOPK) * TOPK
    assert int(counters["dsa_pairs_selected"]) == 2 * 2 * per_sequence
    assert int(counters["dsa_pairs_visible"]) == 2 * 2 * SEQ * (SEQ + 1) // 2
    assert float(counters["dsa_index_loss"]) > 0
    assert int(counters["moe_dropped"]) == 0


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
    leaf, rel = worst_leaf(shared, dense_grads)
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
    ref = jax.jit(jax.grad(lambda p: REFERENCE.loss(p, batch["tokens"][0], batch["targets"][0],
                                            dict(s, index_coef=0.0 if term != "index_loss" else 1.0,
                                                 aux_coef=s["aux_coef"] if term != "index_loss" else 0.0))
                   - (0.0 if term != "index_loss" else REFERENCE.loss(
                       p, batch["tokens"][0], batch["targets"][0], dict(s, index_coef=0.0, aux_coef=0.0)))))(weights)
    for name in INDEXER:
        norm = float(jnp.linalg.norm(ref["layers"][name]))
        assert (norm > 0) == (term == "index_loss"), (name, norm)
    assert (float(jnp.linalg.norm(ref["layers"]["wq"])) > 1e-9) == (term != "index_loss")



def _expert_layer() -> ExpertLayer:
    """Under the SOFTMAX router with renormalised gates."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (2, 48, 64), jnp.float32)
    w = {"router": jax.random.normal(ks[1], (64, 8)) * 0.3, "w_gate": jax.random.normal(ks[2], (8, 64, 48)) * 0.12,
         "w_up": jax.random.normal(ks[3], (8, 64, 48)) * 0.12, "w_down": jax.random.normal(ks[4], (8, 48, 64)) * 0.14}
    s = REFERENCE.sizes_of(dict(CONFIG, num_experts_per_tok=3))

    def share(first, count, _, x):
        return moe_layer(x, w["router"], w["w_gate"][first:first + count], w["w_up"][first:first + count],
                         w["w_down"][first:first + count], top_k=3, capacity_factor=None, norm_topk=True,
                         score="softmax", held_first=first, dtype=jnp.float32)

    def uncut(x):
        return jnp.stack([REFERENCE._experts(seq, w, s, "float32")[0] for seq in x]), None

    return ExpertLayer((x,), 8, share, uncut, 96 * 3)


REFUSALS = [(key, {key: value}, None) for key, value in (
    ("use_sliding_window", True), ("mlp_only_layers", [0]), ("attention_bias", True), ("num_local_experts", 4))] + [
    ("indexer_num_kv_heads", dict(sa_config=dict(CONFIG["sa_config"], indexer_num_kv_heads=2)), None)]


def test_the_kernel_names_tell_the_sparse_kernels_from_the_flash_ones() -> None:
    names = PROGRAM.kernel_names()
    assert names["dsa_attn"]("tpuft_dsa_attn_bwd_dkdv_dq.3") and names["dsa_attn"]("tpuft_dsa_attn_fwd")
    assert names["dsa_index"]("tpuft_dsa_index_loss.1") and names["dsa_select"]("tpuft_dsa_mask.7")
    assert not names["attn"]("tpuft_dsa_attn_fwd") and not names["dsa_attn"]("tpuft_fa_fwd")


def _tiny() -> Tiny:
    """`dsa_pairs_selected`, `dsa_pairs_visible` and `dsa_index_loss` ride the
    next step's summary beside the experts', through the benchmark's own
    programs file."""
    def facts(moved, summaries, step, after) -> None:
        per_sequence = TOPK * (TOPK + 1) // 2 + (SEQ - TOPK) * TOPK
        for summary in summaries[1:]:
            assert summary["dsa_pairs_selected"] == 2 * 2 * per_sequence
            assert summary["dsa_pairs_visible"] == 2 * 2 * SEQ * (SEQ + 1) // 2
            assert summary["dsa_index_loss"] > 0 and summary["moe_dropped"] == 0
            assert 0 < summary["moe_rows_held"] < summary["moe_assignments"]

    return Tiny(lambda: REFERENCE.make_weights(2, SHARE), PROGRAM.loss(SHARE), _batch, 3, facts)


ARCH = Architecture(
    name="dsa_moe_lm", configs=dict(zip(HELD, (CONFIG, SHARE))), sizes=SIZES, seq=SEQ, variants=dict(in_the_scan(OMISSIONS), **REMAT),
    leaf_cases=omission_cases(OMISSIONS, 7, read_by_loss=("without_the_per_head_qk_norm",)),
    leaf_tolerance=5e-5, loss_tolerance=2e-6, weights=_weights, prune=_prune, counters=_counters,
    # the same float32 sums; XLA:CPU contracts one product of RoPE's `x * cos + swapped * sin` into the sum, and which
    # one it picks differs between a layer under `jax.checkpoint` and one outside: a unit in the loss's last place
    remat=("a_share_of_the_experts", 4, tuple(REMAT)), remat_ulps=1,
    chips=[8, 4, 2, 1], expert_layer=_expert_layer, refusals=REFUSALS, refusal_config="every_expert_held",
    through=("ft_step",), tiny=_tiny,
)
