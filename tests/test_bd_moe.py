"""SDAR-shaped models (Qwen3-MoE's decoder — GQA with a per-head QK-norm,
softmax top-k experts, one chip's share of them — trained by block diffusion:
a noised and a clean copy of every sequence in one stream under a three-part
block mask, a 1/t-weighted loss over the masked tokens) through the program, on
the CPU at small sizes.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`, which
holds the tests every architecture is held to against the benchmark's plain
float32 reference, ``benchmark/reference/bd_moe_lm.py``).  What only this
architecture has is tested here: the noise (the same for the same sequence, in
program and reference, whatever else the batch holds), the doubled stream and
its positions, what the loss reads and does not.  The kernels in interpret mode
against the XLA form, and the walk: `tests/test_attention_block_diffusion.py`.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from architectures import (  # noqa: F401 — the shared tests this entry has fields for, and their fixture
    BENCH, HELD, REMAT, Architecture, ExpertLayer, Piece, Tiny, batches, in_the_scan, omission_cases, pytest_generate_tests, store,
    test_a_model_without_a_piece_is_another_model, test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_rematerialised_layers_give_the_gradients_of_the_stored_ones, test_the_adapter_raises_on_what_it_does_not_honour,
    test_the_published_configuration_is_handed_over_whole, test_the_shares_add_up_to_the_uncut_layer,
    test_the_tree_goes_through, test_the_tree_is_the_reference_s)
from torchft_tpu.models.moe import moe_layer
from torchft_tpu.models.transformer import (
    TransformerConfig, _decoder, block_diffusion_noise, block_diffusion_stream, loss_and_counters)

REFERENCE = BENCH.reference("bd_moe_lm")
PROGRAM = BENCH.program("bd_moe_lm")
PUBLISHED = BENCH.config("sdar-30b-a3b")

SEQ, BLOCK = 48, 4
SIZES = """48 data tokens in 12 blocks of 4 (the published block length), so the stream has 96 positions and every region of
the mask — a block's own noised keys, the clean blocks before it, the clean triangle, the dead quadrant — several blocks.
Two layers, the least with a layer after a layer.  4 query heads on 2 KV heads of 32 (hidden 64, so a head is NOT hidden /
heads), 8 experts of 48, 2 a token.  Float32 throughout."""
DIFFUSION = dict(block_length=BLOCK, noise_seed=11, schedule="linear", loss_weight="1/t", shift=False,
                 mask_token="last_row_of_the_slice")
CONFIG = dict(
    architecture="bd_moe_lm", vocab_size=200, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, intermediate_size=128, moe_intermediate_size=48, num_experts=8,
    num_experts_per_tok=2, norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[], hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False, sliding_window=None, use_sliding_window=False, rope_scaling=None,
    max_position_embeddings=128, rope_theta=1e6, rms_norm_eps=1e-6, router_aux_loss_coef=0.001, block_diffusion=DIFFUSION,
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
    program=dict(remat=False, scan_unroll=4),
)
# One of the four chips that share each layer: experts 2 and 3 of the router's 8.
SHARE = dict(CONFIG, num_experts=2, expert_parallel=dict(chips=4, rank=1, router_outputs=8, first_expert_held=2))

_batch = batches(CONFIG["vocab_size"], SEQ)

# What the program would compute with one setting of the objective or the body
# wrong: each has to fail the comparison that the whole passes.  (The five wrong
# MECHANISMS — a causal mask, no clean half, no 1/t, the shift, positions 0 .. 2L - 1 — are the reference's
# `LEFT_OUT`, the entry's `pieces`.)
OMISSIONS = {
    "as_published": {},
    "without_the_balance_loss": {"moe_aux_coef": 0.0},
    "top_k_not_renormalised": {"moe_norm_topk": False},
    "blocks_of_eight": {"bd_block_length": 8},
    "another_noise_seed": {"bd_noise_seed": 12},
    "rope_at_another_base": {"rope_theta": 1e4},
}


def _counters(counters, config) -> None:
    assert 0.3 < float(counters["bd_masked_share"]) < 0.7 and 0.5 < float(counters["bd_weight_mean"]) < 2.0
    assert float(counters["bd_live_pairs_share"]) == np.float32((SEQ * SEQ + SEQ * BLOCK) / (2 * SEQ) ** 2)
    assert int(counters["moe_dropped"]) == 0 and counters["moe_tokens_per_expert"].shape == (2, 8)
    assert int(counters["moe_tokens_per_expert"].sum()) == 2 * 2 * 2 * SEQ * 2  # layers x sequences x 2 S positions x k


def _expert_layer() -> ExpertLayer:
    """Under the SOFTMAX router with renormalised gates, over a doubled stream's rows."""
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


def _tree_facts(cfg, ours) -> None:
    """One stack; the published cut's count is the operation count's."""
    assert list(cfg.stacks) == ["layers"] and cfg.stacks["layers"][1] == PUBLISHED["num_hidden_layers"]
    assert ours["layers"]["w_gate"].shape == (PUBLISHED["num_hidden_layers"], 16, 2048, 768)
    assert ours["layers"]["router"].shape[-1] == 128 and ours["embed"].shape == (18992, 2048)
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(ours)) == BENCH.flops("bd_moe_lm").total_params(PUBLISHED)


def _published_facts(cfg, published) -> None:
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_heads, cfg.n_kv_heads, cfg.d_head) == (2048, 768, 18992, 32, 4, 128)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_held, cfg.moe_score, cfg.moe_aux_coef) == (128, 8, (0, 16), "softmax", 0.001)
    assert cfg.qk_norm_per_head and cfg.moe_norm_topk and cfg.rope_theta == 1e6 and cfg.rms_eps == 1e-6 and not cfg.tied_head
    assert (cfg.bd_block_length, cfg.bd_noise_seed) == (4, published["block_diffusion"]["noise_seed"])
    assert cfg.remat and cfg.remat_keeps_attention and cfg.moe_capacity_factor is None
    assert published["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert published["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151_936}
    assert set(PROGRAM.kernel_names()) >= {"attn", "ce", "gmm", "bd_attn"} and PROGRAM.kernel_names()["bd_attn"]("x.tpuft_bd_fwd.3")
    # every other configuration of the benchmark leaves the objective's one setting alone
    for other in BENCH.doc["configs"]:
        if other["name"] != "sdar-30b-a3b":
            config = BENCH.config(other["name"])
            assert BENCH.program(config["architecture"]).transformer_config(config).bd_block_length is None, other["name"]


REFUSALS = [(key, {key: value}, None, True) for key, value in (("use_sliding_window", True), ("mlp_only_layers", [0]))] + [
    (key, {key: value}, None) for key, value in (
        ("attention_bias", True), ("tie_word_embeddings", True), ("hidden_act", "gelu"),
        ("rope_scaling", dict(rope_type="yarn", factor=4.0)))] + [
    ("top_k_not_renormalised", dict(norm_topk_prob=False), "renormalised", True),
    ("a_clipped_schedule", dict(block_diffusion=dict(DIFFUSION, schedule="clipped")), "objective"),
    ("the_shift", dict(block_diffusion=dict(DIFFUSION, shift=True)), "objective"),
    ("an_unknown_key", dict(block_diffusion=dict(DIFFUSION, eps=1e-2)), "objective"),
    ("clipped_levels", dict(block_diffusion=dict(DIFFUSION, levels=[0.1, 0.9])), "objective"),
]


def _tiny() -> Tiny:
    """The objective's counters ride the next step's summary beside the
    experts', through the benchmark's own programs file; every leaf moves."""
    def facts(moved, summaries, step, after) -> None:
        assert {"['embed']", "['lm_head']", "['layers']['wq']", "['layers']['q_norm']", "['layers']['router']",
                "['layers']['w_down']"} <= moved
        for summary in summaries[1:]:
            assert 0.3 < summary["bd_masked_share"] < 0.7 and summary["moe_dropped"] == 0
            assert summary["bd_live_pairs_share"] == float(np.float32((SEQ * SEQ + SEQ * BLOCK) / (2 * SEQ) ** 2))
            assert 0 < summary["moe_rows_held"] < summary["moe_assignments"] == 2 * 2 * 2 * SEQ * 2
        # the same batch every step: the same noise, so the same masked share (a replayed step sees what it saw)
        assert len({summary["bd_masked_share"] for summary in summaries[1:]}) == 1

    return Tiny(lambda: REFERENCE.make_weights(2, SHARE), PROGRAM.loss(SHARE), lambda i: _batch(0), 3, facts)


ARCH = Architecture(
    name="bd_moe_lm", configs=dict(zip(HELD, (CONFIG, SHARE))), sizes=SIZES, seq=SEQ, variants=dict(in_the_scan(OMISSIONS), **REMAT),
    leaf_cases=omission_cases(OMISSIONS, 7), leaf_tolerance=5e-5, loss_tolerance=2e-6, counters=_counters,
    remat=("a_share_of_the_experts", 4, tuple(REMAT)), remat_ulps=1,
    pieces=[Piece(piece, "reference", piece) for piece in REFERENCE.LEFT_OUT], pieces_at=("a_share_of_the_experts", 7),
    piece_floor=0.05,
    chips=[8, 4, 2, 1], expert_layer=_expert_layer,
    published="sdar-30b-a3b", tree_facts=_tree_facts, published_facts=_published_facts,
    refusals=REFUSALS, refusal_config="every_expert_held", through=("ft_step", "heal", "disk_checkpoint"), tiny=_tiny,
)


# -- the noise ---------------------------------------------------------------------------------


def test_the_noise_is_the_sequence_s_own_and_the_reference_s_bit_for_bit() -> None:
    """The same batch twice gives the same mask and levels; a sequence's are
    the reference's bit for bit, and the same whatever else the batch holds;
    two batches differ; another seed differs; a block shares one level in
    [eps, 1) and about that share of its tokens is masked."""
    tokens = _batch(3)["tokens"]
    noise = jax.jit(lambda t, seed=11: block_diffusion_noise(t, BLOCK, seed))
    masked, level = noise(tokens)
    again = noise(jnp.array(tokens))
    assert np.array_equal(masked, again[0]) and np.array_equal(level, again[1])
    for i in range(2):
        theirs = REFERENCE.noise(tokens[i], BLOCK, 11)
        assert np.array_equal(masked[i], theirs[0]) and np.array_equal(np.asarray(level[i]), np.asarray(theirs[1]))
    alone = noise(tokens[1:])
    assert np.array_equal(alone[0][0], masked[1]) and np.array_equal(alone[1][0], level[1])
    other = noise(_batch(4)["tokens"])
    assert not np.array_equal(other[0], masked) and not np.array_equal(other[1], level)
    assert not np.array_equal(block_diffusion_noise(tokens, BLOCK, 12)[1], level)
    one_id_moved = noise(tokens.at[0, 5].add(1))
    assert not np.array_equal(one_id_moved[1][0], level[0]) and np.array_equal(one_id_moved[1][1], level[1])
    blocks = np.asarray(level).reshape(2, SEQ // BLOCK, BLOCK)
    assert (blocks == blocks[..., :1]).all() and 1e-3 <= blocks.min() and blocks.max() < 1.0
    long = block_diffusion_noise(jnp.asarray(np.random.default_rng(0).integers(0, 200, (4, 4096)), jnp.int32), BLOCK, 11)
    assert abs(float(long[0].mean()) - 0.5) < 0.02 and abs(float(jnp.where(long[0], 1 / long[1], 0).mean()) - 1.0) < 0.05


def test_the_stream_is_the_noised_copy_then_the_clean_one_at_the_same_positions() -> None:
    cfg = PROGRAM.transformer_config(CONFIG)
    tokens = _batch(5)["tokens"].at[0, 7].set(CONFIG["vocab_size"] - 1)  # a data token with the mask's id
    stream, masked, weight = block_diffusion_stream(tokens, cfg)
    assert stream.shape == (2, 2 * SEQ) and np.array_equal(stream[:, SEQ:], tokens)
    assert np.array_equal(stream[:, :SEQ], np.where(masked, CONFIG["vocab_size"] - 1, tokens))
    assert np.array_equal(weight > 0, masked) and np.allclose(np.asarray(weight)[np.asarray(masked)].min(), 1.0, atol=1.0)
    # RoPE's operand: a token's place in its sequence, twice — the decoder under positions 0 .. 2L - 1 is another model
    weights = REFERENCE.make_weights(5, CONFIG)
    x, _ = jax.jit(lambda w, s: _decoder(w, s, cfg))(weights, stream)
    shifted = jnp.concatenate([stream[:, SEQ:], stream[:, SEQ:]], axis=1)
    y, _ = jax.jit(lambda w, s: _decoder(w, s, cfg))(weights, shifted)
    # the clean half sees clean keys alone and its own positions: the same rows whatever the noised half holds
    np.testing.assert_allclose(np.asarray(x[:, SEQ:]), np.asarray(y[:, SEQ:]), atol=1e-5)


def test_the_loss_reads_the_tokens_and_not_the_job_s_targets() -> None:
    """`batch["targets"]` (the job's roll by one) is not read; a data token whose
    id is the mask's is an ordinary target; the loss is the masked rows' alone."""
    cfg = PROGRAM.transformer_config(CONFIG)
    weights, batch = REFERENCE.make_weights(9, CONFIG), _batch(9)
    loss = jax.jit(lambda p, b: loss_and_counters(p, b, cfg)[0])
    garbage = dict(batch, targets=jnp.zeros_like(batch["targets"]))
    assert float(loss(weights, batch)) == float(loss(weights, garbage))
    with_the_mask_id = dict(batch, tokens=batch["tokens"].at[:, 3].set(CONFIG["vocab_size"] - 1))
    want = np.mean([float(REFERENCE.loss(weights, t, None, REFERENCE.sizes_of(CONFIG))) for t in with_the_mask_id["tokens"]])
    assert abs(float(loss(weights, with_the_mask_id)) - want) < 2e-6 * want


def test_other_objectives_and_mixers_are_refused() -> None:
    base = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=48, bd_block_length=4)
    assert TransformerConfig(**base).bd_block_length == 4
    for wrong in (dict(loop_steps=2), dict(dsa_index_heads=2), dict(attention="ring"),
                  dict(moe_experts=4, moe_router_state=8, moe_capacity_factor=None)):
        with pytest.raises(AssertionError):
            TransformerConfig(**base, **wrong)
    from torchft_tpu.models import LayerKind

    for kind in (LayerKind("layers", False, 2, 1e4, window=8), LayerKind("layers", False, 2, 1e4, mixer="kda")):
        with pytest.raises(AssertionError, match="doubled stream"):
            TransformerConfig(**base, pattern=(kind, kind))
    assert dataclasses.replace(TransformerConfig(**base), bd_block_length=None).bd_block_length is None
