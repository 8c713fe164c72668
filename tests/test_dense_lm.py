"""Dense models (InternLM2's and Mistral's shape: grouped-query attention under
RoPE, a SiLU-gated feed-forward) through the program, on the CPU at a small
size.

`ARCH` is the architecture's entry in the suite (`tests/architectures.py`): the
program against the benchmark's plain float32 reference
(``benchmark/reference/dense_lm.py``, which shares no code with it) walk by
walk, the tree at the published widths and the adapter's one refusal.  The
kernels a dense model runs have their own files (`tests/test_ops.py`,
`tests/test_chip_compile.py`).
"""

import glob
import importlib
import os

from architectures import (  # noqa: F401 — the shared tests this entry has fields for
    BENCH, Architecture, Case, pytest_generate_tests, test_loss_and_every_gradient_leaf_against_the_plain_reference,
    test_the_adapter_raises_on_what_it_does_not_honour, test_the_tree_is_the_reference_s)

SIZES = """64 positions, the small model's whole `max_position_embeddings`.  Two layers: a layer after a layer, and a
stack the scan walks.  4 query heads over 2 KV heads of 16: a group of two.  The norm's epsilon is the program's fixed
1e-6 (the adapter hands none over).  Float32 throughout."""
CONFIG = dict(
    architecture="dense_lm", vocab_size=256, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, num_hidden_layers=2, max_position_embeddings=64, rope_theta=1e4, rms_norm_eps=1e-6,
    hidden_act="silu", tie_word_embeddings=False, bias=False,
    training=dict(compute_dtype="float32", param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
    program=dict(remat=False, scan_unroll=2),
)
WALKS = {"static_loop": dict(remat=False, scan_unroll=2), "scan": dict(remat=False, scan_unroll=1),
         "remat_in_the_scan": dict(remat=True, scan_unroll=1)}


def _tree_facts(cfg, ours) -> None:
    assert set(ours) == {"embed", "final_norm", "lm_head", "layers"} and list(cfg.stacks) == ["layers"]
    assert set(ours["layers"]) == {"attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up", "w_down"}
    assert ours["layers"]["wk"].shape == (4, 2048, 8 * 128) and ours["layers"]["w_up"].shape == (4, 2048, 8192)
    assert ours["embed"].shape == (92544, 2048) and not cfg.moe_experts and not cfg.tied_head


ARCH = Architecture(
    name="dense_lm", configs={"whole": CONFIG}, sizes=SIZES, seq=64, variants=WALKS,
    leaf_cases=[Case(walk, "whole", walk, 11) for walk in WALKS],
    # float32 on both sides: the order of sums alone, every leaf to 3e-5 of its norm
    leaf_tolerance=3e-5, loss_tolerance=1e-6,
    published="internlm2-1.8b", tree_facts=_tree_facts,
    refusals=[("a_head_that_is_not_hidden_over_heads", dict(head_dim=32), "derives the head size")], refusal_config="whole",
)


def test_every_architecture_of_the_benchmark_has_an_entry() -> None:
    """A file of `benchmark/programs/` is an architecture, and each has one
    `ARCH` in a test file beside this one."""
    here = os.path.dirname(os.path.abspath(__file__))
    entries = [path for path in sorted(glob.glob(os.path.join(here, "test_*.py")))
               if "\nARCH = Architecture(" in open(path, encoding="utf-8").read()]
    names = [importlib.import_module(os.path.basename(path)[:-3]).ARCH.name for path in entries]
    programs = sorted(os.path.basename(p)[:-3] for p in glob.glob(os.path.join(BENCH.bench_dir, "programs", "*.py")))
    assert sorted(names) == programs and len(set(names)) == len(names)
