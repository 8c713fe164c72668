"""The described-chip compile (`tests/chip_compile.py`) of the configuration
that mixes Gated DeltaNet with output-gated attention: the whole
`qwen3-next-80b-a3b` gradient program at the published widths and the cell's
1 x 16,384 tokens, with its kernel calls a layer counted and the bytes its
file's `reduced_why` quotes."""

import re

import pytest

import jax
import jax.numpy as jnp

from chip_compile import ROOT, heads_a_step, kernel_calls, kernel_grids, one_chip, topo  # noqa: F401 — `topo` and `one_chip` are the fixtures


def test_qwen3_next_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip) -> None:
    """The benchmark's `qwen3-next-80b-a3b` configuration as
    `benchmark/programs/gdn_moe_lm.py` hands it to `TrainStep`: three Gated
    DeltaNet layers whose scan runs the `tpuft_kda_*` kernels — forward twice
    a layer (the forward pass, and the backward's that makes the chunks' states
    again) and backward once, eight of the 32 value heads' chunk a grid step —
    and, since PR 69, what stands around the scan the `tpuft_kdamix_*` kernels
    (each forward kernel twice a layer — the forward pass and the remat's — and
    each backward once: before the scan a key head with its two value heads a
    grid step, after it a value head), one attention layer through `tpuft_fa_*` at 16 / 2 heads of 256 (the output
    and row statistics kept: one forward call), 32 held experts a layer through
    `tpuft_gmm_*`, the 18,992-row head through `tpuft_ce_*` over 19,456 padded
    columns, and everything with AdamW's moments under 15.5 GB: arguments and
    outputs the bytes `reduced_why` quotes, the temporaries not above them."""
    import sys

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    bench = Benchmark(ROOT)
    config, traffic = bench.config("qwen3-next-80b-a3b"), bench.traffic("steady-1g-16k")
    assert (traffic["sequences_per_step"], traffic["seq_len"], config["num_hidden_layers"]) == (1, 16384, 4)
    shapes = jax.eval_shape(lambda: bench.reference("gdn_moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(_pallas_util, "on_tpu", lambda: True)  # the gate asks the default backend, the CPU here
        _, step = bench.program("gdn_moe_lm").train_step(config, topo.devices[0])
        compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    gdn = sum(mixer == "gdn" for mixer, _ in bench.reference("gdn_moe_lm").layer_plan(config))
    assert gdn == 3
    assert sorted(kernel_calls(text, "tpuft_kda_")) == ["tpuft_kda_bwd"] * gdn + ["tpuft_kda_fwd"] * 2 * gdn
    assert {grid for _, grid in kernel_grids(text, "tpuft_kda_")} == {(4, 256)}  # eight value heads' chunk a step
    # `gdn_mix` around it (since PR 69; no mix kernel's name holds `tpuft_kda_`, which the cell books to the scan)
    assert sorted(kernel_calls(text, "tpuft_kdamix_")) == (
        ["tpuft_kdamix_bwd"] * gdn + ["tpuft_kdamix_fwd"] * 2 * gdn + ["tpuft_kdamix_out_bwd"] * gdn + ["tpuft_kdamix_out_fwd"] * 2 * gdn)
    mix_grids = kernel_grids(text, "tpuft_kdamix_")
    assert {grid for name, grid in mix_grids if "_out_" not in name} == {(1, 16, 16)}  # 16 key heads x 16 tiles of 1,024 rows
    assert {grid for name, grid in mix_grids if "_out_" in name} == {(1, 32, 16)}      # 32 value heads
    assert sorted(kernel_calls(text, "tpuft_fa_")) == ["tpuft_fa_bwd_dkdv_dq", "tpuft_fa_fwd"]
    gmm = kernel_calls(text, "tpuft_gmm_")
    assert sorted(gmm) == ["tpuft_gmm_dlhs"] * 3 * 4 + ["tpuft_gmm_drhs"] * 3 * 4 + ["tpuft_gmm_fwd"] * 6 * 4
    assert sorted(kernel_calls(text, "tpuft_ce_")) == ["tpuft_ce_dlogits", "tpuft_ce_lse"]
    assert "bf16[16384,19456]" in text  # the padded slice of the vocabulary
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("gdn_moe_lm").total_params(config) == 625_667_136
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    print("MEMORY", ma.argument_size_in_bytes, ma.output_size_in_bytes, ma.temp_size_in_bytes, 8 * n_params, resident)
    print("MOE_ROWS", kernel_calls(text, "tpuft_moe_"), heads_a_step(text, "tpuft_fa_", 16))
    quoted = [int(n.replace(",", "")) for n in re.findall(r"\d{1,3}(?:,\d{3}){3,}", config["reduced_why"])]
    for name, size in (("arguments", ma.argument_size_in_bytes), ("outputs", ma.output_size_in_bytes)):
        assert size in quoted, f"{name}: {size} bytes compiled, `reduced_why` quotes {quoted}"
    # PR 68's compile (the XLA halves under a checkpoint each), which the file quotes: temporaries 3,998,301,184, the
    # step 14,009,158,656.  With the halves as kernels (PR 69) 3,968,376,320 and 13,979,233,792 (builder's compile)
    assert 3_998_301_184 in quoted and ma.temp_size_in_bytes <= 3_998_301_184, ma.temp_size_in_bytes
    assert 14_009_158_656 in quoted and resident <= 14_009_158_656 < 15_500_000_000
