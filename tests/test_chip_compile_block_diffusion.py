"""The described-chip compiles (`tests/chip_compile.py`) of the configuration
trained by block diffusion: the two `tpuft_bd_*` kernels at the SDAR cell's
shapes, and the whole `sdar-30b-a3b` gradient program — a doubled stream of
2 x 16,384 positions through five layers of 16 held experts — with its kernel
calls counted and the bytes its file's `reduced_why` quotes."""

import re

import pytest

import jax
import jax.numpy as jnp

from chip_compile import ROOT, heads_a_step, kernel_calls, kernel_grids, one_chip, topo  # noqa: F401 — `topo` and `one_chip` are the fixtures


@pytest.mark.parametrize("S,block_length", [(32768, 4), (32768, 32), (24576, 12)])
def test_block_diffusion_kernels_compile_for_v5e(one_chip, S, block_length) -> None:
    """`tpuft_bd_fwd` and the one-pass `tpuft_bd_bwd_dkdv_dq` at 32 query heads
    on 4 KV heads of 128 over 2 x 16,384 positions — the mask worked on a column
    of rows and a row of columns, the walk's 1,088 live tiles of the stream's
    4,096, eight heads a step forward and two backward (a 16 MiB dq row a head)
    — and over 2 x 12,288 in blocks of 12, which divide no tile: a division
    where the power of two has a shift, and tiles beside the diagonal's."""
    from torchft_tpu.ops import attention as fa

    H, KV, D = 32, 4, 128
    q = jax.ShapeDtypeStruct((1, S, H * D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, KV * D), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((H, S), jnp.float32, sharding=one_chip)
    fwd = jax.jit(lambda q, k, v: fa._fa_pallas_call(q, k, v, D ** -0.5, True, q_heads=H, kv_group=H // KV, block_length=block_length))
    bwd = jax.jit(lambda q, k, v, o, l, g: fa._fa_bwd_pallas(q, k, v, o, l, g, D ** -0.5, True, q_heads=H, kv_group=H // KV,
                                                             block_length=block_length))
    text = fwd.lower(q, kv, kv).compile().as_text() + bwd.lower(q, kv, kv, q, lse, q).compile().as_text()
    tiles = len(fa._Walk(True, S, S, 512, 512, block_length=block_length).tables[0])
    # 32 + 32 x 33; at 24 tiles a half 24 + 24 x 25 and, for each of the 16 tile edges that cut a block of 12, the two
    # noised tiles beside the diagonal and the clean one above it
    assert tiles == {4: 1088, 32: 1088, 12: 624 + 16 * 3}[block_length]
    assert kernel_grids(text, "tpuft_bd_") == [("tpuft_bd_fwd", (4, tiles)), ("tpuft_bd_bwd_dkdv_dq", (16, tiles))]


def test_sdar_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip) -> None:
    """The benchmark's `sdar-30b-a3b` configuration as `benchmark/programs/bd_moe_lm.py`
    hands it to `TrainStep`: the whole gradient program at the published widths
    and the cell's 1 x 16,384 data tokens, 32,768 positions — `tpuft_bd_fwd`
    ONCE a layer (`remat_keeps_attention` keeps the new kernel's output and row
    statistics as it keeps the old one's) and the backward once, no
    `tpuft_fa_*` call, the 16 held experts of each layer through `tpuft_gmm_*`,
    the head over the 16,384 noised rows alone through `tpuft_ce_*` with a scale a
    row — and the bytes `reduced_why` quotes, with AdamW's moments under the
    14.5 GB that decided five layers."""
    import sys

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    bench = Benchmark(ROOT)
    config, traffic = bench.config("sdar-30b-a3b"), bench.traffic("steady-1g-16k")
    assert (traffic["sequences_per_step"], traffic["seq_len"], config["num_hidden_layers"]) == (1, 16384, 5)
    shapes = jax.eval_shape(lambda: bench.reference("bd_moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(_pallas_util, "on_tpu", lambda: True)  # the gate asks the default backend, the CPU here
        _, step = bench.program("bd_moe_lm").train_step(config, topo.devices[0])
        compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    layers = config["num_hidden_layers"]
    assert sorted(kernel_calls(text, "tpuft_bd_")) == ["tpuft_bd_bwd_dkdv_dq"] * layers + ["tpuft_bd_fwd"] * layers
    assert kernel_calls(text, "tpuft_fa_") == [] and kernel_calls(text, "tpuft_dsa_") == []
    assert heads_a_step(text, "tpuft_bd_", 32) == {"tpuft_bd_fwd": [8], "tpuft_bd_bwd_dkdv_dq": [2]}
    assert {grid[1] for _, grid in kernel_grids(text, "tpuft_bd_")} == {1088}
    gmm = kernel_calls(text, "tpuft_gmm_")
    assert sorted(gmm) == ["tpuft_gmm_dlhs"] * 3 * layers + ["tpuft_gmm_drhs"] * 3 * layers + ["tpuft_gmm_fwd"] * 6 * layers
    assert sorted(kernel_calls(text, "tpuft_ce_")) == ["tpuft_ce_dlogits", "tpuft_ce_lse"]
    dlogits = [line for line in text.splitlines() if "tpu_custom_call" in line and "custom-call(" in line and "tpuft_ce_dlogits" in line]
    assert all("f32[1,1,16384]" in line and "bf16[16384,19456]" in line for line in dlogits), dlogits[0][:400]
    assert "bf16[32768,19456]" not in text  # the head runs the noised half's rows, not the stream's
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("bd_moe_lm").total_params(config) == 550_984_960
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    quoted = [int(n.replace(",", "")) for n in re.findall(r"\d{1,3}(?:,\d{3}){3,}", config["reduced_why"])]
    for name, size in (("arguments", ma.argument_size_in_bytes), ("outputs", ma.output_size_in_bytes)):
        assert size in quoted, f"{name}: {size} bytes compiled, `reduced_why` quotes {quoted}"
    # the row buffer, bf16[67584,2048] = 264 MiB: a layer's two T * k-row gathers are `tpuft_moe_rows` calls since PR 67,
    # and the gathered [32768, 8, 2048] rows are not written: the temporaries `reduced_why` quotes (PR 66's compile,
    # 5,610,596,352, and the step's 14,426,533,888 with them) are 4,990,429,696 now and the step 13,806,367,232 — the
    # file is the benchmark's and keeps PR 66's figures until a `benchmark` PR quotes these
    assert kernel_calls(text, "tpuft_moe_") == ["tpuft_moe_rows"] * 2 * layers == ["tpuft_moe_rows"] * 10
    assert {5_610_596_352, 14_426_533_888} <= set(quoted) and ma.temp_size_in_bytes <= 4_990_429_696
    assert resident <= 13_806_367_232
