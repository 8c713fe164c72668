"""The described-chip compiles (`tests/chip_compile.py`) of the configurations
that mix kinds of layer whose cost does not grow with the square of the length:
the `tpuft_swa_*` kernels under a window of 512 and of 1,536 and the whole
`laguna-xs.2` and `smallthinker-21b-a3b` gradient programs; the `tpuft_ssd_*`
and `tpuft_ssmmix_*` kernels, `grouped_matmul` at a width of 1,856 and the whole
`nemotron-twotower-30b-a3b` gradient program."""

import pytest

import jax
import jax.numpy as jnp

from chip_compile import (  # noqa: F401 — `topo` and `one_chip` are the fixtures
    ROOT, attention_calls, compile_text, has_kernel, instructions, kernel_calls, kernel_grids, one_chip, topo)


@pytest.mark.parametrize("window", [512, 1536])
def test_windowed_attention_kernels_compile_for_v5e(one_chip, window) -> None:
    """The band-walk kernels at the Laguna cell's window layers (64 heads x
    16,384 x 128, a window of 512) and at a window of three tiles: one forward
    and ONE backward `tpu_custom_call` under the `tpuft_swa_*` names — the
    tile's one unsigned comparison and the walk's traced row and column ends
    are what interpret mode cannot refuse."""
    from torchft_tpu.ops.attention import _fa_bwd_pallas, _fa_pallas_call

    qkv = jax.ShapeDtypeStruct((1, 16384, 64 * 128), jnp.bfloat16, sharding=one_chip)  # position-major
    lse = jax.ShapeDtypeStruct((64, 16384), jnp.float32, sharding=one_chip)
    text = compile_text(lambda q, k, v: _fa_pallas_call(q, k, v, 128 ** -0.5, True, window=window, q_heads=64), qkv, qkv, qkv)
    assert kernel_calls(text, "tpuft_swa_") == ["tpuft_swa_fwd"] and not attention_calls(text)
    text = compile_text(lambda q, k, v, o, l, g: _fa_bwd_pallas(q, k, v, o, l, g, 128 ** -0.5, True, window=window, q_heads=64),
                    qkv, qkv, qkv, qkv, lse, qkv)
    assert kernel_calls(text, "tpuft_swa_") == ["tpuft_swa_bwd_dkdv_dq"] and not attention_calls(text)


def test_laguna_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip, monkeypatch) -> None:
    """The benchmark's `laguna-xs.2` configuration as
    `benchmark/programs/swa_moe_lm.py` hands it to `TrainStep`: the whole
    gradient program at the published widths and 1 x 16,384 tokens — the three
    window layers through `tpuft_swa_*` at 64 heads, the two full layers
    through `tpuft_fa_*` at 48, the 32 held experts of each sparse layer
    through `tpuft_gmm_*`, the sliced vocabulary through `tpuft_ce_*` — with
    room for AdamW's moments beside it on a 16 GiB chip."""
    import os
    import sys

    root = ROOT
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    bench = Benchmark(root)
    config, traffic = bench.config("laguna-xs.2"), bench.traffic("steady-1g-16k")
    shapes = jax.eval_shape(lambda: bench.reference("swa_moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((traffic["sequences_per_step"], traffic["seq_len"]), jnp.int32, sharding=one_chip)
    _, step = bench.program("swa_moe_lm").train_step(config, topo.devices[0])
    compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    for name in ("tpuft_gmm_fwd", "tpuft_gmm_dlhs", "tpuft_gmm_drhs", "tpuft_ce_lse", "tpuft_ce_dlogits"):
        assert has_kernel(text, name), f"{name} is not in the compiled program"
    # the two kinds of layer read apart: one backward and, attention's output kept under remat, one
    # forward kernel a layer
    assert config["program"]["remat_keeps_attention"]
    assert sorted(attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq"] * 2 + ["tpuft_fa_fwd"] * 2
    assert sorted(kernel_calls(text, "tpuft_swa_")) == ["tpuft_swa_bwd_dkdv_dq"] * 3 + ["tpuft_swa_fwd"] * 3
    # the band's grid, as `swa_pairs_share` reads it out of the compiled calls: a step for each of the
    # 2n - 1 = 63 tiles with a visible pair a head, where the triangle has 528
    grids = bench.reader("swa_pairs_share").grids(text)
    assert sorted(g["name"] for g in grids) == ["tpuft_swa_bwd_dkdv_dq"] * 3 + ["tpuft_swa_fwd"] * 3
    # ... eight heads a grid step forward and four backward (two 16 MiB dq rows and their tiles a pair of heads)
    assert all((g["grid"], g["block_q"], g["seq"]) == ([8 if g["name"].endswith("fwd") else 16, 63], 512, 16_384)
               for g in grids), grids
    assert sorted(kernel_grids(text, "tpuft_fa_")) == [("tpuft_fa_bwd_dkdv_dq", (12, 528))] * 2 + [("tpuft_fa_fwd", (6, 528))] * 2
    # four sparse layers' row buffers of bf16[36864,2048] = 144 MiB: two `tpuft_moe_rows` calls a layer since PR 67
    assert kernel_calls(text, "tpuft_moe_") == ["tpuft_moe_rows"] * 8
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("swa_moe_lm").total_params(config) == 691_623_936
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # 15,451,607,040 (15,167,032,832 with the full layers' attention kept alone; builder's compiles,
    # PR 37), 15,272,240,128 since PR 39, 14,923,113,472 since PR 45, 14,568,567,808 since PR 60 (k and v reach the
    # attention kernels with their 8 KV heads, not repeated to 64 and 48), 14,653,631,488 since PR 65 (the kernels read
    # q, k, v position-major).  Nothing is held longer: the buffer assignment's peak of LIVE bytes fell, 6,686,688,738 ->
    # 6,661,719,522 (`--xla_dump_to`'s `*buffer-assignment.txt`, "peak usage"), and the heap it packs the temporaries
    # into came out 95.5 MB larger, 3,464,856,064 -> 3,560,391,168: the packing's, PERF.md section 6, PR 65 (7).  The
    # chip's allocator has 16.9e9.  14,278,144,512 since PR 67 (temporaries 3,587,478,016 -> 3,211,991,040: the gathered
    # [16384, 8, 2048] rows are not written)
    assert resident <= 14_278_144_512, f"the step needs {resident} bytes with AdamW's moments"


def test_smallthinker_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip, monkeypatch) -> None:
    """The benchmark's `smallthinker-21b-a3b` configuration as
    `benchmark/programs/early_router_moe_lm.py` hands it to `TrainStep`: the
    whole gradient program at the published widths and 1 x 16,384 tokens — the
    six window-4,096 layers through `tpuft_swa_*` on a band of nine tiles a row
    (252 of the triangle's 528 a head), the two un-rotated full layers through
    `tpuft_fa_*`, both at 28 query heads over 4 KV heads (a group of 7), the 8
    held ReGLU experts of each layer through `tpuft_gmm_*`, the sliced
    vocabulary through `tpuft_ce_*` — with room for AdamW's moments beside it
    on a 16 GiB chip."""
    import os
    import sys

    root = ROOT
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    bench = Benchmark(root)
    config, traffic = bench.config("smallthinker-21b-a3b"), bench.traffic("steady-1g-16k")
    shapes = jax.eval_shape(lambda: bench.reference("early_router_moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((traffic["sequences_per_step"], traffic["seq_len"]), jnp.int32, sharding=one_chip)
    _, step = bench.program("early_router_moe_lm").train_step(config, topo.devices[0])
    compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    for name in ("tpuft_gmm_fwd", "tpuft_gmm_dlhs", "tpuft_gmm_drhs", "tpuft_ce_lse", "tpuft_ce_dlogits"):
        assert has_kernel(text, name), f"{name} is not in the compiled program"
    # the two kinds of layer read apart: one backward and, attention's output kept under remat, one
    # forward kernel a layer
    assert config["program"]["remat"] and config["program"]["remat_keeps_attention"]
    assert sorted(attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq"] * 2 + ["tpuft_fa_fwd"] * 2
    assert sorted(kernel_calls(text, "tpuft_swa_")) == ["tpuft_swa_bwd_dkdv_dq"] * 6 + ["tpuft_swa_fwd"] * 6
    # the grids, read out of the compiled calls: the band walk a step for each of the 252 tiles with a
    # visible pair a head (rows of 1 ... 8 tiles, then 24 rows of 9), the full layers the triangle's 528
    grids = bench.reader("swa_pairs_share").grids(text)
    assert sorted(g["name"] for g in grids) == ["tpuft_swa_bwd_dkdv_dq"] * 6 + ["tpuft_swa_fwd"] * 6
    # ... 28 heads: seven a grid step forward, four backward
    assert all((g["grid"], g["block_q"], g["seq"]) == ([4 if g["name"].endswith("fwd") else 7, 252], 512, 16_384)
               for g in grids), grids
    assert sorted(kernel_grids(text, "tpuft_fa_")) == [("tpuft_fa_bwd_dkdv_dq", (7, 528))] * 2 + [("tpuft_fa_fwd", (4, 528))] * 2
    # the row buffer, bf16[25600,2560] = 125 MiB, is past what XLA keeps in the fast memory: a layer's two T * k-row
    # gathers (the combine, the dispatch's transpose) are `tpuft_moe_rows` calls since PR 67, no [6, 16384, 2560] array
    assert kernel_calls(text, "tpuft_moe_") == ["tpuft_moe_rows"] * 2 * config["num_hidden_layers"] == ["tpuft_moe_rows"] * 16
    assert not [op for op, n in instructions(text) if n == 16_384 * 6 * 2_560]
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("early_router_moe_lm").total_params(config) == 643_852_800
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # 15,835,302,912 (arguments 2,575,585,280 + outputs 2,575,461,888 + temporaries 5,533,433,344 + moments
    # 5,150,822,400; builder's compile, PR 51) and an allocator's peak of 11.70 GB on the chip; with nothing kept
    # under remat 14,735,490,048, without remat 20,776,999,424; since PR 52, with several heads a grid step in the
    # attention kernels, the temporaries are 258,048 bytes more (builder's compile): 15,835,560,960; since PR 60, k
    # and v given to the kernels with their 4 KV heads and not repeated to 28, 282,052,608 fewer: 15,553,508,352; since
    # PR 65 15,422,781,440 (temporaries 5,120,911,872); since PR 67, the gathered [6, 16384, 2560] rows gone and a padded
    # copy of the row buffer come, temporaries 4,377,747,968: 14,679,617,536
    assert resident <= 14_679_617_536, f"the step needs {resident} bytes with AdamW's moments"


@pytest.mark.parametrize("kernel", ["before_forward", "before_backward", "after_forward", "after_backward"])
def test_ssm_mix_kernels_compile_for_v5e(one_chip, kernel) -> None:
    """The four `tpuft_ssmmix_*` kernels at the Nemotron cell's shape: one
    sequence of 16,384 positions, 64 heads of 64 in 8 groups over a state of
    128 in bfloat16 — u's 6,144 columns read in place in blocks of four lane
    tiles (x's eight blocks, B's two, C's two: the outputs whose turn it is
    not stay where they are), dt onto two heads a lane tile as a product with
    a 0/1 matrix and back as its transpose, the convolution's shifted reads at
    unaligned rows of a float32 scratch, the group norm's reduction over four
    lane tiles, the partial sums' blocks of one row."""
    import re

    from torchft_tpu.ops import ssm_mix

    bf16, f32 = jnp.bfloat16, jnp.float32
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    b, seq, heads, p, groups, state = 1, 16_384, 64, 64, 8, 128
    inner, bc = heads * p, groups * state
    assert ssm_mix.tile_of(seq) == 1024 and ssm_mix._after_tile(seq, inner // groups, None) == 1024
    assert ssm_mix._lanes(inner, bc) == 4
    u, wide, narrow, dt = sds((b, seq, inner + 2 * bc), bf16), sds((b, seq, inner), bf16), sds((b, seq, bc), bf16), sds((b, seq, 128), f32)
    taps, bias, column = sds((4, inner + 2 * bc), f32), sds((1, inner + 2 * bc), f32), sds((1, inner), f32)
    fn, shapes, name = {
        "before_forward": (lambda *a: ssm_mix._before_fwd_pallas(*a, p, inner, 1024), [u, dt, taps, bias], "tpuft_ssmmix_fwd"),
        "before_backward": (lambda *a: ssm_mix._before_bwd_pallas(*a, p, inner, 1024),
                            [u, dt, taps, bias, wide, wide, narrow, narrow], "tpuft_ssmmix_bwd"),
        "after_forward": (lambda *a: ssm_mix._after_fwd_pallas(*a, groups, 1e-5, 1024), [wide] * 3 + [column] * 2,
                          "tpuft_ssmmix_out_fwd"),
        "after_backward": (lambda *a: ssm_mix._after_bwd_pallas(*a, groups, 1e-5, 1024), [wide] * 3 + [column] * 2 + [wide],
                           "tpuft_ssmmix_out_bwd"),
    }[kernel]
    text = compile_text(fn, *shapes)
    assert kernel_calls(text, "tpuft_ssmmix_") == [name] and not kernel_calls(text, "tpuft_ssd_")
    # nothing between input and output in HBM: no transpose, copy or join of a [16,384, 4,096] or [16,384, 6,144] array
    assert not re.search(r"= (?:bf16|f32)\[1,16384,(?:4096|6144)\]\S* (?:copy|transpose|concatenate|fusion)\(", text)


@pytest.mark.parametrize("direction", ["forward", "forward_with_states", "backward"])
def test_state_space_kernels_compile_for_v5e(one_chip, direction) -> None:
    """`tpuft_ssd_fwd` (with and without the chunks' states) and `tpuft_ssd_bwd`
    at the Nemotron cell's shape: 64 heads of 64 in 8 groups over a state of 128,
    16,384 positions in bfloat16, the running sums float32, chunks of 128 — a
    group's eight heads a grid step, read in place out of [1, 16,384, 4,096]
    (grid (8, 128): batch * groups, chunks), a head's 64 columns picked by a lane
    mask inside a 128-lane block, the per-head columns by masked lane sums, the
    transposed-left products as Mosaic takes them."""
    from torchft_tpu.ops import ssd

    bf16, f32 = jnp.bfloat16, jnp.float32
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    seq, heads, p, groups, n = 16_384, 64, 64, 8, 128
    per_group, chunks = heads // groups, seq // ssd.CHUNK
    rows = [sds((1, seq, heads * p), bf16), sds((1, seq, groups * n), bf16), sds((1, seq, groups * n), bf16),
            sds((1, groups, seq, per_group), f32), sds((1, groups, chunks, per_group, ssd.CHUNK), f32)]
    if direction == "backward":
        text = compile_text(lambda *a: ssd._bwd_pallas(*a, p, ssd.CHUNK), *rows,
                        sds((chunks, groups, n, per_group * p), f32), sds((1, seq, heads * p), bf16))
        assert kernel_calls(text, "tpuft_ssd_") == ["tpuft_ssd_bwd"]
    else:
        text = compile_text(lambda *a: ssd._fwd_pallas(*a, p, ssd.CHUNK, direction == "forward_with_states"), *rows)
        assert kernel_calls(text, "tpuft_ssd_") == ["tpuft_ssd_fwd"]
        assert ("f32[128,8,128,512]" in text) == (direction == "forward_with_states")
    assert [grid for _, grid in kernel_grids(text, "tpuft_ssd_")] == [(groups, chunks)]


def test_grouped_matmul_at_a_width_of_1856_compiles_to_the_kernels_for_v5e(one_chip, monkeypatch) -> None:
    """An expert of 1,856 = 14.5 x 128 columns, up and down, forward and both
    gradients: `grouped_matmul` pads to 1,920 inside the call and the compiled
    program holds the three `tpuft_gmm_*` kernels twice each and no
    `ragged-dot`; the gradients keep the leaves' [8, 2,688, 1,856] and [8, 1,856,
    2,688]."""
    from torchft_tpu.ops import _pallas_util, grouped_matmul as gmm

    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    rows = 13_312  # the cell's buffer: twice the even share of 98,304 assignments over 8 of 128 experts, a tile an expert

    def loss(xs, w_up, w_down, counts):
        sizes = gmm.padded_group_sizes(counts, gmm.ROW_TILE)
        hidden = jnp.square(jax.nn.relu(gmm.grouped_matmul(xs, w_up, sizes, row_tile=gmm.ROW_TILE)))
        return jnp.sum(gmm.grouped_matmul(hidden, w_down, sizes, row_tile=gmm.ROW_TILE).astype(jnp.float32))

    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    compiled = fn.lower(sds((rows, 2688), jnp.bfloat16), sds((8, 2688, 1856), jnp.float32), sds((8, 1856, 2688), jnp.float32),
                        sds((8,), jnp.int32)).compile()
    text = compiled.as_text()
    assert sorted(kernel_calls(text, "tpuft_gmm_")) == ["tpuft_gmm_dlhs"] * 2 + ["tpuft_gmm_drhs"] * 2 + ["tpuft_gmm_fwd"] * 2
    assert "ragged-dot" not in text and "ragged_dot" not in text
    assert [tuple(o.shape) for o in jax.tree.leaves(compiled.out_info)] == [(), (rows, 2688), (8, 2688, 1856), (8, 1856, 2688)]


def test_nemotron_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip, monkeypatch) -> None:
    """The benchmark's `nemotron-twotower-30b-a3b` configuration as
    `benchmark/programs/mamba2_moe_lm.py` hands it to `TrainStep`: the whole
    gradient program at the published widths and 1 x 16,384 tokens — the four
    Mamba-2 blocks through `tpuft_ssd_fwd` twice (the forward pass, and the
    backward's own that makes the chunks' states again: the scan's output is
    kept under remat) and `tpuft_ssd_bwd` once each, the one attention block at
    32 query heads over 2 KV heads through one `tpuft_fa_fwd` and one
    `tpuft_fa_bwd_dkdv_dq`, the 8 held un-gated experts of each of the four
    expert blocks at 1,856 columns through `tpuft_gmm_*` (two projections:
    forward, its recomputation, and the two gradients each) with no
    `ragged-dot` anywhere, the sliced vocabulary through `tpuft_ce_*` — with
    room for AdamW's moments beside it on a 16 GiB chip."""
    import os
    import sys

    root = ROOT
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    bench = Benchmark(root)
    config, traffic = bench.config("nemotron-twotower-30b-a3b"), bench.traffic("steady-1g-16k")
    shapes = jax.eval_shape(lambda: bench.reference("mamba2_moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((traffic["sequences_per_step"], traffic["seq_len"]), jnp.int32, sharding=one_chip)
    _, step = bench.program("mamba2_moe_lm").train_step(config, topo.devices[0])
    compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    for name in ("tpuft_ce_lse", "tpuft_ce_dlogits"):
        assert has_kernel(text, name), f"{name} is not in the compiled program"
    assert config["program"]["remat"] and config["program"]["remat_keeps_attention"]
    assert sorted(kernel_calls(text, "tpuft_ssd_")) == ["tpuft_ssd_bwd"] * 4 + ["tpuft_ssd_fwd"] * 8
    assert set(kernel_grids(text, "tpuft_ssd_")) == {("tpuft_ssd_bwd", (8, 128)), ("tpuft_ssd_fwd", (8, 128))}
    # `ssm_mix` around it (since PR 57): each half's forward kernel twice a block (the forward pass and the block's
    # recomputation: a half keeps its inputs, so nothing runs it a third time) and its backward kernel once
    assert sorted(kernel_calls(text, "tpuft_ssmmix_")) == (
        ["tpuft_ssmmix_bwd"] * 4 + ["tpuft_ssmmix_fwd"] * 8 + ["tpuft_ssmmix_out_bwd"] * 4 + ["tpuft_ssmmix_out_fwd"] * 8)
    # before: 16 tiles of 1,024 rows x 12 blocks of 512 columns (8 of x, 2 of B, 2 of C); after: 16 tiles x 8 groups
    assert set(kernel_grids(text, "tpuft_ssmmix_")) == {
        ("tpuft_ssmmix_fwd", (1, 16, 12)), ("tpuft_ssmmix_bwd", (1, 16, 12)),
        ("tpuft_ssmmix_out_fwd", (1, 16, 8)), ("tpuft_ssmmix_out_bwd", (1, 16, 8))}
    assert sorted(attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq", "tpuft_fa_fwd"]
    assert sorted(kernel_calls(text, "tpuft_gmm_")) == (["tpuft_gmm_dlhs"] * 8 + ["tpuft_gmm_drhs"] * 8 + ["tpuft_gmm_fwd"] * 16)
    assert "ragged-dot" not in text and "ragged_dot" not in text
    # the row buffers here are under what XLA keeps in the fast memory: the gathers stay XLA's (PR 67)
    assert kernel_calls(text, "tpuft_moe_") == []
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("mamba2_moe_lm").total_params(config) == 666_962_944
    assert shapes["moe"]["w_up"].shape == (4, 8, 2688, 1856)  # no width is cut or grown in the tree
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # 15,317,239,296 (arguments 2,667,987,456 + outputs 2,667,862,528 + temporaries 4,645,685,760 + moments
    # 5,335,703,552; builder's compile, PR 56); with `ssm_mix` as kernels 14,471,001,088 (temporaries 3,799,447,552;
    # builder's compile, PR 57): the XLA halves' float32 [16,384, 6,144] arrays are gone; 14,471,033,344 since PR 60
    # (the one GQA block's k and v un-repeated: 32,256 bytes MORE, the repeated copies were never at the peak);
    # 14,695,830,528 since PR 65 (temporaries 4,024,276,992 from 3,799,479,808).  Nothing is held longer: the buffer
    # assignment's peak of LIVE bytes is the parent's to the byte, 6,121,351,714, at a `tpuft_ssd_bwd` (the attention
    # block is not at the peak), and the values of 8 MB and more in the temporaries' heap are FEWER (two [32, 16384, 128]
    # and two [16384, 3712] go, one [1, 16384, 4096] comes); the heap packs them into 3,769,811,456 bytes where the
    # parent's pack into 3,657,155,072 — and into 3,636,953,600 (temporaries 3,759,086,080, under the parent's) with
    # the backward's delta written as a row sum, the same large values to the last: the packing's, not an array's
    # (PERF.md section 6, PR 65 (7)); the cell's allocator peaks at 12.9 GB of 16.9
    assert resident <= 14_700_000_000, f"the step needs {resident} bytes with AdamW's moments"
