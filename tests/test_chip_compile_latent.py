"""The described-chip compiles (`tests/chip_compile.py`) of the configurations
with latent attention: the sigmoid router, the attention kernels at 256 / 128
and the whole `moonlight-16b-a3b` gradient program with its memory bound; the
`tpuft_kda_*` and `tpuft_kdamix_*` kernels and the whole `kimi-linear-48b-a3b`
gradient program (Kimi Delta Attention 3 : 1 with an unrotated latent layer)."""

import pytest

import jax
import jax.numpy as jnp

from chip_compile import (  # noqa: F401 — `topo` and `one_chip` are the fixtures
    ROOT, attention_calls, compile_text, has_kernel, heads_a_step, instructions, kernel_calls, kernel_grids, one_chip, topo)


def test_sigmoid_router_compiles_without_a_gather_for_v5e(one_chip) -> None:
    """`route`'s sigmoid branch at the Moonlight cell's shapes (16,384 tokens,
    a router of 64, 6 chosen) with its gradient: the chosen scores are a
    masked sum, so nothing gathers T * k scalars along a 6-wide axis or
    scatter-adds them back."""
    from torchft_tpu.models import moe

    tokens, k, width, n_exp = 16384, 6, 2048, 64
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)  # noqa: E731
    x, router = shape((2, tokens // 2, width), jnp.bfloat16), shape((width, n_exp), jnp.float32)
    bias, ct = shape((n_exp,), jnp.float32), shape((2, tokens // 2, k), jnp.float32)

    def gates_and_their_gradients(x, router, bias, ct):
        gate_vals, vjp = jax.vjp(lambda x_, r: moe.route(x_, r, k, True, score="sigmoid", bias=bias, scale=2.446)[2],
                                 x, router)
        return gate_vals, vjp(ct)

    text = compile_text(gates_and_their_gradients, x, router, bias, ct)  # fusions' bodies and all
    assert " sort(" in text or "topk" in text.lower(), "top_k is not there: the text was not read"
    assert " gather(" not in text and " scatter(" not in text


def test_latent_attention_kernels_compile_for_v5e(one_chip) -> None:
    """`tpuft_fa_*` at latent attention's widths and the Moonlight cell's
    shapes: 2 x 16 heads, 8,192 positions, query and key 256 wide (192 padded
    to a lane multiple), value and output 128; 16 key blocks and an 8 MiB dq
    row, so the backward is the one kernel."""
    from torchft_tpu.ops.attention import _fa_bwd_pallas, _fa_pallas_call

    bh, seq, d_qk, d_v = 32, 8192, 256, 128
    qk = jax.ShapeDtypeStruct((bh, seq, d_qk), jnp.bfloat16, sharding=one_chip)  # an entry a head, as `flash_attention` hands padded heads over
    v = jax.ShapeDtypeStruct((bh, seq, d_v), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32, sharding=one_chip)
    text = compile_text(lambda q, k, v_: _fa_pallas_call(q, k, v_, 192 ** -0.5, True, q_heads=1), qk, qk, v)
    assert attention_calls(text) == ["tpuft_fa_fwd"] and heads_a_step(text, "tpuft_fa_", bh) == {"tpuft_fa_fwd": [8]}
    text = compile_text(lambda q, k, v_, o, l, g: _fa_bwd_pallas(q, k, v_, o, l, g, 192 ** -0.5, True, q_heads=1), qk, qk, v, v, lse, v)
    assert attention_calls(text) == ["tpuft_fa_bwd_dkdv_dq"] and heads_a_step(text, "tpuft_fa_", bh) == {"tpuft_fa_bwd_dkdv_dq": [4]}


def test_moonlight_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip, monkeypatch) -> None:
    """The benchmark's `moonlight-16b-a3b` configuration as
    `benchmark/programs/mla_moe_lm.py` hands it to `TrainStep`: the whole
    gradient program at the published widths — latent attention through
    `tpuft_fa_*`, the 8 held experts of each sparse layer through
    `tpuft_gmm_*`, the leading dense layer, the sliced vocabulary through
    `tpuft_ce_*` — with room for AdamW's moments beside it on a 16 GiB chip."""
    import os
    import sys

    root = ROOT
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    bench = Benchmark(root)
    config, traffic = bench.config("moonlight-16b-a3b"), bench.traffic("steady-1g-8k")
    shapes = jax.eval_shape(lambda: bench.reference("mla_moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((traffic["sequences_per_step"], traffic["seq_len"]), jnp.int32, sharding=one_chip)
    _, step = bench.program("mla_moe_lm").train_step(config, topo.devices[0])
    compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    for name in ("tpuft_fa_fwd", "tpuft_fa_bwd_dkdv_dq", "tpuft_gmm_fwd", "tpuft_gmm_dlhs",
                 "tpuft_gmm_drhs", "tpuft_ce_lse", "tpuft_ce_dlogits"):
        assert has_kernel(text, name), f"{name} is not in the compiled program"
    # `remat_keeps_attention`: one forward attention kernel a layer, not a second in the backward
    # pass, and ONE backward kernel a layer: no `tpuft_fa_bwd_dq` with its recomputed scores
    calls = attention_calls(text)
    layers = config["num_hidden_layers"]
    assert sorted(calls) == ["tpuft_fa_bwd_dkdv_dq"] * layers + ["tpuft_fa_fwd"] * layers, calls
    # 2 x 16 heads: eight a grid step forward, four backward (8 MiB dq rows)
    assert heads_a_step(text, "tpuft_fa_", 32) == {"tpuft_fa_fwd": [8], "tpuft_fa_bwd_dkdv_dq": [4]}
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    assert n_params == bench.flops("mla_moe_lm").total_params(config)
    assert resident < 14.5e9, f"the step needs {resident} bytes with AdamW's moments; the cut's bound is 14.5 GB"
    # what the two-kernel backward compiled to (PR 31): dq stays in VMEM until it is bf16, so the
    # one-pass kernel brings no f32 dq, 268 MB a layer here, into HBM
    # (14,339,268,608 then; 14,340,042,752 since PR 34: the kernels alone compile to the same
    # temporaries, the program's schedule around the copies of the walk's tables holds 0.77 MB more)
    # 13,910,258,176 since PR 36: the experts' gathered rows are [k, T, E], so no copy of them
    # re-tiled to [T, 6 -> 8, E] is held (temporaries 3,637,552,640 -> 3,207,768,064)
    # (PR 65: heads of 192 columns reach the kernels folded into the batch, in the form of before it: the same bytes)
    assert resident <= 13.915e9, (
        f"{resident} bytes: the backward's dq has left VMEM in f32, or the experts' gathered rows are laid out again")
    gathered = 16384 * 6 * 2048
    assert not [op for op, n in instructions(text) if op in ("reshape", "copy") and n == gathered]
    # the row buffer, bf16[25600,2048] = 100 MiB, fits the fast memory: the gathers stay XLA's (no `tpuft_moe_rows`,
    # PR 67) and XLA still prefetches four of the layers' buffers there behind `tpuft_gmm_fwd` (PR 65 (8))
    assert kernel_calls(text, "tpuft_moe_") == []
    import re

    prefetched = [line for line in text.splitlines() if " copy-start(%tpuft_gmm_fwd" in line
                  and re.search(r"= \(bf16\[25600,2048\]\{[^}]*S\(1\)\}", line)]
    assert len(prefetched) == 4, f"{len(prefetched)} row buffers are prefetched into the fast memory"


@pytest.mark.parametrize("direction", ["forward", "forward_with_states", "backward"])
def test_delta_rule_kernels_compile_for_v5e(one_chip, direction) -> None:
    """`tpuft_kda_fwd` (with and without the chunks' states) and `tpuft_kda_bwd`
    at the Kimi cell's shape: 32 heads x 16,384 positions x 128 in bfloat16, g
    float32, chunks of 64 — the level masks and the stacked 0/1 sums resident in
    VMEM, the [64, 64] products, the transposed-left products and the squarings
    of the solve as Mosaic takes them — and, since PR 50, several heads' chunk
    a grid step: the compiled call's grid is (32 / H, 256) with the H that
    `_heads_per_step` reads from the 32 heads, above 1."""
    from torchft_tpu.ops import delta_attention as da

    bf16, f32 = jnp.bfloat16, jnp.float32
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    bh, seq, d = 32, 16_384, 128
    rows = [sds((bh, seq, d), bf16)] * 3 + [sds((bh, seq, d), f32), sds((bh, seq), f32)]
    if direction == "backward":
        text = compile_text(lambda *a: da._bwd_pallas(*a, da.CHUNK), *rows, sds((bh, seq // da.CHUNK, d, d), f32), sds((bh, seq, d), bf16))
        assert kernel_calls(text, "tpuft_kda_") == ["tpuft_kda_bwd"]
    else:
        text = compile_text(lambda *a: da._fwd_pallas(*a, da.CHUNK, direction == "forward_with_states"), *rows)
        assert kernel_calls(text, "tpuft_kda_") == ["tpuft_kda_fwd"]
        assert ("f32[32,256,128,128]" in text) == (direction == "forward_with_states")
    heads = da._heads_per_step(bh)
    assert heads > 1 and [grid for _, grid in kernel_grids(text, "tpuft_kda_")] == [(bh // heads, seq // da.CHUNK)]


@pytest.mark.parametrize("kernel", ["before_forward", "before_backward", "after_forward", "after_backward"])
def test_kda_mix_kernels_compile_for_v5e(one_chip, kernel) -> None:
    """The four `tpuft_kdamix_*` kernels at the Kimi cell's shape: one sequence
    of 16,384 positions x 32 heads of 128 in bfloat16, tiles of 1,024 rows
    worked through in blocks of 64 — a head's lane tile read out of
    [1, 16,384, 4,096] and written head-major, the convolution's shifted reads
    at unaligned rows of a float32 scratch, the lane reductions of the norms,
    the partial sums' blocks of one row."""
    import re

    from torchft_tpu.ops import kda_mix

    bf16, f32 = jnp.bfloat16, jnp.float32
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    b, seq, h, d = 1, 16_384, 32, 128
    tile = kda_mix.tile_of(seq)
    assert tile == 1024
    joined, major, decay = sds((b, seq, h * d), bf16), sds((b, h, seq, d), bf16), sds((b, h, seq, d), f32)
    taps, column, norm = sds((3, 4, h * d), f32), sds((1, h * d), f32), sds((1, d), f32)
    fn, shapes, name = {
        "before_forward": (lambda *a: kda_mix._before_fwd_pallas(*a, tile), [joined] * 4 + [taps, column, column],
                           "tpuft_kdamix_fwd"),
        "before_backward": (lambda *a: kda_mix._before_bwd_pallas(*a, tile),
                            [joined] * 4 + [taps, column, column] + [major] * 3 + [decay], "tpuft_kdamix_bwd"),
        "after_forward": (lambda *a: kda_mix._after_fwd_pallas(*a, 1e-5, tile), [major, joined, norm, column],
                          "tpuft_kdamix_out_fwd"),
        "after_backward": (lambda *a: kda_mix._after_bwd_pallas(*a, 1e-5, tile), [major, joined, norm, column, joined],
                           "tpuft_kdamix_out_bwd"),
    }[kernel]
    text = compile_text(fn, *shapes)
    assert kernel_calls(text, "tpuft_kdamix_") == [name] and not kernel_calls(text, "tpuft_kda_")
    # nothing between input and output in HBM: no transpose or copy of a [16,384, 4,096] array beside the call
    assert not re.search(r"= (?:bf16|f32)\[1,(?:16384,4096|32,16384,128)\]\S* (?:copy|transpose)\(", text)


def test_kimi_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip, monkeypatch) -> None:
    """The benchmark's `kimi-linear-48b-a3b` configuration as
    `benchmark/programs/kda_mla_moe_lm.py` hands it to `TrainStep`: the whole
    gradient program at the published widths and 1 x 16,384 tokens — four Kimi
    Delta Attention layers through `tpuft_kda_*`, the one latent layer through
    `tpuft_fa_*` at 32 heads and 256 / 128 (192 padded), the 8 held experts of
    each of four sparse layers through `tpuft_gmm_*`, the 20,480-row head
    through `tpuft_ce_*` — with room for AdamW's moments beside it on a 16 GiB
    chip."""
    import os
    import sys

    root = ROOT
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util
    from torchft_tpu.ops import delta_attention as da

    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    bench = Benchmark(root)
    config, traffic = bench.config("kimi-linear-48b-a3b"), bench.traffic("steady-1g-16k")
    shapes = jax.eval_shape(lambda: bench.reference("kda_mla_moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((traffic["sequences_per_step"], traffic["seq_len"]), jnp.int32, sharding=one_chip)
    _, step = bench.program("kda_mla_moe_lm").train_step(config, topo.devices[0])
    compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    # what `program.why` states: a KDA layer runs the forward kernel TWICE (the forward pass, and the backward's
    # pass that makes the chunks' states again: its output is kept under remat, so no third run recomputes it)
    # and the backward kernel once; the latent layer's attention output is kept too, one kernel each way
    assert config["program"]["remat"] and config["program"]["remat_keeps_attention"]
    assert sorted(kernel_calls(text, "tpuft_kda_")) == ["tpuft_kda_bwd"] * 4 + ["tpuft_kda_fwd"] * 8
    # each of the twelve carries H heads' chunk a grid step (PR 50): 32 heads, 256 chunks of 64 positions
    heads = da._heads_per_step(32)
    assert heads > 1 and [grid for _, grid in kernel_grids(text, "tpuft_kda_")] == [(32 // heads, 16_384 // da.CHUNK)] * 12
    # `kda_mix` around it (since PR 49): each half's forward kernel twice a layer (the forward pass and the layer's
    # recomputation: a half keeps its inputs, so nothing runs it a third time) and its backward kernel once
    assert sorted(kernel_calls(text, "tpuft_kdamix_")) == (
        ["tpuft_kdamix_bwd"] * 4 + ["tpuft_kdamix_fwd"] * 8 + ["tpuft_kdamix_out_bwd"] * 4 + ["tpuft_kdamix_out_fwd"] * 8)
    assert sorted(attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq", "tpuft_fa_fwd"]
    # the latent layer's 32 heads at 256 / 128: eight a grid step forward, two backward (16 MiB dq rows)
    assert heads_a_step(text, "tpuft_fa_", 32) == {"tpuft_fa_fwd": [8], "tpuft_fa_bwd_dkdv_dq": [2]}
    # three projections a sparse layer: forward, recomputed, and the two gradients
    gmm = kernel_calls(text, "tpuft_gmm_")
    assert sorted(gmm) == ["tpuft_gmm_dlhs"] * 12 + ["tpuft_gmm_drhs"] * 12 + ["tpuft_gmm_fwd"] * 24
    assert "tpuft_ce_lse" in kernel_calls(text, "tpuft_ce_") and "tpuft_ce_dlogits" in kernel_calls(text, "tpuft_ce_")
    # the chunks' states exist only inside a layer's backward pass: float32 [32, 256, 128, 128], 537 MB
    assert "f32[32,256,128,128]" in text
    assert kernel_calls(text, "tpuft_moe_") == []  # row buffers under the rule's size: XLA's gathers (PR 67)
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("kda_mla_moe_lm").total_params(config) == 602_449_792
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # 13,827,982,336 (temporaries 4,188,533,760; builder's compile, PR 48).  Without the two checkpoints inside
    # `kda_mix` (`_kda_mixer`: each half keeps its inputs and nothing between) the same program compiles to
    # 15,980,264,960: some twenty float32 [16,384, 4,096] arrays a layer are alive at once.  With the halves as
    # kernels (PR 49) 13,817,533,952 (temporaries 4,178,085,376; builder's compile, PR 49): not above PR 48's
    assert resident <= 13_827_982_336, f"the step needs {resident} bytes with AdamW's moments"
