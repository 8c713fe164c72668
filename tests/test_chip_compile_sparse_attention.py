"""The described-chip compiles (`tests/chip_compile.py`) of the configurations
whose attention reads a part of the past it works out itself: the five
`tpuft_dsa_*` kernels and the whole `keye-vl-2.0-30b-a3b` gradient program, and
the whole `zaya1-8b` gradient program (attention in a compressed latent, the
tied head over blocks of rows)."""

import jax
import jax.numpy as jnp

from chip_compile import (  # noqa: F401 — `topo` and `one_chip` are the fixtures
    ROOT, attention_calls, compile_text, has_kernel, heads_a_step, kernel_calls, one_chip, topo)


def test_sparse_attention_kernels_compile_for_v5e(one_chip) -> None:
    """The five `tpuft_dsa_*` kernels at the Keye cell's shapes: one sequence
    of 32,768 positions, 32 query heads on 4 KV heads of 128, 16 index heads of
    64, topk 2,048 — the selection's [256, 32,768] int32 keys (32 MiB) and the
    index loss's resident key-gradient row in VMEM, the mask as the packed lower
    triangle of int8 tiles, the one-pass backward with a 16 MiB dq row."""
    from torchft_tpu.ops import sparse_attention as sa

    B, H, KV, S, D, J, Di = 1, 32, 4, 32768, 128, 16, 64

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, k, a, bt = sds((B, S, H, D)), sds((B, S, KV, D)), sds((B, J, S, Di)), sds((B, Di, S))  # q, k position-major
    w, row, lse = sds((B, S, J), jnp.float32), sds((B, S, 1), jnp.int32), sds((B, H, S), jnp.float32)
    z, mask = sds((B, S, 1), jnp.float32), sds((B, 64 * 65 // 2, 512, 512), jnp.int8)
    scale = D ** -0.5
    for name, fn, args in (
        ("tpuft_dsa_select", lambda a, bt, w: sa._select_pallas(a, bt, w, 2048), (a, bt, w)),
        ("tpuft_dsa_mask", sa._mask_pallas, (a, bt, w, row, row)),
        ("tpuft_dsa_attn_fwd", lambda q, k, v, m: sa._masked_flash_fwd(q, k, v, m, scale), (q, k, k, mask)),
        ("tpuft_dsa_index_loss", lambda *x: sa._index_loss_pallas(*x, scale), (q, k, lse, a, bt, w, z, mask)),
        ("tpuft_dsa_attn_bwd_dkdv_dq", lambda q, k, v, o, l, g, m: sa._masked_flash_bwd(q, k, v, o, l, g, m, scale),
         (q, k, k, q, lse, q, mask)),
    ):
        assert kernel_calls(compile_text(fn, *args), "tpuft_dsa_") == [name]


def test_keye_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip, monkeypatch) -> None:
    """The benchmark's `keye-vl-2.0-30b-a3b` configuration as
    `benchmark/programs/dsa_moe_lm.py` hands it to `TrainStep`: the whole
    gradient program at the published widths and the cell's 1 x 32,768 tokens
    — the indexer, the exact selection and attention over it through
    `tpuft_dsa_*`, the 16 held experts of each layer through `tpuft_gmm_*`,
    the sliced vocabulary (18,992 columns, padded for the kernels) through
    `tpuft_ce_*` — with AdamW's moments beside it on a 16 GiB chip."""
    import os
    import sys

    root = ROOT
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    bench = Benchmark(root)
    config, traffic = bench.config("keye-vl-2.0-30b-a3b"), bench.traffic("steady-1g-32k")
    assert (traffic["sequences_per_step"], traffic["seq_len"]) == (1, 32768)
    shapes = jax.eval_shape(lambda: bench.reference("dsa_moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=one_chip)
    _, step = bench.program("dsa_moe_lm").train_step(config, topo.devices[0])
    compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    for name in ("tpuft_gmm_fwd", "tpuft_gmm_dlhs", "tpuft_gmm_drhs", "tpuft_ce_lse", "tpuft_ce_dlogits"):
        assert has_kernel(text, name), f"{name} is not in the compiled program"
    # `remat_keeps_attention`, extended: a layer selects, attends forward and takes the index loss ONCE;
    # the backward pass rebuilds the mask from the kept thresholds (the second `tpuft_dsa_mask`) and runs
    # the one-pass backward kernel; no dense `tpuft_fa_*` kernel is left in the program
    layers = config["num_hidden_layers"]
    per_layer = ["tpuft_dsa_attn_bwd_dkdv_dq", "tpuft_dsa_attn_fwd", "tpuft_dsa_index_loss", "tpuft_dsa_mask",
                 "tpuft_dsa_mask", "tpuft_dsa_select"]
    assert sorted(kernel_calls(text, "tpuft_dsa_")) == sorted(per_layer * layers)
    assert attention_calls(text) == []
    # a KV head's eight query heads a grid step forward, two of them backward (16 MiB dq rows)
    assert heads_a_step(text, "tpuft_dsa_attn_", 32) == {"tpuft_dsa_attn_fwd": [8], "tpuft_dsa_attn_bwd_dkdv_dq": [2]}
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("dsa_moe_lm").total_params(config)
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # four layers, the floor: 14.82 GB by this count (PR 33); a fifth layer reads 17.29 GB; 13,303,942,144 at PR 66 and,
    # the gathered [32768, 8, 2048] rows no longer written, 12,736,049,152 since PR 67 (temporaries 5,857,412,096 ->
    # 5,289,519,104)
    assert resident <= 12_736_049_152, f"the step needs {resident} bytes with AdamW's moments"
    # the row buffer, bf16[67584,2048] = 264 MiB: a layer's two T * k-row gathers are `tpuft_moe_rows` calls (PR 67)
    assert kernel_calls(text, "tpuft_moe_") == ["tpuft_moe_rows"] * 2 * layers == ["tpuft_moe_rows"] * 8


def test_zaya_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip, monkeypatch) -> None:
    """The benchmark's `zaya1-8b` configuration as `benchmark/programs/cca_moe_lm.py`
    hands it to `TrainStep`: the whole gradient program at the published widths
    and 1 x 16,384 tokens — compressed attention through `tpuft_fa_*` at 8 query
    heads on 2 KV heads in each of four layers, the 8 held experts of each layer
    through `tpuft_gmm_*`, the tied 131,136-row head through `tpuft_ce_*` over
    blocks of 1,024 rows forward and slabs of 16,384 columns backward, a layer's
    weight gradients finished inside the layer's backward pass — with room
    for AdamW's moments beside it on a 16 GiB chip, and no array of rows x
    vocabulary anywhere in it."""
    import os
    import re
    import sys

    root = ROOT
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    bench = Benchmark(root)
    config, traffic = bench.config("zaya1-8b"), bench.traffic("steady-1g-16k")
    shapes = jax.eval_shape(lambda: bench.reference("cca_moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((traffic["sequences_per_step"], traffic["seq_len"]), jnp.int32, sharding=one_chip)
    _, step = bench.program("cca_moe_lm").train_step(config, topo.devices[0])
    compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    # attention's output kept under remat: one forward and one backward kernel a layer
    assert config["program"]["remat_keeps_attention"]
    assert sorted(attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq"] * 4 + ["tpuft_fa_fwd"] * 4
    assert heads_a_step(text, "tpuft_fa_", 8) == {"tpuft_fa_fwd": [8], "tpuft_fa_bwd_dkdv_dq": [4]}
    # three projections a layer: forward, recomputed, and the two gradients
    gmm = kernel_calls(text, "tpuft_gmm_")
    assert sorted(gmm) == ["tpuft_gmm_dlhs"] * 12 + ["tpuft_gmm_drhs"] * 12 + ["tpuft_gmm_fwd"] * 24
    # a head in pieces puts the program at the memory's edge, and there a layer's weight gradients are finished
    # inside the layer's backward pass: after each attention backward kernel its own layer's three, not all twelve
    # after the last (what the compiler chooses alone, holding 21 arrays of [17,408, 2,048] rows till then)
    late = [c for c in kernel_calls(text, "tpuft_") if c in ("tpuft_fa_bwd_dkdv_dq", "tpuft_gmm_drhs")]
    assert late == (["tpuft_fa_bwd_dkdv_dq"] + ["tpuft_gmm_drhs"] * 3) * 4
    # the head: the forward kernel once in the text, inside the loop over the 16 blocks of rows; the backward one
    # twice — inside the loop over the 8 slabs of 16,384 columns, and for the last slab of 512 (64 of them the
    # head's), a call of its own before the loop
    assert sorted(kernel_calls(text, "tpuft_ce_")) == ["tpuft_ce_dlogits"] * 2 + ["tpuft_ce_lse"]
    rows, vocab = 16_384, 131_136
    import math

    widest = max(math.prod(int(d) for d in dims.split(","))
                 for dims in re.findall(r"(?:bf16|f32|s32)\[([0-9,]+)\]", text))
    # the largest array is the embedding padded to the kernels' 131,584 columns (the head's weight): an eighth of
    # rows x vocabulary; a slab's dlogits [16,384, 16,384] are as large (`_DLOGITS_BLOCK_BYTES` to the byte),
    # twice a row block's [1,024, 131,584], which is gone
    assert widest == 131_584 * 2_048 <= rows * 131_584 // 8, widest
    assert "bf16[16384,16384]" in text and "[1024,131584]" not in text
    assert f"[{rows},{vocab}]" not in text and f"[{rows},131584]" not in text
    # the mechanism's witness: inside the head's backward loop the gradient of the table is WRITTEN, a slab's rows
    # at their place in a buffer of the leaf's own shape, and never summed — no float32 [V, E] is the result of
    # an add there, padded or not, and none of the padded shape exists at all
    looped = [line for line in text.splitlines() if "jvp(head_loss))/while/body" in line]
    table = re.compile(r"= f32\[13(?:1136|1584),2048\]\S* ([a-z-]+)\(")
    assert "dynamic-update-slice" in {m.group(1) for line in looped for m in [table.search(line)] if m}
    assert not [line for line in looped for m in [table.search(line)] if m and m.group(1) == "add"]
    assert "f32[131584,2048]" not in text
    # a 68 MiB row buffer: under the rule's size, the gathers stay XLA's (PR 67)
    assert kernel_calls(text, "tpuft_moe_") == []
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("cca_moe_lm").total_params(config) == 696_250_376
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # 15,039,388,224 (temporaries 3,899,244,032; builder's compile, PR 46), under PR 45's 15,177,589,312 with the
    # head's backward by rows.  Without the barrier a layer (`_grads_inside`) the same head compiles to
    # 15,339,575,872 with slabs of 8,192 columns and 15.84e9 with these of 16,384: the table's gradient is written
    # into the program's output buffer, which the compiler had lent to the layers' backward pass while the padded
    # accumulator (1.08 GB) sat among the temporaries, and with that room the compiler leaves all twelve
    # `tpuft_gmm_drhs` calls to the end of the program and peaks in layer 0's backward pass.  With the barrier
    # the slab's width moves nothing (8,192 and 16,384 compile to the same byte).  The chip's allocator has 16.9e9
    # (PERF.md section 6, PR 46); by rows, blocks of 2,048 took 15.88e9 at PR 41
    assert resident <= 15.2e9, f"the step needs {resident} bytes with AdamW's moments"
