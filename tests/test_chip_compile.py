"""Compiles the main path's pallas kernels, and the whole flagship and
`olmoe-1b-7b` gradient programs with them inside, for a DESCRIBED `v5e:2x2`
chip — no chip attached, no chip time.  What interpret mode cannot show
(tiling, fast-memory budget, whether the kernel survives inside the jitted
step) the TPU compiler installed here refuses or accepts exactly as the chip's
would.  The other families of configurations have a file each beside this one
(`test_chip_compile_*.py`); `tests/chip_compile.py` holds what they share."""

import pytest

import jax
import jax.numpy as jnp

from chip_compile import (  # noqa: F401 — `topo` and `one_chip` are the fixtures
    ROOT, attention_calls, compile_text, elements, has_kernel, heads_a_step, instructions, kernel_calls, kernel_grids, models, one_chip,
    topo)


@pytest.mark.parametrize("width", ["flagship", "1b"])
@pytest.mark.parametrize("kernel", ["fa_fwd", "fa_bwd", "ce_lse", "ce_dlogits", "rms"])
def test_kernel_compiles_for_v5e(one_chip, width, kernel) -> None:
    cfg, batch, seq = models()[width]
    bf16 = jnp.bfloat16

    def sds(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bh, d = batch * cfg.n_heads, cfg.d_head
    n, e, v = batch * seq, cfg.d_model, cfg.vocab_size
    qkv = sds((batch, seq, cfg.n_heads * d))  # position-major: a position's heads side by side
    if kernel == "fa_fwd":
        from torchft_tpu.ops.attention import _fa_pallas_call

        text = compile_text(lambda q, k, v_: _fa_pallas_call(q, k, v_, d ** -0.5, True, q_heads=cfg.n_heads), qkv, qkv, qkv)
        names = ["tpuft_fa_fwd"]
    elif kernel == "fa_bwd":
        from torchft_tpu.ops.attention import _fa_bwd_pallas

        text = compile_text(
            lambda q, k, v_, o, lse, g: _fa_bwd_pallas(q, k, v_, o, lse, g, d ** -0.5, True, q_heads=cfg.n_heads),
            qkv, qkv, qkv, qkv, sds((bh, seq), jnp.float32), qkv,
        )
        names = ["tpuft_fa_bwd_dkdv_dq"]  # the one-pass form, as at every cell's shape
    elif kernel == "ce_lse":
        from torchft_tpu.ops.cross_entropy import _ce_lse_pallas

        text = compile_text(_ce_lse_pallas, sds((n, e)), sds((e, v)))
        names = ["tpuft_ce_lse"]
    elif kernel == "ce_dlogits":
        from torchft_tpu.ops.cross_entropy import _ce_dlogits_pallas

        text = compile_text(
            _ce_dlogits_pallas, sds((n, e)), sds((e, v)), sds((n,), jnp.int32),
            sds((n,), jnp.float32), sds((), jnp.float32),
        )
        names = ["tpuft_ce_dlogits"]
    else:
        from torchft_tpu.ops.rmsnorm import _rms_pallas

        text = compile_text(lambda x, w: _rms_pallas(x, w, 1e-6), sds((n, e)), sds((e,), jnp.float32))
        names = ["tpuft_rms"]
    for name in names:
        assert has_kernel(text, name), f"{name} is not in the compiled program"


@pytest.mark.parametrize(
    "bh,seq,d_qk,d_v",
    [(32, 4096, 128, 128), (16, 32768, 128, 128), (8, 65536, 128, 128)],
    ids=["dense_cells_4096", "roadmap_w3_32768", "largest_resident_row_65536"],
)
def test_one_pass_backward_compiles_for_v5e(one_chip, bh, seq, d_qk, d_v) -> None:
    """The backward at the dense cells' shape (8 kv blocks; Moonlight's 16 at
    256 / 128 are `test_latent_attention_kernels_compile_for_v5e`'s), at the
    32,768 positions ROADMAP W3 asks for (a 16 MiB dq row) and at the
    longest row the budget admits (32 MiB): ONE attention `tpu_custom_call`,
    whose dq accumulates in VMEM under a `vmem_limit_bytes` sized from the
    shapes, and no `tpuft_fa_bwd_dq`."""
    from torchft_tpu.ops.attention import _dq_row_resident, _fa_bwd_pallas

    assert _dq_row_resident(seq, d_qk)
    qk = jax.ShapeDtypeStruct((1, seq, bh * d_qk), jnp.bfloat16, sharding=one_chip)  # position-major, one batch entry
    v = jax.ShapeDtypeStruct((1, seq, bh * d_v), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32, sharding=one_chip)
    text = compile_text(
        lambda q, k, v_, o, l, g: _fa_bwd_pallas(q, k, v_, o, l, g, d_qk ** -0.5, True, q_heads=bh),
        qk, qk, v, v, lse, v,
    )
    assert attention_calls(text) == ["tpuft_fa_bwd_dkdv_dq"]
    # four heads a grid step at 4,096 positions, two at 32,768, one at 65,536 (two 64 MiB rows do not fit VMEM)
    assert heads_a_step(text, "tpuft_fa_", bh) == {"tpuft_fa_bwd_dkdv_dq": [{4096: 4, 32768: 2, 65536: 1}[seq]]}


def test_long_context_two_pass_backward_compiles_for_v5e(one_chip) -> None:
    """A dq row over the VMEM budget (one block more than 65,536 positions at
    128 wide): the dk/dv pass without a row plus the separate dq pass
    (`_fa_bwd_dq_kernel`)."""
    from torchft_tpu.ops.attention import _dq_row_resident, _fa_bwd_pallas

    bh, seq, d = 4, 65536 + 512, 128
    assert not _dq_row_resident(seq, d)
    qkv = jax.ShapeDtypeStruct((1, seq, bh * d), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32, sharding=one_chip)
    text = compile_text(
        lambda q, k, v, o, l, g: _fa_bwd_pallas(q, k, v, o, l, g, d ** -0.5, True, q_heads=bh),
        qkv, qkv, qkv, qkv, lse, qkv,
    )
    assert attention_calls(text) == ["tpuft_fa_bwd_dkdv", "tpuft_fa_bwd_dq"]


def test_flagship_gradient_program_compiles_with_kernels_for_v5e(
    topo, one_chip, monkeypatch
) -> None:
    """The whole jitted flagship gradient program, kernels inside.  The gate
    asks `jax.default_backend()`, which is the CPU here, so the test steers
    it on — in the test, the program has no option for it."""
    import optax

    import chip_smoke
    from torchft_tpu.models import init_params, loss_fn
    from torchft_tpu.ops import _pallas_util
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    cfg, batch, seq = chip_smoke.flagship_config()
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes
    )
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    step = TrainStep(
        ft_init_mesh({"data": 1}, devices=[topo.devices[0]]),
        optax.adamw(3e-4),
        lambda p, b: loss_fn(p, b, cfg),
    )
    compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    found = chip_smoke.kernels_in(compiled.as_text())
    assert all(found.values()), found
    ma = compiled.memory_analysis()
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
    assert resident < 16 * 2**30, f"the program needs {resident} bytes, a v5e chip has 16 GiB"


@pytest.mark.parametrize("k,n,experts,assignments", [
    (2048, 1024, 64, 8192 * 8), (1024, 2048, 64, 8192 * 8), (2048, 1408, 8, 24576), (1408, 2048, 8, 24576),
], ids=["olmoe_up_and_gate", "olmoe_down", "moonlight_up_and_gate", "moonlight_down"])
def test_grouped_matmul_kernels_compile_for_v5e(one_chip, k, n, experts, assignments) -> None:
    """The three `tpuft_gmm_*` kernels at OLMoE-1B-7B's widths and its cell's
    rows (8,192 tokens x 8 experts a token over 64 experts), and at
    Moonlight-16B-A3B's over the 8 experts one chip holds (twice their even
    share of 16,384 x 6 assignments), each expert's rows padded to the row
    tile.  An expert's whole matrix is one block at both."""
    from torchft_tpu.ops import grouped_matmul as gm

    rows = assignments + experts * gm.ROW_TILE
    assert gm._tiles(rows, k, n, gm.ROW_TILE) == (n, k, n)
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((experts, k, n), jnp.float32, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((experts,), jnp.int32, sharding=one_chip)

    def product_and_gradients(l, r, s):
        out, vjp = jax.vjp(lambda l_, r_: gm._gmm(l_, r_, s, gm.ROW_TILE, False), l, r)
        return out, vjp(out)

    text = compile_text(product_and_gradients, lhs, rhs, sizes)
    for name in ("tpuft_gmm_fwd", "tpuft_gmm_dlhs", "tpuft_gmm_drhs"):
        assert has_kernel(text, name), f"{name} is not in the compiled program"


@pytest.fixture(scope="module")
def olmoe_program(topo, one_chip):
    """The benchmark's `olmoe-1b-7b` gradient program at the published widths,
    compiled once for the tests that read it: (compiled, weight shapes)."""
    import os
    import sys

    root = ROOT
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    bench = Benchmark(root)
    config, traffic = bench.config("olmoe-1b-7b"), bench.traffic("steady-1g")
    shapes = jax.eval_shape(lambda: bench.reference("moe_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((traffic["sequences_per_step"], traffic["seq_len"]), jnp.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(_pallas_util, "on_tpu", lambda: True)
        _, step = bench.program("moe_lm").train_step(config, topo.devices[0])
        return step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile(), shapes


def test_olmoe_gradient_program_compiles_with_kernels_for_v5e(olmoe_program) -> None:
    """The benchmark's `olmoe-1b-7b` configuration as `benchmark/programs/moe_lm.py`
    hands it to `TrainStep`: the whole gradient program at the published
    widths, all 64 experts, with the grouped-matmul, attention and CE kernels
    inside, and room for AdamW's moments beside it on a 16 GiB chip."""
    compiled, shapes = olmoe_program
    text = compiled.as_text()
    for name in ("tpuft_gmm_fwd", "tpuft_gmm_dlhs", "tpuft_gmm_drhs", "tpuft_fa_fwd", "tpuft_ce_lse", "tpuft_ce_dlogits"):
        assert has_kernel(text, name), f"{name} is not in the compiled program"
    assert heads_a_step(text, "tpuft_fa_", 32) == {"tpuft_fa_fwd": [8], "tpuft_fa_bwd_dkdv_dq": [4]}  # 2 x 16 heads x 4,096
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    assert n_params == 625_616_896
    assert resident < 14 * 2**30, f"the step needs {resident} bytes with AdamW's moments, a v5e chip has 16 GiB"
    # the one sparse layer's row buffer, bf16[73728,2048] = 288 MiB: its two T * k-row gathers are `tpuft_moe_rows`
    # calls since PR 67 (no remat here: XLA shared the forward's gather with the backward, and still shares the call);
    # 11,733,697,536 bytes where PR 66's tree compiled to 11,733,536,256 (temporaries 1,723,598,336 -> 1,723,759,616)
    assert kernel_calls(text, "tpuft_moe_") == ["tpuft_moe_rows"] * 2
    assert resident <= 11_733_697_536


def test_olmoe_gradient_program_is_named_part_by_part_for_v5e(olmoe_program) -> None:
    """The op map of that program as the TPU's compiler leaves it
    (`obs.opmap.op_names` over the compiled text, what `TrainStep.op_map`
    returns on the chip): it holds every instruction the entry runs; every
    kernel and every fusion with an op_name path is booked to a part of the
    vocabulary; what stays unattributed — buffers the compiler allocates or
    concatenates in place, and `jnp.cumsum`, whose lowering is cached without
    its name stack (`op_name="reduce_window_sum"`) — holds none of the ten
    largest results by the compiler's own shapes."""
    import re

    from torchft_tpu.obs import opmap

    text = olmoe_program[0].as_text()
    ops = opmap.op_names(text, detail=True)
    entry = text[text.index("\nENTRY "):]
    ran = re.findall(r"^\s+(?:ROOT\s+)?%([\w.\-]+) = ", entry[:entry.index("\n}")], flags=re.M)
    assert len(ran) > 500 and set(ran) <= set(ops)
    booked = {name: opmap.booked(entry) for name, entry in ops.items()}
    assert {part for part, _ in booked.values()} - {None} == set(opmap.PARTS) - {"cca_mix", "kda_mix", "kda_scan", "attn_window", "dsa_index", "dsa_select", "ffn", "shared_expert", "ssm_mix", "ssm_scan", "exit_gate", "bd_noise", "bd_attn", "gdn_mix", "gdn_scan"}
    assert {direction for part, direction in booked.values() if part} == {"fwd", "bwd"}  # the cell does not rematerialise
    kernels = {name: entry for name, entry in ops.items() if "tpuft_" in entry["op_name"] and entry["opcode"] == "custom-call"}
    # attention forward and backward, `tpuft_ce_lse` and `_dlogits`, nine grouped matmuls and, since PR 67, the expert
    # layer's two `tpuft_moe_rows` calls — booked to `experts` like the grouped matmuls
    assert len(kernels) == 15 and sum("tpuft_moe_rows" in e["op_name"] for e in kernels.values()) == 2
    assert all(booked[n][0] == "experts" for n, e in kernels.items() if "tpuft_moe_rows" in e["op_name"])
    assert all(booked[name][0] in ("attn", "head_loss", "experts") for name in kernels), kernels
    working = [n for n, e in ops.items() if e["opcode"] in ("dot", "convolution", "fusion", "custom-call")]
    nameless = [n for n in working if booked[n][0] is None]
    assert nameless and len(nameless) <= len(working) // 6
    for name in nameless:
        entry = ops[name]
        assert entry["op_name"] in ("", "reduce_window_sum") and not any(
            opmap.part_of(path + "/") for path in entry.get("inside", {})), (name, entry)

    def result_bytes(name: str) -> int:
        kind, dims = re.search(r"%" + re.escape(name) + r" = \(?(\w+)\[([\d,]*)\]", text).groups()
        count = 1
        for dim in filter(None, dims.split(",")):
            count *= int(dim)
        return count * {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2}.get(kind, 4)

    largest = sorted(working, key=result_bytes, reverse=True)[:10]
    assert result_bytes(largest[0]) >= 64 * 2048 * 1024 * 4  # an expert matrix's gradient
    assert not set(largest) & set(nameless), [(n, ops[n]) for n in largest if n in nameless]


@pytest.mark.parametrize("tokens,k,width,n_rows", [(16384, 6, 2560, 25600), (32768, 8, 2048, 67584), (8192, 8, 2048, 73728)],
                         ids=["smallthinker", "keye_and_sdar", "olmoe"])
@pytest.mark.parametrize("weighted", [True, False], ids=["combine", "dispatch_transpose"])
def test_moe_rows_kernel_compiles_for_v5e(one_chip, tokens, k, width, n_rows, weighted) -> None:
    """`ops/moe_rows.py` at three cells' shapes, as `models/moe.py` calls it
    for the combine (gates) and for the dispatch's transpose (a plain sum):
    one `tpuft_moe_rows` call on a grid of T / 32 steps, a row whole tiles of
    its own (2,560 columns padded to 24 pieces of 128), no gather left and no
    array of T * k * E elements — what stands around the kernel is the
    source's turn (R rows) and the result's (T rows)."""
    from torchft_tpu.ops import moe_rows as mr

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)  # noqa: E731
    rows, dest = shape((n_rows, width), jnp.bfloat16), shape((tokens, k), jnp.int32)
    gates = shape((tokens, k), jnp.float32) if weighted else None
    assert mr._tiles(rows.shape, rows.dtype, dest.shape) and n_rows * width * 2 > mr.FAST_SOURCE_BYTES
    text = compile_text(mr.moe_rows, rows, dest, gates)
    assert kernel_grids(text, "tpuft_moe_") == [("tpuft_moe_rows", (tokens // 32,))]
    assert f"bf16[{n_rows},{-(-width // 1024) * 8},128]" in text and " gather(" not in text
    assert not [(op, n) for op, n in instructions(text) if n == tokens * k * width]  # the gathered rows are nowhere


@pytest.mark.parametrize("tokens,k,n_exp,held", [(16384, 6, 64, 8), (32768, 8, 128, 16), (16384, 8, 256, 32)],
                         ids=["moonlight_top6", "keye_top8", "laguna_top8"])
def test_expert_row_moves_compile_without_a_relayout_for_v5e(one_chip, tokens, k, n_exp, held) -> None:
    """`models/moe.py`'s gathers of a token's k rows at the Moonlight cell's
    shapes (16,384 tokens, 6 choices, the 8 held experts' buffer of 25,600
    rows), the Keye cell's (32,768 tokens, 8 choices, 67,584 rows) and the
    Laguna cell's (16,384 tokens, 8 choices of 256, 32 held: 36,864 rows),
    2,048 columns: no `reshape`, `copy` or `transpose` re-tiles the T * k * E
    gathered elements.  At 6 choices that is so because the k axis leads (six
    rows on an eight-row tile, where k is the middle axis: the relayout PR 36
    took out); at 8 the token-major form is a bitcast.  And there are two such
    gathers, not three: the combine's own and the dispatch's transpose.  The
    combine's gradients hold none — the gates' is a product of two [R, E]
    arrays on the row side, scattered as R scalars, and nothing gathers T * k
    scalars either (PR 45)."""
    import re

    from torchft_tpu.models import moe

    width = 2048
    n_rows = moe.held_rows(tokens * k, n_exp, held, 2.0)
    assert n_rows == {(6, 64): 25600, (8, 128): 67584, (8, 256): 36864}[k, n_exp] and moe._k_leads(k) == (k == 6)
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)  # noqa: E731
    rows, dy = shape((n_rows, width), jnp.bfloat16), shape((tokens, width), jnp.bfloat16)
    gates, dest = shape((tokens, k), jnp.float32), shape((tokens, k), jnp.int32)
    row_assignment = shape((n_rows,), jnp.int32)

    def combine_and_its_gradients(rows, gates, dest, row_assignment, dy):
        out, vjp = jax.vjp(lambda r, g: moe._tokens_of_rows(r, g, dest, row_assignment, False, False), rows, gates)
        return out, vjp(dy)

    def combine_gradients_alone(rows, gates, dest, row_assignment, dy):  # as the backward pass runs them: no forward beside
        return moe._tokens_bwd(False, False, (rows, gates, dest, row_assignment), dy)[:2]

    def dispatch_gradient(drows, dest, row_assignment):
        return moe._rows_bwd(False, False, (row_assignment, dest), drows)[0]

    for fn, args, gathers in ((combine_and_its_gradients, (rows, gates, dest, row_assignment, dy), 1),
                              (combine_gradients_alone, (rows, gates, dest, row_assignment, dy), 0),
                              (dispatch_gradient, (rows, dest, row_assignment), 1)):
        text = compile_text(fn, *args)
        found = instructions(text)
        whole = [(op, n) for op, n in found if n >= tokens * k * width and op != "bitcast"]  # a bitcast moves nothing
        assert whole == [("fusion", tokens * k * width)] * gathers, f"{fn.__name__}: results of T * k * E elements: {whole}"
        assert text.count(" gather(") >= 1, "the text was not read: no gather found"
        scalars = [m.group(0) for m in re.finditer(r"= \w+\[([\d,]*)\](?:\{[^}]*\})? gather\(", text)
                   if elements(m.group(1)) == tokens * k]
        assert not scalars, f"{fn.__name__}: T * k scalars are gathered: {scalars}"
        if fn is combine_gradients_alone:  # the gates' gradient lands through a scatter of float32 scalars
            assert re.search(r"= f32\[%d\](?:\{[^}]*\})? scatter\(" % (tokens * k), text), "no scatter into T * k gates"
