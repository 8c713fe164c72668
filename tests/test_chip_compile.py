"""Compiles the main path's pallas kernels, and one whole flagship gradient
program with them inside, for a DESCRIBED `v5e:2x2` chip — no chip attached,
no chip time.  What interpret mode cannot show (tiling, fast-memory budget,
whether the kernel survives inside the jitted step) the TPU compiler
installed here refuses or accepts exactly as the chip's would.

The topology is described inside a module-scoped fixture of this file and
nowhere else: only one process may load the TPU's library, so the call must
not run while any module is imported (every xdist worker imports every test
file).  Everything compiles in this test's own process, with the persistent
compile cache off around it (a described-device entry cannot be read back).
A compile that passes is not a chip run and is never reported as one.
"""

import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _models():
    import chip_smoke

    return {"flagship": chip_smoke.flagship_config(), "1b": chip_smoke.large_config()}


def _compile(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _has_kernel(text: str, name: str) -> bool:
    import chip_smoke

    return chip_smoke.has_kernel(text, name)


@pytest.mark.parametrize("width", ["flagship", "1b"])
@pytest.mark.parametrize("kernel", ["fa_fwd", "fa_bwd", "ce_lse", "ce_dlogits", "rms"])
def test_kernel_compiles_for_v5e(one_chip, width, kernel) -> None:
    cfg, batch, seq = _models()[width]
    bf16 = jnp.bfloat16

    def sds(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bh, d = batch * cfg.n_heads, cfg.d_head
    n, e, v = batch * seq, cfg.d_model, cfg.vocab_size
    qkv = sds((bh, seq, d))
    if kernel == "fa_fwd":
        from torchft_tpu.ops.attention import _fa_pallas_call

        text = _compile(lambda q, k, v_: _fa_pallas_call(q, k, v_, d ** -0.5, True), qkv, qkv, qkv)
        names = ["tpuft_fa_fwd"]
    elif kernel == "fa_bwd":
        from torchft_tpu.ops.attention import _fa_bwd_pallas

        text = _compile(
            lambda q, k, v_, o, lse, g: _fa_bwd_pallas(q, k, v_, o, lse, g, d ** -0.5, True),
            qkv, qkv, qkv, qkv, sds((bh, seq), jnp.float32), qkv,
        )
        names = ["tpuft_fa_bwd_dkdv_dq"]  # the one-pass form, as at every cell's shape
    elif kernel == "ce_lse":
        from torchft_tpu.ops.cross_entropy import _ce_lse_pallas

        text = _compile(_ce_lse_pallas, sds((n, e)), sds((e, v)))
        names = ["tpuft_ce_lse"]
    elif kernel == "ce_dlogits":
        from torchft_tpu.ops.cross_entropy import _ce_dlogits_pallas

        text = _compile(
            _ce_dlogits_pallas, sds((n, e)), sds((e, v)), sds((n,), jnp.int32),
            sds((n,), jnp.float32), sds((), jnp.float32),
        )
        names = ["tpuft_ce_dlogits"]
    else:
        from torchft_tpu.ops.rmsnorm import _rms_pallas

        text = _compile(lambda x, w: _rms_pallas(x, w, 1e-6), sds((n, e)), sds((e,), jnp.float32))
        names = ["tpuft_rms"]
    for name in names:
        assert _has_kernel(text, name), f"{name} is not in the compiled program"


def _attention_calls(text: str) -> list:
    """The names of the compiled program's attention kernels, one entry per
    `tpu_custom_call` (a pallas kernel's `name=` is in its metadata)."""
    import re

    return [m.group(0) for line in text.splitlines() if "tpu_custom_call" in line and "custom-call(" in line
            for m in [re.search(r"tpuft_fa_[a-z_]*[a-z]", line)] if m]


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
    qk = jax.ShapeDtypeStruct((bh, seq, d_qk), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((bh, seq, d_v), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32, sharding=one_chip)
    text = _compile(
        lambda q, k, v_, o, l, g: _fa_bwd_pallas(q, k, v_, o, l, g, d_qk ** -0.5, True),
        qk, qk, v, v, lse, v,
    )
    assert _attention_calls(text) == ["tpuft_fa_bwd_dkdv_dq"]
    # four heads a grid step at 4,096 positions, two at 32,768, one at 65,536 (two 64 MiB rows do not fit VMEM)
    assert _heads_a_step(text, "tpuft_fa_", bh) == {"tpuft_fa_bwd_dkdv_dq": [{4096: 4, 32768: 2, 65536: 1}[seq]]}


def test_long_context_two_pass_backward_compiles_for_v5e(one_chip) -> None:
    """A dq row over the VMEM budget (one block more than 65,536 positions at
    128 wide): the dk/dv pass without a row plus the separate dq pass
    (`_fa_bwd_dq_kernel`)."""
    from torchft_tpu.ops.attention import _dq_row_resident, _fa_bwd_pallas

    bh, seq, d = 4, 65536 + 512, 128
    assert not _dq_row_resident(seq, d)
    qkv = jax.ShapeDtypeStruct((bh, seq, d), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32, sharding=one_chip)
    text = _compile(
        lambda q, k, v, o, l, g: _fa_bwd_pallas(q, k, v, o, l, g, d ** -0.5, True),
        qkv, qkv, qkv, qkv, lse, qkv,
    )
    assert _attention_calls(text) == ["tpuft_fa_bwd_dkdv", "tpuft_fa_bwd_dq"]


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

    text = _compile(product_and_gradients, lhs, rhs, sizes)
    for name in ("tpuft_gmm_fwd", "tpuft_gmm_dlhs", "tpuft_gmm_drhs"):
        assert _has_kernel(text, name), f"{name} is not in the compiled program"


@pytest.fixture(scope="module")
def olmoe_program(topo, one_chip):
    """The benchmark's `olmoe-1b-7b` gradient program at the published widths,
    compiled once for the tests that read it: (compiled, weight shapes)."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
        assert _has_kernel(text, name), f"{name} is not in the compiled program"
    assert _heads_a_step(text, "tpuft_fa_", 32) == {"tpuft_fa_fwd": [8], "tpuft_fa_bwd_dkdv_dq": [4]}  # 2 x 16 heads x 4,096
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    assert n_params == 625_616_896
    assert resident < 14 * 2**30, f"the step needs {resident} bytes with AdamW's moments, a v5e chip has 16 GiB"


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
    assert {part for part, _ in booked.values()} - {None} == set(opmap.PARTS) - {"cca_mix", "kda_mix", "kda_scan", "attn_window", "dsa_index", "dsa_select", "ffn", "shared_expert", "ssm_mix", "ssm_scan"}
    assert {direction for part, direction in booked.values() if part} == {"fwd", "bwd"}  # the cell does not rematerialise
    kernels = {name: entry for name, entry in ops.items() if "tpuft_" in entry["op_name"] and entry["opcode"] == "custom-call"}
    assert len(kernels) == 13  # attention forward and backward, `tpuft_ce_lse` and `_dlogits`, nine grouped matmuls
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


def _instructions(text: str) -> list:
    """(opcode, elements of the result) of every instruction with one array
    for a result in a compiled program's entry computation: what runs as an
    instruction of its own (a `reshape` inside a fusion's body costs what the
    fusion costs)."""
    import re

    found = []
    text = text[text.index("ENTRY "):]
    for m in re.finditer(r"^\s*(?:ROOT )?%?[\w.-]+ = \w+\[([\d,]*)\](?:\{[^}]*\})? ([\w-]+)\(", text, re.M):
        found.append((m.group(2), _elements(m.group(1))))
    return found


def _elements(dims: str) -> int:
    """Elements of an array whose shape the compiled text writes as `16384,8`."""
    import math

    return math.prod(int(d) for d in dims.split(",") if d)


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
        out, vjp = jax.vjp(lambda r, g: moe._tokens_of_rows(r, g, dest, row_assignment, False), rows, gates)
        return out, vjp(dy)

    def combine_gradients_alone(rows, gates, dest, row_assignment, dy):  # as the backward pass runs them: no forward beside
        return moe._tokens_bwd(False, (rows, gates, dest, row_assignment), dy)[:2]

    def dispatch_gradient(drows, dest, row_assignment):
        return moe._rows_bwd(False, (row_assignment, dest), drows)[0]

    for fn, args, gathers in ((combine_and_its_gradients, (rows, gates, dest, row_assignment, dy), 1),
                              (combine_gradients_alone, (rows, gates, dest, row_assignment, dy), 0),
                              (dispatch_gradient, (rows, dest, row_assignment), 1)):
        text = _compile(fn, *args)
        found = _instructions(text)
        whole = [(op, n) for op, n in found if n >= tokens * k * width and op != "bitcast"]  # a bitcast moves nothing
        assert whole == [("fusion", tokens * k * width)] * gathers, f"{fn.__name__}: results of T * k * E elements: {whole}"
        assert text.count(" gather(") >= 1, "the text was not read: no gather found"
        scalars = [m.group(0) for m in re.finditer(r"= \w+\[([\d,]*)\](?:\{[^}]*\})? gather\(", text)
                   if _elements(m.group(1)) == tokens * k]
        assert not scalars, f"{fn.__name__}: T * k scalars are gathered: {scalars}"
        if fn is combine_gradients_alone:  # the gates' gradient lands through a scatter of float32 scalars
            assert re.search(r"= f32\[%d\](?:\{[^}]*\})? scatter\(" % (tokens * k), text), "no scatter into T * k gates"


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

    text = _compile(gates_and_their_gradients, x, router, bias, ct)  # fusions' bodies and all
    assert " sort(" in text or "topk" in text.lower(), "top_k is not there: the text was not read"
    assert " gather(" not in text and " scatter(" not in text


def test_latent_attention_kernels_compile_for_v5e(one_chip) -> None:
    """`tpuft_fa_*` at latent attention's widths and the Moonlight cell's
    shapes: 2 x 16 heads, 8,192 positions, query and key 256 wide (192 padded
    to a lane multiple), value and output 128; 16 key blocks and an 8 MiB dq
    row, so the backward is the one kernel."""
    from torchft_tpu.ops.attention import _fa_bwd_pallas, _fa_pallas_call

    bh, seq, d_qk, d_v = 32, 8192, 256, 128
    qk = jax.ShapeDtypeStruct((bh, seq, d_qk), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((bh, seq, d_v), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, seq), jnp.float32, sharding=one_chip)
    text = _compile(lambda q, k, v_: _fa_pallas_call(q, k, v_, 192 ** -0.5, True), qk, qk, v)
    assert _attention_calls(text) == ["tpuft_fa_fwd"] and _heads_a_step(text, "tpuft_fa_", bh) == {"tpuft_fa_fwd": [8]}
    text = _compile(lambda q, k, v_, o, l, g: _fa_bwd_pallas(q, k, v_, o, l, g, 192 ** -0.5, True), qk, qk, v, v, lse, v)
    assert _attention_calls(text) == ["tpuft_fa_bwd_dkdv_dq"] and _heads_a_step(text, "tpuft_fa_", bh) == {"tpuft_fa_bwd_dkdv_dq": [4]}


def test_moonlight_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip, monkeypatch) -> None:
    """The benchmark's `moonlight-16b-a3b` configuration as
    `benchmark/programs/mla_moe_lm.py` hands it to `TrainStep`: the whole
    gradient program at the published widths — latent attention through
    `tpuft_fa_*`, the 8 held experts of each sparse layer through
    `tpuft_gmm_*`, the leading dense layer, the sliced vocabulary through
    `tpuft_ce_*` — with room for AdamW's moments beside it on a 16 GiB chip."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
        assert _has_kernel(text, name), f"{name} is not in the compiled program"
    # `remat_keeps_attention`: one forward attention kernel a layer, not a second in the backward
    # pass, and ONE backward kernel a layer: no `tpuft_fa_bwd_dq` with its recomputed scores
    calls = _attention_calls(text)
    layers = config["num_hidden_layers"]
    assert sorted(calls) == ["tpuft_fa_bwd_dkdv_dq"] * layers + ["tpuft_fa_fwd"] * layers, calls
    # 2 x 16 heads: eight a grid step forward, four backward (8 MiB dq rows)
    assert _heads_a_step(text, "tpuft_fa_", 32) == {"tpuft_fa_fwd": [8], "tpuft_fa_bwd_dkdv_dq": [4]}
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
    assert resident <= 13.915e9, (
        f"{resident} bytes: the backward's dq has left VMEM in f32, or the experts' gathered rows are laid out again")
    gathered = 16384 * 6 * 2048
    assert not [op for op, n in _instructions(text) if op in ("reshape", "copy") and n == gathered]


def _kernel_calls(text: str, prefix: str) -> list:
    """As `_attention_calls`, for the kernels whose names start with `prefix`."""
    import re

    return [m.group(0) for line in text.splitlines() if "tpu_custom_call" in line and "custom-call(" in line
            for m in [re.search(prefix + r"[a-z_]*[a-z]", line)] if m]


def _kernel_grids(text: str, prefix: str) -> list:
    """[(name, grid)] of the compiled kernel calls whose names start with
    `prefix`: the grid is `iteration_bounds` of the kernel's serialised body."""
    import base64
    import re

    from jax._src.lib.mlir import ir

    found = []
    for line in text.splitlines():
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        name = re.search(prefix + r"[a-z_]*[a-z]", line[:line.find("backend_config=")])
        if "tpu_custom_call" not in line or not body or not name:
            continue
        context = ir.Context()
        context.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(body.group(1)), context)
        kernel = next(op for op in module.body.operations if "iteration_bounds" in op.attributes)
        found.append((name.group(0), tuple(kernel.attributes["iteration_bounds"])))
    return found


def _heads_a_step(text: str, prefix: str, bh: int) -> dict:
    """{kernel name: heads a grid step} over the compiled calls whose names
    start with `prefix`, each at batch * heads = ``bh``: the grid's outer axis
    is bh / H (since PR 52)."""
    found = {}
    for name, grid in _kernel_grids(text, prefix):
        assert bh % grid[0] == 0, (name, grid)
        found.setdefault(name, set()).add(bh // grid[0])
    return {name: sorted(heads) for name, heads in found.items()}


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

    q, k, a, bt = sds((B, H, S, D)), sds((B, KV, S, D)), sds((B, J, S, Di)), sds((B, Di, S))
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
        assert _kernel_calls(_compile(fn, *args), "tpuft_dsa_") == [name]


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

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
        assert _has_kernel(text, name), f"{name} is not in the compiled program"
    # `remat_keeps_attention`, extended: a layer selects, attends forward and takes the index loss ONCE;
    # the backward pass rebuilds the mask from the kept thresholds (the second `tpuft_dsa_mask`) and runs
    # the one-pass backward kernel; no dense `tpuft_fa_*` kernel is left in the program
    layers = config["num_hidden_layers"]
    per_layer = ["tpuft_dsa_attn_bwd_dkdv_dq", "tpuft_dsa_attn_fwd", "tpuft_dsa_index_loss", "tpuft_dsa_mask",
                 "tpuft_dsa_mask", "tpuft_dsa_select"]
    assert sorted(_kernel_calls(text, "tpuft_dsa_")) == sorted(per_layer * layers)
    assert _attention_calls(text) == []
    # a KV head's eight query heads a grid step forward, two of them backward (16 MiB dq rows)
    assert _heads_a_step(text, "tpuft_dsa_attn_", 32) == {"tpuft_dsa_attn_fwd": [8], "tpuft_dsa_attn_bwd_dkdv_dq": [2]}
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("dsa_moe_lm").total_params(config)
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # four layers, the floor: 14.82 GB by this count (PR 33); a fifth layer reads 17.29 GB
    assert resident < 14.9e9, f"the step needs {resident} bytes with AdamW's moments"


@pytest.mark.parametrize("window", [512, 1536])
def test_windowed_attention_kernels_compile_for_v5e(one_chip, window) -> None:
    """The band-walk kernels at the Laguna cell's window layers (64 heads x
    16,384 x 128, a window of 512) and at a window of three tiles: one forward
    and ONE backward `tpu_custom_call` under the `tpuft_swa_*` names — the
    tile's one unsigned comparison and the walk's traced row and column ends
    are what interpret mode cannot refuse."""
    from torchft_tpu.ops.attention import _fa_bwd_pallas, _fa_pallas_call

    qkv = jax.ShapeDtypeStruct((64, 16384, 128), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((64, 16384), jnp.float32, sharding=one_chip)
    text = _compile(lambda q, k, v: _fa_pallas_call(q, k, v, 128 ** -0.5, True, window=window), qkv, qkv, qkv)
    assert _kernel_calls(text, "tpuft_swa_") == ["tpuft_swa_fwd"] and not _attention_calls(text)
    text = _compile(lambda q, k, v, o, l, g: _fa_bwd_pallas(q, k, v, o, l, g, 128 ** -0.5, True, window=window),
                    qkv, qkv, qkv, qkv, lse, qkv)
    assert _kernel_calls(text, "tpuft_swa_") == ["tpuft_swa_bwd_dkdv_dq"] and not _attention_calls(text)


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

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
        assert _has_kernel(text, name), f"{name} is not in the compiled program"
    # the two kinds of layer read apart: one backward and, attention's output kept under remat, one
    # forward kernel a layer
    assert config["program"]["remat_keeps_attention"]
    assert sorted(_attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq"] * 2 + ["tpuft_fa_fwd"] * 2
    assert sorted(_kernel_calls(text, "tpuft_swa_")) == ["tpuft_swa_bwd_dkdv_dq"] * 3 + ["tpuft_swa_fwd"] * 3
    # the band's grid, as `swa_pairs_share` reads it out of the compiled calls: a step for each of the
    # 2n - 1 = 63 tiles with a visible pair a head, where the triangle has 528
    grids = bench.reader("swa_pairs_share").grids(text)
    assert sorted(g["name"] for g in grids) == ["tpuft_swa_bwd_dkdv_dq"] * 3 + ["tpuft_swa_fwd"] * 3
    # ... eight heads a grid step forward and four backward (two 16 MiB dq rows and their tiles a pair of heads)
    assert all((g["grid"], g["block_q"], g["seq"]) == ([8 if g["name"].endswith("fwd") else 16, 63], 512, 16_384)
               for g in grids), grids
    assert sorted(_kernel_grids(text, "tpuft_fa_")) == [("tpuft_fa_bwd_dkdv_dq", (12, 528))] * 2 + [("tpuft_fa_fwd", (6, 528))] * 2
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("swa_moe_lm").total_params(config) == 691_623_936
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # 15,451,607,040 (15,167,032,832 with the full layers' attention kept alone; builder's compiles,
    # PR 37), 15,272,240,128 since PR 39, 14,923,113,472 since PR 45: the chip's allocator has 16.9e9
    assert resident <= 15.5e9, f"the step needs {resident} bytes with AdamW's moments"


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

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
    assert sorted(_attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq"] * 4 + ["tpuft_fa_fwd"] * 4
    assert _heads_a_step(text, "tpuft_fa_", 8) == {"tpuft_fa_fwd": [8], "tpuft_fa_bwd_dkdv_dq": [4]}
    # three projections a layer: forward, recomputed, and the two gradients
    gmm = _kernel_calls(text, "tpuft_gmm_")
    assert sorted(gmm) == ["tpuft_gmm_dlhs"] * 12 + ["tpuft_gmm_drhs"] * 12 + ["tpuft_gmm_fwd"] * 24
    # a head in pieces puts the program at the memory's edge, and there a layer's weight gradients are finished
    # inside the layer's backward pass: after each attention backward kernel its own layer's three, not all twelve
    # after the last (what the compiler chooses alone, holding 21 arrays of [17,408, 2,048] rows till then)
    late = [c for c in _kernel_calls(text, "tpuft_") if c in ("tpuft_fa_bwd_dkdv_dq", "tpuft_gmm_drhs")]
    assert late == (["tpuft_fa_bwd_dkdv_dq"] + ["tpuft_gmm_drhs"] * 3) * 4
    # the head: the forward kernel once in the text, inside the loop over the 16 blocks of rows; the backward one
    # twice — inside the loop over the 8 slabs of 16,384 columns, and for the last slab of 512 (64 of them the
    # head's), a call of its own before the loop
    assert sorted(_kernel_calls(text, "tpuft_ce_")) == ["tpuft_ce_dlogits"] * 2 + ["tpuft_ce_lse"]
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
        text = _compile(lambda *a: da._bwd_pallas(*a, da.CHUNK), *rows, sds((bh, seq // da.CHUNK, d, d), f32), sds((bh, seq, d), bf16))
        assert _kernel_calls(text, "tpuft_kda_") == ["tpuft_kda_bwd"]
    else:
        text = _compile(lambda *a: da._fwd_pallas(*a, da.CHUNK, direction == "forward_with_states"), *rows)
        assert _kernel_calls(text, "tpuft_kda_") == ["tpuft_kda_fwd"]
        assert ("f32[32,256,128,128]" in text) == (direction == "forward_with_states")
    heads = da._heads_per_step(bh)
    assert heads > 1 and [grid for _, grid in _kernel_grids(text, "tpuft_kda_")] == [(bh // heads, seq // da.CHUNK)]


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
    text = _compile(fn, *shapes)
    assert _kernel_calls(text, "tpuft_kdamix_") == [name] and not _kernel_calls(text, "tpuft_kda_")
    # nothing between input and output in HBM: no transpose or copy of a [16,384, 4,096] array beside the call
    assert not re.search(r"= (?:bf16|f32)\[1,(?:16384,4096|32,16384,128)\]\S* (?:copy|transpose)\(", text)


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
    text = _compile(fn, *shapes)
    assert _kernel_calls(text, "tpuft_ssmmix_") == [name] and not _kernel_calls(text, "tpuft_ssd_")
    # nothing between input and output in HBM: no transpose, copy or join of a [16,384, 4,096] or [16,384, 6,144] array
    assert not re.search(r"= (?:bf16|f32)\[1,16384,(?:4096|6144)\]\S* (?:copy|transpose|concatenate|fusion)\(", text)


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

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
    assert sorted(_kernel_calls(text, "tpuft_kda_")) == ["tpuft_kda_bwd"] * 4 + ["tpuft_kda_fwd"] * 8
    # each of the twelve carries H heads' chunk a grid step (PR 50): 32 heads, 256 chunks of 64 positions
    heads = da._heads_per_step(32)
    assert heads > 1 and [grid for _, grid in _kernel_grids(text, "tpuft_kda_")] == [(32 // heads, 16_384 // da.CHUNK)] * 12
    # `kda_mix` around it (since PR 49): each half's forward kernel twice a layer (the forward pass and the layer's
    # recomputation: a half keeps its inputs, so nothing runs it a third time) and its backward kernel once
    assert sorted(_kernel_calls(text, "tpuft_kdamix_")) == (
        ["tpuft_kdamix_bwd"] * 4 + ["tpuft_kdamix_fwd"] * 8 + ["tpuft_kdamix_out_bwd"] * 4 + ["tpuft_kdamix_out_fwd"] * 8)
    assert sorted(_attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq", "tpuft_fa_fwd"]
    # the latent layer's 32 heads at 256 / 128: eight a grid step forward, two backward (16 MiB dq rows)
    assert _heads_a_step(text, "tpuft_fa_", 32) == {"tpuft_fa_fwd": [8], "tpuft_fa_bwd_dkdv_dq": [2]}
    # three projections a sparse layer: forward, recomputed, and the two gradients
    gmm = _kernel_calls(text, "tpuft_gmm_")
    assert sorted(gmm) == ["tpuft_gmm_dlhs"] * 12 + ["tpuft_gmm_drhs"] * 12 + ["tpuft_gmm_fwd"] * 24
    assert "tpuft_ce_lse" in _kernel_calls(text, "tpuft_ce_") and "tpuft_ce_dlogits" in _kernel_calls(text, "tpuft_ce_")
    # the chunks' states exist only inside a layer's backward pass: float32 [32, 256, 128, 128], 537 MB
    assert "f32[32,256,128,128]" in text
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("kda_mla_moe_lm").total_params(config) == 602_449_792
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # 13,827,982,336 (temporaries 4,188,533,760; builder's compile, PR 48).  Without the two checkpoints inside
    # `kda_mix` (`_kda_mixer`: each half keeps its inputs and nothing between) the same program compiles to
    # 15,980,264,960: some twenty float32 [16,384, 4,096] arrays a layer are alive at once.  With the halves as
    # kernels (PR 49) 13,817,533,952 (temporaries 4,178,085,376; builder's compile, PR 49): not above PR 48's
    assert resident <= 13_827_982_336, f"the step needs {resident} bytes with AdamW's moments"


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

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
        assert _has_kernel(text, name), f"{name} is not in the compiled program"
    # the two kinds of layer read apart: one backward and, attention's output kept under remat, one
    # forward kernel a layer
    assert config["program"]["remat"] and config["program"]["remat_keeps_attention"]
    assert sorted(_attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq"] * 2 + ["tpuft_fa_fwd"] * 2
    assert sorted(_kernel_calls(text, "tpuft_swa_")) == ["tpuft_swa_bwd_dkdv_dq"] * 6 + ["tpuft_swa_fwd"] * 6
    # the grids, read out of the compiled calls: the band walk a step for each of the 252 tiles with a
    # visible pair a head (rows of 1 ... 8 tiles, then 24 rows of 9), the full layers the triangle's 528
    grids = bench.reader("swa_pairs_share").grids(text)
    assert sorted(g["name"] for g in grids) == ["tpuft_swa_bwd_dkdv_dq"] * 6 + ["tpuft_swa_fwd"] * 6
    # ... 28 heads: seven a grid step forward, four backward
    assert all((g["grid"], g["block_q"], g["seq"]) == ([4 if g["name"].endswith("fwd") else 7, 252], 512, 16_384)
               for g in grids), grids
    assert sorted(_kernel_grids(text, "tpuft_fa_")) == [("tpuft_fa_bwd_dkdv_dq", (7, 528))] * 2 + [("tpuft_fa_fwd", (4, 528))] * 2
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("early_router_moe_lm").total_params(config) == 643_852_800
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # 15,835,302,912 (arguments 2,575,585,280 + outputs 2,575,461,888 + temporaries 5,533,433,344 + moments
    # 5,150,822,400; builder's compile, PR 51) and an allocator's peak of 11.70 GB on the chip; with nothing kept
    # under remat 14,735,490,048, without remat 20,776,999,424; since PR 52, with several heads a grid step in the
    # attention kernels, the temporaries are 258,048 bytes more (builder's compile): 15,835,560,960
    assert resident <= 15_835_560_960, f"the step needs {resident} bytes with AdamW's moments"


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
        text = _compile(lambda *a: ssd._bwd_pallas(*a, p, ssd.CHUNK), *rows,
                        sds((chunks, groups, n, per_group * p), f32), sds((1, seq, heads * p), bf16))
        assert _kernel_calls(text, "tpuft_ssd_") == ["tpuft_ssd_bwd"]
    else:
        text = _compile(lambda *a: ssd._fwd_pallas(*a, p, ssd.CHUNK, direction == "forward_with_states"), *rows)
        assert _kernel_calls(text, "tpuft_ssd_") == ["tpuft_ssd_fwd"]
        assert ("f32[128,8,128,512]" in text) == (direction == "forward_with_states")
    assert [grid for _, grid in _kernel_grids(text, "tpuft_ssd_")] == [(groups, chunks)]


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
    assert sorted(_kernel_calls(text, "tpuft_gmm_")) == ["tpuft_gmm_dlhs"] * 2 + ["tpuft_gmm_drhs"] * 2 + ["tpuft_gmm_fwd"] * 2
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

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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
        assert _has_kernel(text, name), f"{name} is not in the compiled program"
    assert config["program"]["remat"] and config["program"]["remat_keeps_attention"]
    assert sorted(_kernel_calls(text, "tpuft_ssd_")) == ["tpuft_ssd_bwd"] * 4 + ["tpuft_ssd_fwd"] * 8
    assert set(_kernel_grids(text, "tpuft_ssd_")) == {("tpuft_ssd_bwd", (8, 128)), ("tpuft_ssd_fwd", (8, 128))}
    # `ssm_mix` around it (since PR 57): each half's forward kernel twice a block (the forward pass and the block's
    # recomputation: a half keeps its inputs, so nothing runs it a third time) and its backward kernel once
    assert sorted(_kernel_calls(text, "tpuft_ssmmix_")) == (
        ["tpuft_ssmmix_bwd"] * 4 + ["tpuft_ssmmix_fwd"] * 8 + ["tpuft_ssmmix_out_bwd"] * 4 + ["tpuft_ssmmix_out_fwd"] * 8)
    # before: 16 tiles of 1,024 rows x 12 blocks of 512 columns (8 of x, 2 of B, 2 of C); after: 16 tiles x 8 groups
    assert set(_kernel_grids(text, "tpuft_ssmmix_")) == {
        ("tpuft_ssmmix_fwd", (1, 16, 12)), ("tpuft_ssmmix_bwd", (1, 16, 12)),
        ("tpuft_ssmmix_out_fwd", (1, 16, 8)), ("tpuft_ssmmix_out_bwd", (1, 16, 8))}
    assert sorted(_attention_calls(text)) == ["tpuft_fa_bwd_dkdv_dq", "tpuft_fa_fwd"]
    assert sorted(_kernel_calls(text, "tpuft_gmm_")) == (["tpuft_gmm_dlhs"] * 8 + ["tpuft_gmm_drhs"] * 8 + ["tpuft_gmm_fwd"] * 16)
    assert "ragged-dot" not in text and "ragged_dot" not in text
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == bench.flops("mamba2_moe_lm").total_params(config) == 666_962_944
    assert shapes["moe"]["w_up"].shape == (4, 8, 2688, 1856)  # no width is cut or grown in the tree
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    # 15,317,239,296 (arguments 2,667,987,456 + outputs 2,667,862,528 + temporaries 4,645,685,760 + moments
    # 5,335,703,552; builder's compile, PR 56); with `ssm_mix` as kernels 14,471,001,088 (temporaries 3,799,447,552;
    # builder's compile, PR 57): the XLA halves' float32 [16,384, 6,144] arrays are gone
    assert resident <= 15_400_000_000, f"the step needs {resident} bytes with AdamW's moments"
