"""The described-chip compile (`tests/chip_compile.py`) of the looped
configuration: the whole `ouro-2.6b` gradient program — 8 layers run four times
over the same weights, four head passes under a loss weight a row — with its
kernel calls counted and its memory bound."""

import pytest

import jax
import jax.numpy as jnp

from chip_compile import ROOT, kernel_calls, one_chip, topo  # noqa: F401 — `topo` and `one_chip` are the fixtures


def test_ouro_gradient_program_compiles_with_kernels_for_v5e(topo, one_chip) -> None:
    """The benchmark's `ouro-2.6b` configuration as `benchmark/programs/looped_lm.py`
    hands it to `TrainStep`, at the published widths and the cell's 2 x 4,096
    tokens.  The passes are a static loop: 32 layer applications, each with its
    attention forward kernel twice (the rematerialised layer keeps its input
    alone and runs it again in the backward pass) and its backward kernel once;
    the head's two kernels once a pass, `tpuft_ce_dlogits` with a scale a row
    (a [1, 1, 8192] float32 operand in place of the mean form's one number).
    A weight's gradient is ONE running float32 sum over the passes (the
    weights go from pass to pass through `_grads_inside`): with every pass's term
    kept to the end of the program the temporaries were 6.74e9 bytes and the step
    16.5e9; so they are under 4.2e9, and the step with AdamW's moments under the
    14.5e9 the configuration states."""
    import sys

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.spec import Benchmark
    from torchft_tpu.ops import _pallas_util

    bench = Benchmark(ROOT)
    config, traffic = bench.config("ouro-2.6b"), bench.traffic("steady-1g")
    shapes = jax.eval_shape(lambda: bench.reference("looped_lm").make_weights(1, config))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((traffic["sequences_per_step"], traffic["seq_len"]), jnp.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(_pallas_util, "on_tpu", lambda: True)  # the gate asks the default backend, the CPU here
        _, step = bench.program("looped_lm").train_step(config, topo.devices[0])
        compiled = step.lower_grads(params, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    layers, passes = config["num_hidden_layers"], config["total_ut_steps"]
    assert (layers, passes, config["program"]["loop_scan"], config["program"]["remat_keeps_attention"]) == (8, 4, False, False)
    attention = kernel_calls(text, "tpuft_fa_")
    assert attention.count("tpuft_fa_fwd") == 2 * layers * passes and attention.count("tpuft_fa_bwd_dkdv_dq") == layers * passes
    head = kernel_calls(text, "tpuft_ce_")
    assert head.count("tpuft_ce_lse") == passes and head.count("tpuft_ce_dlogits") == passes
    dlogits = [line for line in text.splitlines() if "tpu_custom_call" in line and "custom-call(" in line and "tpuft_ce_dlogits" in line]
    assert all("f32[1,1,8192]" in line and "bf16[8192,49152]" in line for line in dlogits), dlogits[0][:400]
    ma = compiled.memory_analysis()
    n_params = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert n_params == 612_438_017
    assert ma.temp_size_in_bytes < 4.2e9, f"{ma.temp_size_in_bytes} bytes of temporaries: is a weight's gradient one running sum?"
    resident = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes + 8 * n_params
    assert resident <= 14_500_000_000, f"the step needs {resident} bytes with AdamW's moments"
