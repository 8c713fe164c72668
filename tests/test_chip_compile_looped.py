"""The described-chip compile (`tests/chip_compile.py`) of the looped
configuration: the whole `ouro-2.6b` gradient program — 8 layers run four times
over the same weights, four head passes under a loss weight a row — with its
kernel calls counted and its memory bound; and what stands between a layer's
projections and its attention kernels at the Ouro and the SmallThinker cells'
widths."""

import pytest

import jax
import jax.numpy as jnp

from chip_compile import ROOT, kernel_calls, one_chip, relayouts, topo  # noqa: F401 — `topo` and `one_chip` are the fixtures


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


# kind: (heads, KV heads, sequences, positions, model width, window, rotated), and the most instructions that only move
# a Q-sized and a K-sized array (q or the output; k or v) a layer may hold inside the attention scopes
LAYERS = {
    "smallthinker_full": ((28, 4, 1, 16_384, 2_560, None, False), (0, 0)),
    "smallthinker_window": ((28, 4, 1, 16_384, 2_560, 4_096, True), (3, 5)),
    "ouro": ((16, 16, 2, 4_096, 2_048, None, True), (6, 6)),
}


@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_nothing_turns_between_a_projection_and_an_attention_kernel_for_v5e(one_chip, kind, monkeypatch) -> None:
    """One decoder layer at a cell's attention widths (a small feed-forward and
    vocabulary beside it), its gradient program compiled for the described v5e,
    and the instructions that only move an array inside `attn_proj`, `attn` and
    `attn_window` counted by size (`chip_compile.relayouts`).  The kernels read
    q, k and v and write the output, dq, dk and dv where the projections leave
    them, [B, S, heads * 128], and the forward kernel gives lse as the rows
    the backward reads (PR 65): an un-rotated layer holds NO such instruction
    in either direction — V, the output, its cotangent and dv go from product
    to kernel and back as they lie, and no float32 array is re-tiled (the
    lane-padded statistics had a copy a call to take a lane out of).  Under RoPE q and k (and dq, dk) are re-tiled once each way, forward,
    recomputed and backward: XLA turns a head's half-split pairs as [B, S,
    heads, 128] with S innermost, a layout no reshape to [B, S, heads * 128]
    keeps — one copy where the head-major kernels had a transposing fusion and
    a copy; nothing of V's, the output's or the cotangent's size moves there
    either (Ouro's K and V are Q's size: its six are q and k, three times)."""
    import math

    from torchft_tpu.models import LayerKind, TransformerConfig, init_params
    from torchft_tpu.models.transformer import loss_and_counters
    from torchft_tpu.ops import _pallas_util

    (heads, kv, batch, seq, width, window, rotated), (most_q, most_k) = LAYERS[kind]
    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    layer = LayerKind("layers", False, heads, 1e6, window=window, rotary_fraction=1.0 if rotated else 0.0)
    cfg = TransformerConfig(vocab_size=1024, d_model=width, n_layers=1, n_heads=heads, n_kv_heads=kv, head_dim=128, d_ff=256,
                            max_seq=seq, remat=True, remat_keeps_attention=kind != "ouro", pattern=(layer,), scan_unroll=8)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), shapes)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(lambda p, b: loss_and_counters(p, b, cfg)[0])).lower(
        params, {"tokens": tokens, "targets": tokens}).compile().as_text()
    family = "tpuft_fa_" if window is None else "tpuft_swa_"
    assert sorted(kernel_calls(text, family)) == [family + "bwd_dkdv_dq"] + [family + "fwd"] * (2 if kind == "ouro" else 1)
    q_size, k_size = batch * seq * heads * 128, batch * seq * kv * 128
    moved = relayouts(text, at_least=k_size)
    of_size = lambda n: [m for m in moved if math.prod(m[2]) == n]  # noqa: E731
    assert len(of_size(q_size)) <= most_q, of_size(q_size)
    if k_size != q_size:
        assert len(of_size(k_size)) <= most_k, of_size(k_size)
    assert all(math.prod(m[2]) in (q_size, k_size) for m in moved), moved
    assert not [m for m in moved if m[1] == "f32"], "a float32 array is re-tiled: is delta a sum over [.., heads, d], or lse lane-padded?"
