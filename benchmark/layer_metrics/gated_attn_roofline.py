"""The flash-attention kernels' share of their roofline in the output-gated
attention layers at 16 / 2 heads of 256: the least time the chip could take for
the layers' causal attention (the larger of operations over the bf16 peak and
bytes over the HBM peak, from shapes by `flops/tpuft_fa_gated.py`) over the
`tpuft_fa_*` kernels' summed device time per step in the trace.  None where
there is no such kernel or the configuration is not of this family."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "linear_num_value_heads" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_fa_gated").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
