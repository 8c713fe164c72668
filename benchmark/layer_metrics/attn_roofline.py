"""The tpuft_fa kernels' share of their roofline: the least time the chip
could take for what the algorithm needs (the larger of operations over the
bf16 peak and bytes over the HBM peak, both from shapes by
`flops/tpuft_fa.py`) over the kernels' summed device time per step in the trace."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or ctx["peaks"] is None:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_fa").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
