"""The tpuft_gmm kernels' share of their roofline: the least time the chip
could take for the grouped matmuls the expert layers need (the larger of
operations over the bf16 peak and bytes over the HBM peak, both from shapes by
`flops/tpuft_gmm.py`: one row per (token, expert) assignment, no padding rows)
over the kernels' summed device time per step in the trace.  None where the
program has no such kernel."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("gmm", 0.0)
    if seconds <= 0 or ctx["peaks"] is None:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_gmm").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
