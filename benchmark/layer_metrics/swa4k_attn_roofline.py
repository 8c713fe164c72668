"""The windowed flash-attention kernels' share of their roofline under a window
of 4,096: the least time the chip could take for the six products over the
band's pairs at 28 query heads (the larger of operations over the bf16 peak and
bytes over the HBM peak, both from shapes by `flops/tpuft_swa4k.py`; the masked
parts of the band's edge tiles are not required work) over the `tpuft_swa_*`
kernels' summed device time per step in the trace.  None where there is no such
kernel or the configuration is not of this family."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("swa", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "sliding_window_layout" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_swa4k").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
