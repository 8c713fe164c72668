"""The `tpuft_ce_*` kernels' share of their roofline in a looped model: the
least time the chip could take for `total_ut_steps` passes of the head's two
kernels (the larger of operations over the bf16 peak and bytes over the HBM
peak, from shapes by `flops/tpuft_ce_loop.py`, the backward's scale a row in the
bytes) over the kernels' summed device time per step in the trace.  None where
there is no such kernel or the configuration is not a looped one."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("ce", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "total_ut_steps" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_ce_loop").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
