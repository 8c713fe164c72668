"""The flash-attention kernels' share of their roofline in a looped model: the
least time the chip could take for layers x passes causal attention calls a
direction at 16 heads of 128 (the larger of operations over the bf16 peak and
bytes over the HBM peak, from shapes by `flops/tpuft_fa_loop.py`) over the
`tpuft_fa_*` kernels' summed device time per step in the trace.  None where
there is no such kernel or the configuration is not a looped one."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "total_ut_steps" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_fa_loop").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
