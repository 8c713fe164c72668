"""Device time per step of the fused cross-entropy kernels (`tpuft_ce_lse`,
`tpuft_ce_dlogits`) in a looped model, where every pass's state goes through the
head: summed over the traced steps' kernel events by name, `total_ut_steps`
calls of each.  None where the program has no such kernel or the configuration
is not a looped one."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("ce", 0.0)
    if seconds <= 0 or "total_ut_steps" not in ctx["config"]:
        return None
    return seconds * 1e3
