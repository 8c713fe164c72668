"""Device time per step of the flash-attention kernels (`tpuft_fa_fwd`,
`tpuft_fa_bwd_dkdv_dq`) in a looped model, whose layers run `total_ut_steps`
times a step over the same weights: summed over the traced steps' kernel events
by name, every pass's calls and the rematerialised layers' second forward run
among them.  None where the program has no such kernel or the configuration is
not a looped one."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or "total_ut_steps" not in ctx["config"]:
        return None
    return seconds * 1e3
