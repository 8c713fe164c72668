"""Device time per step of the windowed flash-attention kernels
(`tpuft_swa_fwd`, `tpuft_swa_bwd_dkdv_dq`: the band walk of the
`sliding_attention` layers), summed over the traced steps' kernel events by
name.  None where the program has no such kernel."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("swa", 0.0)
    return seconds * 1e3 if seconds > 0 else None
