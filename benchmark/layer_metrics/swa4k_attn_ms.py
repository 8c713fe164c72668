"""Device time per step of the windowed flash-attention kernels under a window
of 4,096 (`tpuft_swa_fwd`, `tpuft_swa_bwd_dkdv_dq`: a band of nine 512-tiles a
row, 252 of the triangle's 528 a head), summed over the traced steps' kernel
events by name.  None where the program has no such kernel or the configuration
is not of this family (Laguna's band of two tiles is `swa_attn_ms`'s)."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("swa", 0.0)
    if seconds <= 0 or "sliding_window_layout" not in ctx["config"]:
        return None
    return seconds * 1e3
