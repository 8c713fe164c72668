"""Device time per step of the un-windowed flash-attention kernels
(`tpuft_fa_fwd`, `tpuft_fa_bwd_dkdv_dq`) in the un-rotated full layers of a
model whose other layers attend under a window, 28 query heads over 4 KV heads:
summed over the traced steps' kernel events by name.  None where the program
has no such kernel or the configuration is not of this family."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or "rope_layout" not in ctx["config"]:
        return None
    return seconds * 1e3
