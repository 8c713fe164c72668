"""Device time per step of the un-windowed flash-attention kernels
(`tpuft_fa_fwd`, `tpuft_fa_bwd_dkdv_dq`) in a model that also has window
layers: the `full_attention` layers' share of attention, summed over the traced
steps' kernel events by name.  None where the program has no such kernel or the
configuration no window layers (there `attn_roofline` and its cells read it)."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or "sliding_window" not in ctx["config"]:
        return None
    return seconds * 1e3
