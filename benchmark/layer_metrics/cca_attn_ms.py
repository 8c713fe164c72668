"""Device time per step of the flash-attention kernels (`tpuft_fa_fwd`,
`tpuft_fa_bwd_dkdv_dq`) under compressed convolutional attention: 8 query heads
on 2 KV heads of 128 in every layer, summed over the traced steps' kernel
events by name.  None where the program has no such kernel or the configuration
is not of this family (there `attn_roofline`, `full_attn_ms` and `mla_attn_ms`
and their cells read the same kernels)."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or "cca_time0" not in ctx["config"]:
        return None
    return seconds * 1e3
