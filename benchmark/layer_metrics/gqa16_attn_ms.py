"""Device time per step of the flash-attention kernels (`tpuft_fa_fwd`,
`tpuft_fa_bwd_dkdv_dq`) in the un-rotated attention blocks of a model whose
other blocks are Mamba-2 mixers and experts, 32 query heads over 2 KV heads:
summed over the traced steps' kernel events by name.  None where the program
has no such kernel or the configuration is not of this family."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or "hybrid_override_pattern" not in ctx["config"]:
        return None
    return seconds * 1e3
