"""Device time per step of the flash-attention kernels (`tpuft_fa_fwd`,
`tpuft_fa_bwd_dkdv_dq`) in the output-gated attention layers of a model whose
other layers are Gated DeltaNet, 16 query heads over 2 KV heads of 256: summed
over the traced steps' kernel events by name.  None where the program has no
such kernel or the configuration is not of this family."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or "linear_num_value_heads" not in ctx["config"]:
        return None
    return seconds * 1e3
