"""Device time per step of the tpuft_fa kernels where they run latent
attention (MLA: query and key 192 wide, value 128; forward, dK/dV and dQ
kernels, once a layer each), summed over the traced steps' kernel events by
name.  None where the program has no such kernel or the configuration no
latent attention."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or not ctx["config"].get("kv_lora_rank"):
        return None
    return seconds * 1e3
