"""Device time per step of the tpuft_fa kernels where they run UNROTATED latent
attention (`mla_use_nope`: query and key 192 wide, value 128, 32 heads; the
forward and the one-pass backward kernel once a latent layer), summed over the
traced steps' kernel events by name.  None where the program has no such
kernel or the configuration is not of this family (there `mla_attn_ms` and its
cell read the same kernels)."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or not ctx["config"].get("mla_use_nope"):
        return None
    return seconds * 1e3
