"""Device time per step of the block-diffusion attention kernels
(`tpuft_bd_fwd`, `tpuft_bd_bwd_dkdv_dq`: the flash kernels on the walk of the
three-part block mask's live tiles over a doubled stream): summed over the
traced steps' kernel events by name.  None where the program has no such
kernel (a tree from before PR 66, a model under another objective)."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("bd_attn", 0.0)
    return seconds * 1e3 if seconds > 0 else None
