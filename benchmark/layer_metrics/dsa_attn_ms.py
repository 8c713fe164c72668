"""Device time per step of attention over the selected keys (`tpuft_dsa_attn_fwd`
and `tpuft_dsa_attn_bwd_dkdv_dq`: forward and the one-pass backward, once a
layer each), summed over the traced steps' kernel events by name.  None where
the program has no such kernel (a parent without the indexer, or another
configuration)."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("dsa_attn", 0.0)
    return seconds * 1e3 if seconds > 0 else None
