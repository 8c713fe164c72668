"""Device time per step of the delta rule's scan: the `tpuft_kda_fwd` and
`tpuft_kda_bwd` kernels (ops/delta_attention.py) of every Kimi Delta Attention
layer — the forward pass, the backward's forward pass that makes the chunks'
states again, and the backward — summed over the traced steps' kernel events
by name.  None where the program has no such kernel (a tree from before PR 48,
a model without a KDA layer)."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("kda", 0.0)
    return seconds * 1e3 if seconds > 0 else None
