"""Device time per step of the state-space scan: the `tpuft_ssd_fwd` and
`tpuft_ssd_bwd` kernels (ops/ssd.py) of every Mamba-2 block — the forward pass,
the backward's forward pass that makes the chunks' states again, and the
backward — summed over the traced steps' kernel events by name.  None where the
program has no such kernel (a tree from before PR 56, a model without a
Mamba-2 block)."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("ssd", 0.0)
    return seconds * 1e3 if seconds > 0 else None
