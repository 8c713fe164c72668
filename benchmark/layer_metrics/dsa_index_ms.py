"""Device time per step of the indexer's loss kernel (`tpuft_dsa_index_loss`: the
scores, the KL term and its gradient in one pass, once a layer), summed over the
traced steps' kernel events by name.  The selection passes (`tpuft_dsa_select`,
`tpuft_dsa_mask`) are booked apart, under `dsa_select`, and show in the
breakdown.  None where the program has no such kernel."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("dsa_index", 0.0)
    return seconds * 1e3 if seconds > 0 else None
