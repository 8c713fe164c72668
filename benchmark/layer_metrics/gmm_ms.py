"""Device time per step of the tpuft_gmm kernels (forward, gradient of the
rows, gradient of the stacked expert matrices; three projections a layer),
summed over the traced steps' kernel events by name.  None where the program
has no such kernel."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("gmm", 0.0)
    return seconds * 1e3 if seconds > 0 else None
