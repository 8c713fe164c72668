"""Device time per step of the parts `router` and `experts` of the gradient
program, all directions: scores and top-k, the row table, the row moves, the
`tpuft_gmm_*` kernels, gate weighting and the combine — not the shared expert
(`benchmark/device_parts.py`).  None where the program has no op map or the model
no experts."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("router", "experts"))
