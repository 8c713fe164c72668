"""Device time per step of the part `head_loss` of the gradient program, all
directions: final norm, head product and its two gradients, the `tpuft_ce_*`
kernels, the loss's terms (`benchmark/device_parts.py`).  None where the program
has no op map."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("head_loss",))
