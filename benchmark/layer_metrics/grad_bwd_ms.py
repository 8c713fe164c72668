"""Device time per step of the gradient program's backward pass: the operations
whose op_name carries a `transpose(` and no rematerialised computation, over all
parts of the model (`benchmark/device_parts.py`).  None where the program has no
op map."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, direction="bwd")
