"""Device time per step of the gradient program's forward pass: the operations
whose op_name carries neither a transpose nor a rematerialised computation, over
all parts of the model (`benchmark/device_parts.py`: the traced steps' `XLA Ops`
self times joined to `TrainStep.op_map`).  None where the program has no op map."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, direction="fwd")
