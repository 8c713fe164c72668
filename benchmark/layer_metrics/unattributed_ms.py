"""Device time per step of the gradient program's operations that no part of the
model claims: an instruction that is not in `TrainStep.op_map`, or whose op_name
path holds no scope of `obs/spans.PARTS` (`benchmark/device_parts.py`).  With the
three directions it adds up to the program's device time.  None where the program
has no op map."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, unattributed=True)
