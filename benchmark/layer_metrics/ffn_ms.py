"""Device time per step of the part `ffn` of the gradient program, all directions:
the dense gate / up / down products and what XLA fuses with them
(`benchmark/device_parts.py`).  None where the program has no op map or the
model no dense feed-forward."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("ffn",))
