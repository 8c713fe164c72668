"""Device time per step of the part `router` of the gradient program ALONE, all
directions, where the router reads the layer's input before attention: the
scores from the un-normed stream in float32, the six largest, the softmax over
them, the statistics — and in the backward pass the gates' cotangent into the
pre-attention stream.  Apart from the experts (`experts_ms` sums the two;
`benchmark/device_parts.py`).  None where the program has no op map or the
configuration's router reads the experts' input."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    if "moe_primary_router_apply_softmax" not in ctx["config"]:
        return None
    return device_parts.grad_ms(ctx, parts=("router",))
