"""Device time per step of the part `router` of the gradient program ALONE, all
directions: where the router is a function — the state's down-projection, its
norm, the MLP's three products in float32, the softmax, the choice and the
statistics — it is worth reading apart from the experts (`experts_ms` sums the
two; `benchmark/device_parts.py`).  None where the program has no op map, the
model no router, or the configuration a router that is one matrix."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    if "router_hidden_size" not in ctx["config"]:
        return None
    return device_parts.grad_ms(ctx, parts=("router",))
