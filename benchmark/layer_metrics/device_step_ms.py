"""Device busy time per step: the union of the device-operation intervals of
the profiler trace over the traced steps, divided by their number."""

LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    return ctx["trace"]["device_step_s"] * 1e3
