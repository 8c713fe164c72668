"""Device time per step of what `jax.checkpoint` computes again in the backward
pass: the operations with `rematted_computation` on their op_name path, over all
parts of the model (`benchmark/device_parts.py`).  None where the program has no
op map or rematerialises nothing."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, direction="recompute")
