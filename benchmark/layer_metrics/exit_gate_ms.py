"""Device time per step of the part `exit_gate` of the gradient program, both
directions: a looped model's gate product on every pass's state, the exit
distribution, the combination of the passes' losses, the entropy term and the
three counters (`benchmark/device_parts.py`).  The passes' heads and the final
norm between passes are `head_loss`'s.  None where the program has no op map or
no such part (a tree from before PR 63, a model that is not looped)."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("exit_gate",))
