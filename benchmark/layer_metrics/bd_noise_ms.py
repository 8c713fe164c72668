"""Device time per step of the part `bd_noise` of the gradient program:
block-diffusion training's noise — the sequences' keys, the blocks' levels, the
tokens' mask, the weights 1 / t and the doubled stream of ids
(`benchmark/device_parts.py`).  None where the program has no op map or no such
part (a tree from before PR 66, a model under another objective)."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("bd_noise",))
