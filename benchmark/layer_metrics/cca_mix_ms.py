"""Device time per step of the part `cca_mix` of the gradient program, all
directions: what compressed convolutional attention puts between its
projections and RoPE — the value shift, the convolution a channel, the
convolution a head over sequence and channels, the q-k mean and the norm a
head, with their backward passes and their recomputation
(`benchmark/device_parts.py`).  None where the program has no op map or no such
part (a tree from before PR 41, a model whose mixer is plain attention)."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("cca_mix",))
