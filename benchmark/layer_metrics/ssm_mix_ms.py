"""Device time per step of the part `ssm_mix` of the gradient program, all
directions: what a Mamba-2 block puts around its scan — the kernel-4 causal
convolution with its bias and SiLU, softplus and the decay before it, the skip
D x, the gate SiLU(z) and the RMS norm over groups after it — with their
backward passes and their recomputation (`benchmark/device_parts.py`).  None
where the program has no op map or no such part (a tree from before PR 56, a
model without a Mamba-2 block)."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("ssm_mix",))
