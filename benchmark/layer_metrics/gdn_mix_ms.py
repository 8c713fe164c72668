"""Device time per step of the part `gdn_mix` of the gradient program, all
directions: what Gated DeltaNet puts around its scan — the kernel-4 causal
convolution with SiLU over q, k and v, the L2 norm a key head, the decay's
softplus and the sigmoid of beta before it, the head-wise RMSNorm times SiLU(z)
after it — with their backward passes and their recomputation
(`benchmark/device_parts.py`).  None where the program has no op map or no such
part (a tree from before PR 68, a model without such a layer)."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("gdn_mix",))
