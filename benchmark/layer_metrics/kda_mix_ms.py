"""Device time per step of the part `kda_mix` of the gradient program, all
directions: what Kimi Delta Attention puts around its scan — the three
kernel-4 causal convolutions with SiLU, the L2 norm a head on q and k, the
decay's softplus and the sigmoid of beta before it, the head-wise RMSNorm
under its sigmoid gate after it — with their backward passes and their
recomputation (`benchmark/device_parts.py`).  None where the program has no op
map or no such part (a tree from before PR 48, a model without a KDA layer)."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("kda_mix",))
