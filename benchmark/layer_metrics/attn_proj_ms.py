"""Device time per step of the part `attn_proj` of the gradient program, all
directions: the q/k/v/o products, latent attention's low-rank path, RoPE, the
reshapes and transposes around the heads — not the norms, not the attention call
(`benchmark/device_parts.py`).  None where the program has no op map."""

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts

    return device_parts.grad_ms(ctx, parts=("attn_proj",))
