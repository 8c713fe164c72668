"""Model zoo: TPU-first reference models for the framework.

The torchft reference trains user-supplied torch models (its examples use a
CIFAR CNN, and its README targets Llama-class models through torchtitan
HSDP, README.md:67-74).  This package provides the equivalent first-party
models for the TPU build: a decoder-only transformer LM (the flagship, the
Llama-3-class shape), a mixture-of-experts variant (expert parallelism), and
a small conv net (the train_ddp example class).
"""

from torchft_tpu.models.convnet import (
    convnet_forward,
    convnet_loss,
    init_convnet_params,
)
from torchft_tpu.models.moe import moe_ffn, moe_layer
from torchft_tpu.models.transformer import (
    LayerKind,
    TransformerConfig,
    forward,
    forward_with_aux,
    init_params,
    loss_and_counters,
    loss_fn,
)

__all__ = [
    "LayerKind",
    "TransformerConfig",
    "init_params",
    "loss_fn",
    "forward",
    "forward_with_aux",
    "moe_ffn",
    "moe_layer",
    "loss_and_counters",
    "convnet_forward",
    "convnet_loss",
    "init_convnet_params",
]
