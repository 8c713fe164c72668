"""TPU-native hot ops: pallas kernels with XLA fallbacks.

The reference has no custom kernels (it delegates compute to torch); these
exist because the TPU build's compute path is our own.  Each op provides a
pallas TPU kernel for the forward pass and an XLA-expressed backward
(flash-style recompute), and falls back to pure-XLA reference math off-TPU
so the same model code runs under the CPU test mesh.
"""

from torchft_tpu.ops.attention import flash_attention
from torchft_tpu.ops.cross_entropy import (
    fused_ce_applicable,
    fused_linear_cross_entropy,
    fused_linear_cross_entropy_per_row,
)
from torchft_tpu.ops.ring_attention import ring_attention
from torchft_tpu.ops.rmsnorm import rms_norm, rms_norm_pallas
from torchft_tpu.ops.ulysses import ulysses_attention

__all__ = [
    "flash_attention",
    "fused_ce_applicable",
    "fused_linear_cross_entropy",
    "fused_linear_cross_entropy_per_row",
    "ring_attention",
    "rms_norm",
    "rms_norm_pallas",
    "ulysses_attention",
]
