"""Operations and bytes the program's WINDOWED flash-attention kernels need
under a window of several tiles (SmallThinker: 4,096 at 16,384 positions), from
shapes.

`tpuft_swa_fwd` and `tpuft_swa_bwd_dkdv_dq` (ops/attention.py: the flash
kernels on the band walk) run once each in every window layer
(`sliding_window_layout` 1) on [batch * heads, seq, head_dim] bf16 tensors, the
4 K/V heads broadcast to the 28 query heads before the kernel.  What the
algorithm requires over the band's pairs — a query at t sees the keys s with
0 <= t - s < window, 58,722,304 pairs a head at 16,384 and 4,096:

- forward: QK^T and PV: 2 products of 2 * pairs * head_dim each;
- backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q: 4 products; the
  recomputation of the scores in the backward kernel is the flash trade and is
  not counted, nor are the masked parts of the band's edge tiles (of the 252
  tiles of 512 x 512 a head that the walk visits, 32 straddle the diagonal and
  24 the window's far edge).

Bytes are the least the kernels must move through HBM: each operand read once
and each result written once (Q, K, V, O, dO, dQ, dK, dV in bf16, the row
statistics in f32), per query head as the kernels see them.  At 3,584 keys a
query the operations bound by far: 28 heads are 2.53 TFLOP a layer (12.8 ms at the
bf16 peak) against 1.41 GB (1.7 ms at the HBM peak).
"""

from __future__ import annotations

from typing import Any, Dict


def window_layers(config: Dict[str, Any]) -> int:
    return sum(config["sliding_window_layout"][: config["num_hidden_layers"]])


def band_pairs(seq: int, window: int) -> int:
    causal = seq * (seq + 1) // 2
    return causal if window >= seq else causal - (seq - window) * (seq - window + 1) // 2


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    dim, seq, batch = config["head_dim"], traffic["seq_len"], traffic["sequences_per_step"]
    bh, layers = batch * config["num_attention_heads"], window_layers(config)
    one_matmul = 2.0 * band_pairs(seq, config["sliding_window_size"]) * dim
    tensor, stats = seq * dim * 2, seq * 4
    forward = 4 * tensor + stats  # read Q K V, write O, lse
    backward = 8 * tensor + 2 * stats  # read Q K V O dO, write dQ dK dV; lse, delta
    return {"flops": layers * bh * (2 + 4) * one_matmul, "bytes": float(layers * bh * (forward + backward))}
