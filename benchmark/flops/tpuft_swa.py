"""Operations and bytes the program's WINDOWED flash-attention kernels need,
from shapes.

`tpuft_swa_fwd` and `tpuft_swa_bwd_dkdv_dq` (ops/attention.py: the flash
kernels on the band walk) run once each in every `sliding_attention` layer on
[batch * heads, seq, head_dim] bf16 tensors, the grouped K/V heads broadcast to
the query heads before the kernel.  What the algorithm requires over the
band's pairs — a query at t sees the keys s with 0 <= t - s < window:

- forward: QK^T and PV: 2 products of 2 * pairs * head_dim each;
- backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q: 4 products; the
  recomputation of the scores in the backward kernel is the flash trade and is
  not counted, nor are the masked halves of the band's tiles (at a window of one
  tile's side half of what the kernels compute lies outside the band).

Bytes are the least the kernels must move through HBM: each operand read once
and each result written once (Q, K, V, O, dO, dQ, dK, dV in bf16, the row
statistics in f32), per query head as the kernels see them.  At a window of 512
and head_dim 128 the two bounds are close (the band holds 504 keys a query): the
roofline takes the larger.
"""

from __future__ import annotations

from typing import Any, Dict


def window_layers(config: Dict[str, Any]) -> int:
    return sum(kind == "sliding_attention" for kind in config["layer_types"][: config["num_hidden_layers"]])


def band_pairs(seq: int, window: int) -> int:
    causal = seq * (seq + 1) // 2
    return causal if window >= seq else causal - (seq - window) * (seq - window + 1) // 2


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    n = config["num_hidden_layers"]
    heads = {h for kind, h in zip(config["layer_types"][:n], config["num_attention_heads_per_layer"][:n])
             if kind == "sliding_attention"}
    if len(heads) != 1:
        raise ValueError(f"window layers of one head count, not {sorted(heads)}")
    dim, seq, batch = config["head_dim"], traffic["seq_len"], traffic["sequences_per_step"]
    bh, layers = batch * heads.pop(), window_layers(config)
    one_matmul = 2.0 * band_pairs(seq, config["sliding_window"]) * dim
    flops = layers * bh * (2 + 4) * one_matmul
    tensor, stats = seq * dim * 2, seq * 4
    forward = 4 * tensor + stats  # read Q K V, write O, lse
    backward = 8 * tensor + 2 * stats  # read Q K V O dO, write dQ dK dV; lse, delta
    return {"flops": flops, "bytes": float(layers * bh * (forward + backward))}
