"""Operations and bytes the program's flash-attention kernels need, from shapes.

`tpuft_fa_fwd`, `tpuft_fa_bwd_dkdv`, `tpuft_fa_bwd_dq` (ops/attention.py) run
once a layer each on [batch * heads, seq, head_dim] bf16 tensors (the program
broadcasts grouped K/V heads to the query heads before the kernel, so the
kernel's own traffic is per query head).  What the algorithm requires, causal:

- forward: QK^T and PV over the visible half: 2 matmuls of 2*S*S/2*D each;
- backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q: 4 matmuls; the
  recomputation of the scores (one more in each backward kernel) is the flash
  trade and is not counted.

Bytes are the least the kernels must move through HBM: each operand read once
and each result written once (Q, K, V, O, dO, dQ, dK, dV in bf16, the row
statistics in f32).  Attention at head_dim 128 and seq 4096 is compute-bound
by these counts.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of all three kernels over one step of one group."""
    heads = config["num_attention_heads"]
    dim = config.get("head_dim", config["hidden_size"] // heads)
    seq, batch, layers = traffic["seq_len"], traffic["sequences_per_step"], config["num_hidden_layers"]
    bh = batch * heads
    one_matmul = 2.0 * seq * (seq + 1) / 2.0 * dim  # causal: visible pairs only
    flops = layers * bh * (2 + 4) * one_matmul
    tensor = seq * dim * 2  # one [S, D] bf16 tensor of one head
    stats = seq * 4
    forward = 4 * tensor + stats  # read Q K V, write O, lse
    backward = 8 * tensor + 2 * stats  # read Q K V O dO, write dQ dK dV; lse, delta
    return {"flops": flops, "bytes": float(layers * bh * (forward + backward))}
