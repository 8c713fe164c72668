"""Operations and bytes the program's flash-attention kernels need for LATENT
attention (MLA), from shapes.

`tpuft_fa_fwd`, `tpuft_fa_bwd_dkdv`, `tpuft_fa_bwd_dq` (ops/attention.py) run
once a layer each on [batch * heads, seq, width] bf16 tensors: query and key
192 wide (128 + 64 rotary columns), value, output and their cotangents 128.
What the algorithm requires, causal, per head:

- forward: QK^T over the visible half at 192, PV at 128;
- backward: dV = P^T dO and dP = dO V^T at 128, dQ = dS K and dK = dS^T Q at
  192; the recomputation of the scores (once in each backward kernel) is the
  flash trade and is not counted.

The program pads query and key to 256 columns (a lane multiple) with zeros:
the products it runs at 256 are counted at 192, so the padding shows as a
lower share of the roofline, not as work.

Bytes are the least the kernels must move through HBM: each operand read once
and each result written once, in bf16, the row statistics in f32.  The rotary
part of the key is ONE [seq, 64] tensor for all heads, but each head's kernel
instance must have it in VMEM beside that head's own 128 columns, so it is
read once a head: K counts 192 columns a head like Q.  Attention at these
widths and seq 8,192 is compute-bound by these counts.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of all three kernels over one step of one group."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    seq, batch, layers = traffic["seq_len"], traffic["sequences_per_step"], config["num_hidden_layers"]
    bh = batch * config["num_attention_heads"]
    pairs = seq * (seq + 1) / 2.0  # causal: visible pairs only
    flops = layers * bh * 2.0 * pairs * (3 * qk + 3 * v)  # QK^T, dQ, dK at qk; PV, dV, dP at v
    wide, narrow, stats = seq * qk * 2, seq * v * 2, seq * 4
    forward = 2 * wide + 2 * narrow + stats  # read Q K V, write O, lse
    backward = 4 * wide + 4 * narrow + 2 * stats  # read Q K V O dO, write dQ dK dV; lse, delta
    return {"flops": flops, "bytes": float(layers * bh * (forward + backward))}
