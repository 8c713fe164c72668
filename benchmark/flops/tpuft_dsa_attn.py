"""Operations and bytes attention over the SELECTED pairs needs, from shapes.

`tpuft_dsa_attn_fwd` and `tpuft_dsa_attn_bwd_dkdv_dq` (ops/attention.py under
a mask of ops/sparse_attention.py) run once a layer each on [batch * heads,
seq, 128] bf16 tensors.  What the algorithm requires: a query keeps
min(position + 1, topk) keys, and over those pairs per head QK^T and PV
forward and dV, dP, dQ, dK backward, each 2 * 128 operations a pair; the
scores formed again in the backward kernel are the flash trade and not
counted, nor is any pair the selection left out — so kernels that visit every
causal tile read near the selected share of what a dense attention's roofline
would say.

Bytes are the least the kernels must move through HBM: each operand read once
and each result written once in bf16, the row statistics in f32, grouped-query
K and V once a KV head.  The int8 mask is the program's own device and is not
required traffic.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    d, heads, kv = config["head_dim"], config["num_attention_heads"], config["num_key_value_heads"]
    seq, batch, layers = traffic["seq_len"], traffic["sequences_per_step"], config["num_hidden_layers"]
    short = min(seq, config["sa_config"]["topk"])
    pairs = short * (short + 1) // 2 + (seq - short) * config["sa_config"]["topk"]
    flops = layers * batch * heads * pairs * 6 * 2.0 * d
    row, stats = seq * d * 2, seq * 4
    forward = heads * (2 * row + stats) + kv * 2 * row                    # read Q, write O, lse; read K V
    backward = heads * (4 * row + 2 * stats) + kv * 4 * row               # read Q O dO, write dQ; K V in, dK dV out
    return {"flops": flops, "bytes": float(layers * batch * (forward + backward))}
