"""Operations and bytes the program's flash-attention kernels need in the
output-gated attention layers of a model whose other layers are Gated DeltaNet
(Qwen3-Next: 16 query heads over 2 KV heads, q, k AND v 256 wide).

`tpuft_fa_fwd` and `tpuft_fa_bwd_dkdv_dq` run once each in every
`full_attention_interval`-th layer within the depth.  Causal, six products over
the visible half a query head (QK^T and PV forward; dV, dP, dQ, dK backward; the
backward's recomputed scores are the flash trade and are not counted).  Bytes
are the least the kernels must move through HBM with grouped queries read in
place (PR 60): q, o and their cotangents a QUERY head, k, v and their gradients
a KV head, each read or written once in bf16, the row statistics in float32 a
query head.  The gate a column, RoPE and the QK-norm are `attn_proj`'s, not
these kernels'.
"""

from __future__ import annotations

from typing import Any, Dict


def attention_layers(config: Dict[str, Any]) -> int:
    return config["num_hidden_layers"] // config["full_attention_interval"]


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    dim, seq, batch = config["head_dim"], traffic["seq_len"], traffic["sequences_per_step"]
    heads, kv_heads, layers = config["num_attention_heads"], config["num_key_value_heads"], attention_layers(config)
    one_matmul = 2.0 * seq * (seq + 1) / 2.0 * dim  # causal: visible pairs only
    tensor, stats = seq * dim * 2, seq * 4
    forward = heads * (2 * tensor + stats) + kv_heads * 2 * tensor          # read q | k v, write o, lse
    backward = heads * (4 * tensor + 2 * stats) + kv_heads * 4 * tensor     # read q o do, write dq | read k v, write dk dv
    return {"flops": batch * layers * heads * (2 + 4) * one_matmul, "bytes": float(batch * layers * (forward + backward))}
