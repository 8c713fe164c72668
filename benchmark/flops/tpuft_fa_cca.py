"""Operations and bytes the program's flash-attention kernels need under
compressed convolutional attention (ZAYA1-8B: 8 query heads on 2 KV heads of
128 in every layer).

`tpuft_fa_fwd` and `tpuft_fa_bwd_dkdv_dq` run once a layer each, causal: six
products over the visible half a query head (`flops/tpuft_fa.py`; the flash
trade's recomputed scores are not counted).  Bytes are the least the kernels
must move through HBM where K and V are read a KV HEAD, not a query head: the
program repeats them to the query heads before the call (`flash_attention`),
which is its own traffic and not the algorithm's — Q, O, dO and dQ a query
head, K, V, dK and dV a KV head, each once, in bf16, and the row statistics in
float32.  By these counts attention at 16,384 positions is compute-bound.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    heads, groups, dim = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    seq, batch, layers = traffic["seq_len"], traffic["sequences_per_step"], config["num_hidden_layers"]
    one_matmul = 2.0 * seq * (seq + 1) / 2.0 * dim  # causal: visible pairs only
    tensor, stats = seq * dim * 2, seq * 4
    forward = heads * (2 * tensor + stats) + groups * 2 * tensor           # read Q, write O, lse; read K, V
    backward = heads * (4 * tensor + 2 * stats) + groups * 4 * tensor      # read Q O dO, write dQ; lse, delta; read K V, write dK dV
    return {"flops": layers * batch * heads * (2 + 4) * one_matmul, "bytes": float(layers * batch * (forward + backward))}
