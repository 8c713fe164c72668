"""Operations and bytes the indexer needs over the VISIBLE pairs, from shapes.

`tpuft_dsa_index_loss` (ops/sparse_attention.py) runs once a layer.  What the
algorithm requires per visible (query, key) pair and index head: the score's
product at the index width forward, and for the indexer's own loss the two
products that pass its gradient to the index query and the index key — 3
products of 2 * 64 operations.  Not required and not counted: the 32 heads'
QK^T formed again for the loss's target, the scores formed a second time
inside the kernel, and every selection pass (`tpuft_dsa_select`,
`tpuft_dsa_mask`).

Bytes are the least the kernel must move through HBM: the index queries, key
and weights read once and their gradients written once.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of the indexer over one step of one group."""
    sa = config["sa_config"]
    heads, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    seq, batch, layers = traffic["seq_len"], traffic["sequences_per_step"], config["num_hidden_layers"]
    pairs = seq * (seq + 1) // 2
    flops = layers * batch * heads * pairs * 3 * 2.0 * d
    operands = seq * (heads * d * 2 + d * 2 + heads * 4)   # a, b in bf16; w in f32
    return {"flops": flops, "bytes": float(layers * batch * 2 * operands)}
