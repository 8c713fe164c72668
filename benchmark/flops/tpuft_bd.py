"""Operations and bytes the program's block-diffusion attention kernels need,
from L (data tokens a sequence), b (the block length), the heads and their
width.

`tpuft_bd_fwd` and `tpuft_bd_bwd_dkdv_dq` (ops/attention.py: the flash kernels
on the walk of the three-part block mask's live tiles) run once each a layer
over the doubled stream of 2 L positions.  Required are the products over the
LIVE pairs — L**2 + L b of the (2 L)**2 (`flops/bd_moe_lm.py` `live_pairs`) —
two forward (QK^T, PV) and four backward, 2 * width operations a pair and head
each, as `flops/tpuft_fa.py` counts a causal call's over its visible half; what
a tile on a block edge computes and masks away, and the backward's second QK^T,
are the kernels' own and are not counted.  Bytes are the least the kernels must
move through HBM: q, the output and their gradients a query head, k and v and
their gradients' per-query-head form as the kernels write it, the row
statistics in float32 — each once a kernel.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    dim, seq, batch = config["head_dim"], traffic["seq_len"], traffic["sequences_per_step"]
    heads, kv_heads, layers = config["num_attention_heads"], config["num_key_value_heads"], config["num_hidden_layers"]
    pairs = seq * seq + seq * config["block_diffusion"]["block_length"]
    flops = batch * layers * heads * (2 + 4) * 2.0 * pairs * dim
    tensor, stats = 2 * seq * dim * 2, 2 * seq * 4  # a head's [2 L, width] in bf16; a row statistic over 2 L
    forward = heads * (2 * tensor + stats) + kv_heads * 2 * tensor            # read Q, write O, lse; read K, V
    backward = heads * (6 * tensor + 2 * stats) + kv_heads * 2 * tensor       # read Q O dO, write dQ dK dV a query head; lse, delta; K, V
    return {"flops": flops, "bytes": float(batch * layers * (forward + backward))}
