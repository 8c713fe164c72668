"""Operations and bytes the program's un-windowed flash-attention kernels need
where only SOME layers run them, each kind of layer at its own head count.

`tpuft_fa_fwd` and `tpuft_fa_bwd_dkdv_dq` run once each in every
`full_attention` layer of a model that mixes window and full attention
(Laguna-XS.2: 48 query heads on full layers, 2 of the cut's 5 layers).
`flops/tpuft_fa.py` counts one head count over every layer; the products and
bytes a head are the same as there: causal, six products over the visible half,
each operand read and each result written once.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    n = config["num_hidden_layers"]
    heads = [h for kind, h in zip(config["layer_types"][:n], config["num_attention_heads_per_layer"][:n])
             if kind == "full_attention"]
    dim, seq, batch = config["head_dim"], traffic["seq_len"], traffic["sequences_per_step"]
    bh_layers = batch * sum(heads)  # heads summed over the full layers
    one_matmul = 2.0 * seq * (seq + 1) / 2.0 * dim  # causal: visible pairs only
    tensor, stats = seq * dim * 2, seq * 4
    forward = 4 * tensor + stats
    backward = 8 * tensor + 2 * stats
    return {"flops": bh_layers * (2 + 4) * one_matmul, "bytes": float(bh_layers * (forward + backward))}
