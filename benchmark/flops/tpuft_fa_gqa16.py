"""Operations and bytes the program's flash-attention kernels need in the
un-rotated attention blocks of a model whose other blocks are Mamba-2 mixers
and experts, at a grouped-query ratio of 16 (Nemotron-H: 32 query heads over 2
KV heads of 128).

`tpuft_fa_fwd` and `tpuft_fa_bwd_dkdv_dq` run once each in every `*` block of
`hybrid_override_pattern` within the depth.  The products and bytes a query
head are `flops/tpuft_fa_full.py`'s: causal, six products over the visible
half, each operand read and each result written once — per QUERY head as the
kernels see them, since `flash_attention` repeats each K/V head sixteen times
in HBM before the call (that repeat is XLA's work under `attn_proj`, not these
kernels').
"""

from __future__ import annotations

from typing import Any, Dict


def attention_blocks(config: Dict[str, Any]) -> int:
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]].count("*")


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    dim, seq, batch = config["head_dim"], traffic["seq_len"], traffic["sequences_per_step"]
    bh_blocks = batch * config["num_attention_heads"] * attention_blocks(config)
    one_matmul = 2.0 * seq * (seq + 1) / 2.0 * dim  # causal: visible pairs only
    tensor, stats = seq * dim * 2, seq * 4
    forward = 4 * tensor + stats
    backward = 8 * tensor + 2 * stats
    return {"flops": bh_blocks * (2 + 4) * one_matmul, "bytes": float(bh_blocks * (forward + backward))}
