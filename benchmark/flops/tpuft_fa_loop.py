"""Operations and bytes the program's flash-attention kernels need in a looped
model (Ouro: `num_hidden_layers` layers run `total_ut_steps` times a step), at
16 ungrouped heads of 128.

`tpuft_fa_fwd` and `tpuft_fa_bwd_dkdv_dq` are required once each a layer AND
pass: L x T causal calls a direction.  The products and bytes of a call are
`flops/tpuft_fa.py`'s: six products over the visible half, each operand read and
each result written once.  A second run of the forward kernel inside a
rematerialised layer's backward pass is work the algorithm does not require and
is not counted, so with `program.remat_keeps_attention` false the share reads
lower by what that second run takes.
"""

from __future__ import annotations

from typing import Any, Dict


def calls(config: Dict[str, Any]) -> int:
    """Causal attention calls a direction and step: layers x passes."""
    return config["num_hidden_layers"] * config["total_ut_steps"]


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    dim, seq, batch = config["head_dim"], traffic["seq_len"], traffic["sequences_per_step"]
    bh_calls = batch * config["num_attention_heads"] * calls(config)
    one_matmul = 2.0 * seq * (seq + 1) / 2.0 * dim  # causal: visible pairs only
    tensor, stats = seq * dim * 2, seq * 4
    forward = 4 * tensor + stats  # read Q K V, write O, lse
    backward = 8 * tensor + 2 * stats  # read Q K V O dO, write dQ dK dV; lse, delta
    return {"flops": bh_calls * (2 + 4) * one_matmul, "bytes": float(bh_calls * (forward + backward))}
