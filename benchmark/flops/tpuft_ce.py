"""Operations and bytes the program's fused cross-entropy kernels need.

`tpuft_ce_lse` computes the logits of N rows against the [E, V] head block by
block and keeps only the log-sum-exp; `tpuft_ce_dlogits` computes them again
and writes the scaled bf16 dlogits [N, V] (ops/cross_entropy.py).  Both matmuls
are required by the fused algorithm (the logits are never stored, so the
backward must form them again): 2 * N * E * V operations each.  Bytes: x and w
read once per kernel in bf16, lse and targets in 4 bytes a row, dlogits
written once in bf16.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    n = traffic["seq_len"] * traffic["sequences_per_step"]
    e, v = config["hidden_size"], config["vocab_size"]
    flops = 2 * (2.0 * n * e * v)
    x, w = n * e * 2, e * v * 2
    lse_kernel = x + w + n * 4
    dlogits_kernel = x + w + 2 * n * 4 + n * v * 2
    return {"flops": flops, "bytes": float(lse_kernel + dlogits_kernel)}
