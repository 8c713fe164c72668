"""Operations and bytes the program's fused cross-entropy kernels need in a
looped model (Ouro: every one of the `total_ut_steps` passes' states goes
through the one head).

T calls of `tpuft_ce_lse` and T of `tpuft_ce_dlogits` a step, each as
`flops/tpuft_ce.py` counts one — 2 * N * E * V operations a kernel; x and w read
once a kernel in bf16, lse and targets in 4 bytes a row, the bf16 dlogits
written once — and in the backward kernel 4 bytes a row more: the exit-weighted
loss hands it a scale a ROW (p_t / N), where a mean hands it one number.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    n = traffic["seq_len"] * traffic["sequences_per_step"]
    e, v, passes = config["hidden_size"], config["vocab_size"], config["total_ut_steps"]
    flops = passes * 2 * (2.0 * n * e * v)
    x, w = n * e * 2, e * v * 2
    lse_kernel = x + w + n * 4
    dlogits_kernel = x + w + 3 * n * 4 + n * v * 2  # targets, lse and the scale a row
    return {"flops": flops, "bytes": float(passes * (lse_kernel + dlogits_kernel))}
