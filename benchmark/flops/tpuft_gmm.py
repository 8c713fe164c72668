"""Operations and bytes the program's grouped-matmul kernels need, from shapes.

`tpuft_gmm_fwd`, `tpuft_gmm_dlhs`, `tpuft_gmm_drhs` (ops/grouped_matmul.py)
run once each for each of an expert layer's three projections: the forward
product of the rows with their expert's matrix, the gradient of the rows, and
the gradient of the stacked matrices.  Rows: one per (token, expert)
assignment, tokens * num_experts_per_tok a step whatever the router decides.
Each product is 2 * rows * hidden * intermediate operations, so a step needs
layers * 3 projections * 3 products of them.  The rows of zeros that pad an
expert's rows to a tile are the kernel's own overhead and are not counted.

Bytes are the least the kernels must move through HBM: every operand read
once and every result written once — rows in bf16, the experts' matrices read
in bf16 (the program rounds the f32 parameters once a step, outside the
kernels) and their gradient written in f32 (it leaves the accumulator in the
parameters' type).  By these counts the kernels are compute-bound on a v5e
(about 1,000 rows an expert at this traffic).
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of all the grouped matmuls of one step of one group."""
    rows = traffic["seq_len"] * traffic["sequences_per_step"] * config["num_experts_per_tok"]
    hidden, inner = config["hidden_size"], config["intermediate_size"]
    experts, layers = config["num_experts"], config["num_hidden_layers"]
    product = 2.0 * rows * hidden * inner
    flops = layers * 3 * 3 * product
    wide, narrow, matrices = rows * hidden * 2, rows * inner * 2, experts * hidden * inner
    one_projection = (
        (wide + narrow + matrices * 2)      # forward: rows in, rows out, the matrices
        + (wide + narrow + matrices * 2)    # gradient of the rows: cotangent in, gradient out, the matrices
        + (wide + narrow + matrices * 4)    # gradient of the matrices: rows and cotangent in, f32 out
    )
    return {"flops": flops, "bytes": float(layers * 3 * one_projection)}
