"""Operations and bytes the program's grouped-matmul kernels need where the
chip holds 8 of 256 narrow experts a layer (Kimi-Linear-48B-A3B: [2,304, 1,024]
at about 512 rows each), from shapes and one counter.

As `flops/tpuft_gmm_held.py`, under this configuration's keys: `tpuft_gmm_fwd`,
`tpuft_gmm_dlhs`, `tpuft_gmm_drhs` run once each for each of a sparse layer's
three projections over the rows of the experts held here; `rows_held` is the
program's own count (`moe_rows_held`, summed over the sparse layers: an
assignment to an expert held on another chip has no row).  Each product is
2 * rows * hidden * width operations, three products a projection, three
projections.  The rows of zeros that pad an expert's rows to a tile are the
kernel's own overhead and are not counted.

Bytes are the least the kernels must move through HBM: rows in bf16, the HELD
experts' matrices read in bf16 and their gradient written in f32, each once a
kernel.  At 512 rows an expert the two bounds meet: 16,384 rows a step are
0.70 TFLOP (3.5 ms at the bf16 peak) and 2.79 GB (3.4 ms at the HBM peak), of
which the matrices and their float32 gradients are 2.3 GB — half the rows and
the products are bound by the matrices' traffic alone.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], rows_held: float) -> Dict[str, float]:
    """{"flops", "bytes"} of all the grouped matmuls of one step of one group;
    `rows_held` summed over the sparse layers."""
    hidden, inner = config["hidden_size"], config["moe_intermediate_size"]
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    flops = 3 * 3 * 2.0 * rows_held * hidden * inner
    wide, narrow = rows_held * hidden * 2, rows_held * inner * 2
    matrices = layers * config["num_experts"] * hidden * inner
    one_projection = (
        (wide + narrow + matrices * 2)      # forward: rows in, rows out, the matrices
        + (wide + narrow + matrices * 2)    # gradient of the rows: cotangent in, gradient out, the matrices
        + (wide + narrow + matrices * 4)    # gradient of the matrices: rows and cotangent in, f32 out
    )
    return {"flops": flops, "bytes": float(3 * one_projection)}
