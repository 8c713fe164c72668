"""Operations and bytes the program's grouped-matmul kernels need where the
chip holds 32 of 512 narrow experts a layer (Qwen3-Next-80B-A3B: [2,048, 512]
at about 320 rows each), from shapes and one counter.

As `flops/tpuft_gmm_held256.py`, under this configuration's keys: `tpuft_gmm_fwd`,
`tpuft_gmm_dlhs`, `tpuft_gmm_drhs` run once each for each of a layer's three
projections over the rows of the experts held here; `rows_held` is the
program's own count (`moe_rows_held`, summed over the layers: an assignment to
an expert held on another chip has no row).  Each product is 2 * rows * hidden
* width operations, three products a projection, three projections.  The rows
of zeros that pad an expert's rows to a tile are the kernel's own overhead and
are not counted.

Bytes are the least the kernels must move through HBM: rows in bf16, the HELD
experts' matrices read in bf16 and their gradient written in f32, each once a
kernel.  At 320 rows an expert the matrices lead: 40,960 rows a step are 0.77
TFLOP (3.9 ms at the bf16 peak) and 4.4 GB (5.4 ms at the HBM peak), of which
the matrices and their float32 gradients are 3.2 GB.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], rows_held: float) -> Dict[str, float]:
    """{"flops", "bytes"} of all the grouped matmuls of one step of one group;
    `rows_held` summed over the layers."""
    hidden, inner, layers = config["hidden_size"], config["moe_intermediate_size"], config["num_hidden_layers"]
    flops = 3 * 3 * 2.0 * rows_held * hidden * inner
    wide, narrow = rows_held * hidden * 2, rows_held * inner * 2
    matrices = layers * config["num_experts"] * hidden * inner
    one_projection = (
        (wide + narrow + matrices * 2)      # forward: rows in, rows out, the matrices
        + (wide + narrow + matrices * 2)    # gradient of the rows: cotangent in, gradient out, the matrices
        + (wide + narrow + matrices * 4)    # gradient of the matrices: rows and cotangent in, f32 out
    )
    return {"flops": flops, "bytes": float(3 * one_projection)}
