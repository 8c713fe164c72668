"""Operations and bytes the program's grouped-matmul kernels need where the
chip holds a share of FEW WIDE experts (ZAYA1-8B: 8 of 16, each 2,048 x 2,048),
from shapes and one counter.

As `flops/tpuft_gmm_held.py`, under this configuration's keys: `tpuft_gmm_fwd`,
`tpuft_gmm_dlhs`, `tpuft_gmm_drhs` run once each for each of a layer's three
projections over the rows of the experts held here; `rows_held` is the
program's own count (`moe_rows_held`, summed over the layers: a position that
took no expert, or one held on the other chip, has no row).  Each product is
2 * rows * hidden * width operations, three products a projection, three
projections.  The rows of zeros that pad an expert's rows to a tile are the
kernel's own overhead and are not counted.

Bytes are the least the kernels must move through HBM: rows in bf16, the HELD
experts' matrices read in bf16 and their gradient written in f32, each once a
kernel.  At about a thousand rows an expert and 4.2M weights a matrix the
products are bound by the MXU by these counts, not by the matrices' reads.
"""

from __future__ import annotations

from typing import Any, Dict


def per_step(config: Dict[str, Any], rows_held: float) -> Dict[str, float]:
    """{"flops", "bytes"} of all the grouped matmuls of one step of one group;
    `rows_held` summed over the layers."""
    hidden, inner = config["hidden_size"], config["moe_intermediate_size"]
    flops = 3 * 3 * 2.0 * rows_held * hidden * inner
    wide, narrow = rows_held * hidden * 2, rows_held * inner * 2
    matrices = config["num_hidden_layers"] * config["num_experts"] * hidden * inner
    one_projection = (
        (wide + narrow + matrices * 2)      # forward: rows in, rows out, the matrices
        + (wide + narrow + matrices * 2)    # gradient of the rows: cotangent in, gradient out, the matrices
        + (wide + narrow + matrices * 4)    # gradient of the matrices: rows and cotangent in, f32 out
    )
    return {"flops": flops, "bytes": float(3 * one_projection)}
