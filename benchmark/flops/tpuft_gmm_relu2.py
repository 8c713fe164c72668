"""Operations and bytes the program's grouped-matmul kernels need where the
chip holds 8 of 128 UN-GATED ReLU^2 experts a block (Nemotron-H's: [2,688,
1,856] up and [1,856, 2,688] down, at about 768 rows each), from shapes and one
counter.

As `flops/tpuft_gmm_reglu.py`, with TWO projections an expert and not three:
`tpuft_gmm_fwd`, `tpuft_gmm_dlhs`, `tpuft_gmm_drhs` run once each for the up
and for the down projection over the rows of the experts held here; `rows_held`
is the program's own count (`moe_rows_held`, summed over the expert blocks: an
assignment to an expert held on another chip has no row).  Each product is 2 *
rows * hidden * width operations, three products a projection, two projections
— at the PUBLISHED width of 1,856 columns: the kernels run over operands
padded with zeros to 1,920 (15 lane tiles; `ops/grouped_matmul._padded`), and
the 64 columns of padding are their overhead, not required work, as are the
rows of zeros that pad an expert's rows to a tile.  Every hidden unit counts:
the kernels are dense, and the share of the units that ReLU leaves at zero
(`relu2_active_share`) is computed like the other.

Bytes are the least the kernels must move through HBM: rows in bf16, the HELD
experts' matrices read in bf16 and their gradient written in f32, each once a
kernel.  At 768 rows an expert the operations bound: 24,576 rows a step are
1.47 TFLOP (7.5 ms at the bf16 peak) against 3.9 GB (4.8 ms at the HBM peak).
"""

from __future__ import annotations

from typing import Any, Dict


def expert_blocks(config: Dict[str, Any]) -> int:
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]].count("E")


def per_step(config: Dict[str, Any], rows_held: float) -> Dict[str, float]:
    """{"flops", "bytes"} of all the grouped matmuls of one step of one group;
    `rows_held` summed over the expert blocks."""
    hidden, inner = config["hidden_size"], config["moe_intermediate_size"]
    flops = 2 * 3 * 2.0 * rows_held * hidden * inner
    wide, narrow = rows_held * hidden * 2, rows_held * inner * 2
    matrices = expert_blocks(config) * config["n_routed_experts"] * hidden * inner
    one_projection = (
        (wide + narrow + matrices * 2)      # forward: rows in, rows out, the matrices
        + (wide + narrow + matrices * 2)    # gradient of the rows: cotangent in, gradient out, the matrices
        + (wide + narrow + matrices * 4)    # gradient of the matrices: rows and cotangent in, f32 out
    )
    return {"flops": flops, "bytes": float(2 * one_projection)}
