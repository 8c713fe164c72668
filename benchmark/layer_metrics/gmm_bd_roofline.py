"""The tpuft_gmm kernels' share of their roofline under block diffusion, where
the chip holds 16 of 128 experts of 2,048 x 768 and the rows are the doubled
stream's: the least time the chip could take for the grouped matmuls over the
rows that fell on held experts (the larger of operations over the bf16 peak
and bytes over the HBM peak, by `flops/tpuft_gmm_wide.py`, whose count — three
SwiGLU projections an expert, the held experts' matrices once a kernel — reads
this configuration's keys as they are; the rows from the program's
`moe_rows_held` counter, median over the steady steps; padding rows not
counted) over the kernels' summed device time per step in the trace.  A quarter
of the rows carry the [MASK] embedding, so the rows an expert sees are uneven:
the kernels' tiles of padding are part of what this share reads.  None where
the program has no such kernel, counts no held rows, or the configuration
states no block diffusion."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("gmm", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "block_diffusion" not in ctx["config"]:
        return None
    from benchmark import stats
    from benchmark.spec import reader_beside

    held = [s["moe_rows_held"] for s in reader_beside(__file__, "moe_held_share").rows_held(ctx)]
    if not held:
        return None
    need = ctx["bench"].flops("tpuft_gmm_wide").per_step(ctx["config"], stats.median(held))
    return stats.roofline_percent(need, ctx["peaks"], seconds)
