"""The tpuft_gmm kernels' share of their roofline where the chip holds 32 of 512
narrow experts a layer ([2,048, 512] at about 320 rows each): the least time
the chip could take for the grouped matmuls over the rows that fell on held
experts (the larger of operations over the bf16 peak and bytes over the HBM
peak, by `flops/tpuft_gmm_held512.py`; the rows from the program's
`moe_rows_held` counter, median over the steady steps; padding rows not
counted) over the kernels' summed device time per step in the trace.  None
where the program has no such kernel, counts no held rows, or the configuration
is not of this family."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("gmm", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "linear_num_value_heads" not in ctx["config"]:
        return None
    from benchmark import stats
    from benchmark.spec import reader_beside

    held = [s["moe_rows_held"] for s in reader_beside(__file__, "moe_held_share").rows_held(ctx)]
    if not held:
        return None
    need = ctx["bench"].flops("tpuft_gmm_held512").per_step(ctx["config"], stats.median(held))
    return stats.roofline_percent(need, ctx["peaks"], seconds)
