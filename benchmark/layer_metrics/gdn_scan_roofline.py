"""The share of its roofline that Gated DeltaNet's scan reaches: the least time
the chip could take for the chunked gated delta rule's REQUIRED work with a
decay a head, forward and backward (the larger of operations over the bf16
peak and bytes over the HBM peak, from shapes and the stated chunk size by
`flops/tpuft_gdn.py`: one triangular product for R and for Rq, q and k read
once a KEY head, one float32 of decay a value head and position — whatever
kernels implement it) over the scan kernels' summed device time per step in the
trace.  None where there is no such kernel."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("gdn", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "linear_num_value_heads" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_gdn").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
