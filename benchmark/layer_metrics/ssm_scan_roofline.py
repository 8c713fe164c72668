"""The `tpuft_ssd_*` kernels' share of their roofline: the least time the chip
could take for the chunked state-space recurrence's REQUIRED work, forward and
backward (the larger of operations over the bf16 peak and bytes over the HBM
peak, from shapes and the published chunk size by `flops/tpuft_ssd.py`; the
forward pass that the backward runs again, the recomputation inside the
backward kernel and the masked upper triangles are not required work) over the
kernels' summed device time per step in the trace.  None where there is no such
kernel."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("ssd", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "hybrid_override_pattern" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_ssd").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
