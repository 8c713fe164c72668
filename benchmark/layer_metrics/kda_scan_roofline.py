"""The `tpuft_kda_*` kernels' share of their roofline: the least time the chip
could take for the chunked gated delta rule's REQUIRED work, forward and
backward (the larger of operations over the bf16 peak and bytes over the HBM
peak, from shapes and the stated chunk size by `flops/tpuft_kda.py`; the
forward pass that the backward runs again, the recomputation inside the
backward kernel and the program's extra products for bounded exponents are not
required work) over the kernels' summed device time per step in the trace.
None where there is no such kernel."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("kda", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "linear_attn_config" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_kda").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
