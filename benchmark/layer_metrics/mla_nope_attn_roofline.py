"""The tpuft_fa kernels' share of their roofline where they run UNROTATED
latent attention: the least time the chip could take for what the algorithm
needs at 192 / 128 over the latent layers WITHIN THE DEPTH (the larger of
operations over the bf16 peak and bytes over the HBM peak, from shapes by
`flops/tpuft_fa_mla_nope.py`; the zero columns that pad 192 to 256 are not
required work) over the kernels' summed device time per step in the trace.
None where there is no such kernel or the configuration is not of this family."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("attn", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or not ctx["config"].get("mla_use_nope"):
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_fa_mla_nope").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
