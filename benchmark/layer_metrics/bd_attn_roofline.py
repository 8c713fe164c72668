"""The block-diffusion attention kernels' share of their roofline: the least
time the chip could take for the six products over the LIVE pairs of the
three-part block mask (the larger of operations over the bf16 peak and bytes
over the HBM peak, from L, b, the heads and their width by `flops/tpuft_bd.py`)
over the `tpuft_bd_*` kernels' summed device time per step in the trace.  None
where there is no such kernel or the configuration states no block diffusion."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("bd_attn", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "block_diffusion" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_bd").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
