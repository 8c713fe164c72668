"""The share of its roofline of attention over the selected keys: the least time
the chip could take for what the algorithm needs (the larger of operations over
the bf16 peak and bytes over the HBM peak, both from shapes by
`flops/tpuft_dsa_attn.py`: the six products over the SELECTED pairs only) over
the two kernels' summed device time per step in the trace.  Kernels that visit
every causal tile read near the selected share (an eighth at 32,768 positions
and topk 2,048) of what `attn_roofline` would say of them.  None where the
program has no such kernel or the configuration no indexer."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("dsa_attn", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "sa_config" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_dsa_attn").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
