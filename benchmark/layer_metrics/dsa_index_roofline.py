"""The share of its roofline of the indexer's loss kernel: the least time the chip
could take for what the algorithm needs (by `flops/tpuft_dsa_index.py`: the
indexer's three products over the VISIBLE pairs; the heads' probabilities formed
again for the loss's target and the selection passes are not required work)
over `tpuft_dsa_index_loss`'s summed device time per step in the trace.  None
where the program has no such kernel or the configuration no indexer."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("dsa_index", 0.0)
    if seconds <= 0 or ctx["peaks"] is None or "sa_config" not in ctx["config"]:
        return None
    from benchmark import stats

    need = ctx["bench"].flops("tpuft_dsa_index").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], seconds)
