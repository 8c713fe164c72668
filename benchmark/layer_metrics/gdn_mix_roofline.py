"""The mix kernels' share of their roofline in a Gated DeltaNet model: the
least time the chip could take for what `models/gdn.py` puts around its scan —
each operand of each half read once and each result written once a direction,
over the HBM peak (`flops/tpuft_gdnmix.py`; the recomputed forward, the
convolution's rows from the tile before, the partial sums and the decay a head
are not required work) — over the summed device time per step of the gradient
program's instructions whose name holds `tpuft_kdamix_` (`ops/kda_mix.py`'s
kernels, which run these halves too), from the run's instruction table
(`device_parts.of_run`).  None where no such kernel ran (a tree from before
PR 69, whose halves are XLA fusions; a mesh of several devices) and in a model
without a Gated DeltaNet layer (Kimi's cell reads the same kernels as
`kda_mix_roofline`)."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts, program_spans, stats

    found = device_parts.of_run(ctx)
    if found is None or ctx["peaks"] is None or "linear_num_value_heads" not in ctx["config"]:
        return None
    instructions = found["programs"][program_spans.GRAD_PROGRAM]["instructions"]
    ms = sum(entry["ms"] for name, entry in instructions.items() if "tpuft_kdamix_" in name)
    if ms <= 0:
        return None
    need = ctx["bench"].flops("tpuft_gdnmix").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], ms / 1e3)
