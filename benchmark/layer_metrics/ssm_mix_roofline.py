"""The `tpuft_ssmmix_*` kernels' share of their roofline: the least time the
chip could take for what a Mamba-2 block puts around its scan — each operand of
each half read once and each result written once a run, the forward halves'
two runs a step and the backward halves' one, over the HBM peak
(`flops/tpuft_ssmmix.py`; the convolution's rows from the tile before and the
partial sums are not required work) — over the summed device time per step of
the gradient program's instructions whose name holds `tpuft_ssmmix_`, from the
run's instruction table (`device_parts.of_run`).  None where no such kernel ran
(a tree from before PR 57, a mesh of several devices, a model without a Mamba-2
block): a run in which the XLA halves ran reads None."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts, program_spans, stats

    found = device_parts.of_run(ctx)
    if found is None or ctx["peaks"] is None or "hybrid_override_pattern" not in ctx["config"]:
        return None
    instructions = found["programs"][program_spans.GRAD_PROGRAM]["instructions"]
    ms = sum(entry["ms"] for name, entry in instructions.items() if "tpuft_ssmmix_" in name)
    if ms <= 0:
        return None
    need = ctx["bench"].flops("tpuft_ssmmix").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], ms / 1e3)
