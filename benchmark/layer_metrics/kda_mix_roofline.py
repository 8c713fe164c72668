"""The `tpuft_kdamix_*` kernels' share of their roofline: the least time the
chip could take for what Kimi Delta Attention puts around its scan — each
operand of each half read once and each result written once a direction, over
the HBM peak (`flops/tpuft_kdamix.py`; the recomputed forward, the
convolution's rows from the tile before and the partial sums are not required
work) — over the summed device time per step of the gradient program's
instructions whose name holds `tpuft_kdamix_`, from the run's instruction
table (`device_parts.of_run`).  None where no such kernel ran (a tree from
before PR 49, a mesh of several devices, a model without a KDA layer)."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import device_parts, program_spans, stats

    found = device_parts.of_run(ctx)
    if found is None or ctx["peaks"] is None or "linear_attn_config" not in ctx["config"]:
        return None
    instructions = found["programs"][program_spans.GRAD_PROGRAM]["instructions"]
    ms = sum(entry["ms"] for name, entry in instructions.items() if "tpuft_kdamix_" in name)
    if ms <= 0:
        return None
    need = ctx["bench"].flops("tpuft_kdamix").per_step(ctx["config"], ctx["traffic"])
    return stats.roofline_percent(need, ctx["peaks"], ms / 1e3)
