"""The busiest expert's rows over the mean, median over the steady steps:
`moe_tokens_per_expert_max` / `moe_tokens_per_expert_mean` of the program's
`step_summary` records (counted inside the gradient program, over all layers;
`TrainStep` lands a step's counters in the next step's record).  1.0 is a
perfectly even router; says whether a step-time change is routing.  None
where the program counts no such thing."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def steady_summaries(ctx):
    """The `step_summary` records written inside the steady steps."""
    from benchmark import program_spans

    summaries = program_spans.of_run()["summaries"]
    out = []
    for step in ctx["steady_steps"]:
        lo, hi = step["start_mono_ns"], step["start_mono_ns"] + step["ms"] * 1e6
        out.extend(s for s in summaries if lo <= s["t_mono"] * 1e9 <= hi)
    return out


def read(ctx):
    from benchmark import stats

    ratios = [s["moe_tokens_per_expert_max"] / s["moe_tokens_per_expert_mean"] for s in steady_summaries(ctx)
              if s.get("moe_tokens_per_expert_mean")]
    return stats.median(ratios) if ratios else None
