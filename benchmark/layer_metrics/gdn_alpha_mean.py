"""The mean of the decay alpha = exp(g) over positions, value heads and Gated
DeltaNet layers, median over the steady steps: `gdn_alpha_mean` of the
program's `step_summary` records (counted inside the gradient program).  1 is a
plain delta rule (nothing forgotten), 0 a state that is never read; it says
whether a change of the scan's time is the gate's doing.  None where the
program counts no such thing (a tree from before PR 68, a model without such a
layer)."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    values = [s["gdn_alpha_mean"] for s in summaries if "gdn_alpha_mean" in s]
    return stats.median(values) if values else None
