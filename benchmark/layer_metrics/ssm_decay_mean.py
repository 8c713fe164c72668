"""The mean of the decay a = exp(dt A) over positions, heads and Mamba-2
blocks, median over the steady steps: `ssm_decay_mean` of the program's
`step_summary` records (counted inside the gradient program).  1 is a state
that only accumulates (nothing forgotten), 0 one that is never read; it says
whether a change of the scan's time is the decay's doing.  None where the
program counts no such thing (a tree from before PR 56, a model without a
Mamba-2 block)."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    values = [s["ssm_decay_mean"] for s in summaries if "ssm_decay_mean" in s]
    return stats.median(values) if values else None
