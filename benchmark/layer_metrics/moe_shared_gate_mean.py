"""The mean of the shared expert's sigmoid gate over positions and layers,
median over the steady steps: `moe_shared_gate_mean` of the program's
`step_summary` records (counted inside the gradient program).  Near 1/2 at
seeded weights; 0 is a layer without its shared expert, 1 an ungated one.  None
where the program counts no such thing (a tree from before PR 68, a shared
expert without a gate)."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    values = [s["moe_shared_gate_mean"] for s in summaries if "moe_shared_gate_mean" in s]
    return stats.median(values) if values else None
