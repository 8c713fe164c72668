"""The last pass's mean next-token loss over the first pass's, median over the
steady steps: `loop_pass_loss`[T] / `loop_pass_loss`[1] of the program's
`step_summary` records (counted inside the gradient program).  Under 1 where
more passes over the same weights predict better; 1 at weights from the seed on
random ids, where no pass predicts anything.  None where the program counts no
such thing."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    losses = [s["loop_pass_loss"] for s in summaries if isinstance(s.get("loop_pass_loss"), list)]
    values = [loss[-1] / loss[0] for loss in losses if loss[0] > 0]
    return stats.median(values) if values else None
