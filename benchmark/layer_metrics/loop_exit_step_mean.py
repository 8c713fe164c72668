"""The pass after which a token leaves, in the mean under the gates' exit
distribution, median over the steady steps: sum_t t * `loop_exit_mass`[t] over
the step's tokens, from the program's `step_summary` records (counted inside the
gradient program; `loop_exit_mass` [T] is sum_i p_t).  1 is every token leaving
after the first pass, T none before the last; near 1.9 at four passes and the
seed's gate (p near 1/2, 1/4, 1/8, 1/8).  Says whether the loss still weighs the
later passes.  None where the program counts no such thing."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    masses = [s["loop_exit_mass"] for s in summaries if isinstance(s.get("loop_exit_mass"), list)]
    values = [sum((t + 1) * m for t, m in enumerate(mass)) / sum(mass) for mass in masses if sum(mass) > 0]
    return stats.median(values) if values else None
