"""(token, expert) assignments that reached no expert, the most in any steady
step: `moe_dropped` of the program's `step_summary` records, counted inside
the gradient program.  0 in every step, or the run is not dropless.  None
where the program counts no such thing."""

LAYER = "model"
UNIT = "count"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    dropped = [s["moe_dropped"] for s in summaries if "moe_dropped" in s]
    return max(dropped) if dropped else None
