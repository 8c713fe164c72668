"""The share of the held experts' hidden units that ReLU leaves above zero,
median over the steady steps: `moe_active_units` / `moe_units_held` of the
program's `step_summary` records (counted inside the gradient program over the
rows that hold an assignment, all layers).  The quantity a ReGLU model is built
around — a kernel that skipped the dead units would do this share of the down
projection's work: near 0.5 at weights from the seed, 1 if SiLU ran.  None
where the program counts no such thing (a tree from before PR 51, experts under
SiLU)."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    shares = [s["moe_active_units"] / s["moe_units_held"] for s in summaries
              if s.get("moe_units_held") and "moe_active_units" in s]
    return stats.median(shares) if shares else None
