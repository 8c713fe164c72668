"""The share of a step's (position, layer) choices that took NO expert, median
over the steady steps: `moe_skipped` / `moe_assignments` of the program's
`step_summary` records (both counted inside the gradient program over all
layers: the router's last output is the choice that takes none).  An even
router over 16 experts and the skip choice gives 1/17 = 0.0588.  A skipped
position has no row and is never counted in `moe_dropped`.  None where the
program counts no such thing (a tree from before PR 41, a router without the
choice)."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    shares = [s["moe_skipped"] / s["moe_assignments"] for s in summaries
              if s.get("moe_assignments") and "moe_skipped" in s]
    return stats.median(shares) if shares else None
