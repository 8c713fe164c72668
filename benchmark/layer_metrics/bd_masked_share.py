"""The share of a step's data tokens that the noise masked, median over the
steady steps: `bd_masked_share` of the program's `step_summary` records (counted
inside the gradient program).  A block's level is uniform on [0.001, 1), so the
share is near 1/2; a reading far from it says the schedule or the key is off.
None where the program counts no such thing."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    shares = [s["bd_masked_share"] for s in summaries if "bd_masked_share" in s]
    return stats.median(shares) if shares else None
