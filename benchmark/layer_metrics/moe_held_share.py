"""The share of a step's (token, expert) assignments that fell on experts
held on this chip, median over the steady steps: `moe_rows_held` /
`moe_assignments` of the program's `step_summary` records (counted inside the
gradient program over all sparse layers).  An even router gives held / routed
experts: 0.125 on an eighth of them.  None where the program counts no such
thing (every expert held, or a program without the counters)."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def rows_held(ctx):
    """`moe_rows_held` of each steady step's summary that has it."""
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    return [s for s in summaries if s.get("moe_assignments") and "moe_rows_held" in s]


def read(ctx):
    from benchmark import stats

    shares = [s["moe_rows_held"] / s["moe_assignments"] for s in rows_held(ctx)]
    return stats.median(shares) if shares else None
