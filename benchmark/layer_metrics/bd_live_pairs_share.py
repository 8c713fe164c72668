"""The share of the doubled stream's (query, key) pairs that the attention
walk's steps cover AND the three-part block mask keeps, median over the steady
steps: `bd_live_pairs_share` of the program's `step_summary` records, counted
at trace time from the tiles the `tpuft_bd_*` kernels step through
(`ops/attention.py` `bd_pairs_walked`).  With every live tile walked it is
(L**2 + L b) / (2 L)**2, 0.25006 at 16,384 tokens in blocks of 4, where a
causal call over 2 L would compute half; a walk short of a live tile reads
less.  None where the program counts no such thing."""

LAYER = "kernels"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    shares = [s["bd_live_pairs_share"] for s in summaries if "bd_live_pairs_share" in s]
    return stats.median(shares) if shares else None
