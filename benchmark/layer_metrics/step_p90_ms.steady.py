"""90th percentile, nearest rank, of the host-clock time of the steps of the
traced run that lie outside the profiler's capture (and not the one step that
follows it on a drained device).  The step tail that synchronous groups pace
each other by; per layer and not end to end because its spread between checks
has no bound that the contract's two tests both admit (PERF.md section 6)."""

LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def read(ctx):
    from benchmark import stats

    values = [s["ms"] for s in ctx["steady_steps"]]
    return stats.percentile_nearest_rank(values, 90) if len(values) >= 20 else None
