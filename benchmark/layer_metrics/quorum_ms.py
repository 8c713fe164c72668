"""Median per step of the `quorum` span: the lighthouse round as the Manager
times it (host monotonic clock).  The train thread waits it out, since the
gradient program's dispatch returns at once."""

LAYER = "control plane"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stats

    return stats.span_median(ctx["steady_steps"], "quorum")
