"""Median per step of the `commit_vote` span: the two-phase commit RPC."""

LAYER = "control plane"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def read(ctx):
    from benchmark import stats

    return stats.span_median(ctx["steady_steps"], "commit_vote")
