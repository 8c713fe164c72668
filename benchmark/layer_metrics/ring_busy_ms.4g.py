"""Median per step of the time during which some ring op was running: the length
of the union of the `ring_run` intervals.  Against the `allreduce_d2h` spans it
says whether the ring overlaps the fetches or starts when they end."""

LAYER = "cross-group exchange"
UNIT = "ms"
MOVES = "tokens_per_s.4g"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_spans

    return program_spans.median_per_step(ctx, program_spans.ring_busy_ms)
