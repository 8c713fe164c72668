"""Median per step of the `d2h_ready` sub-spans summed: what the materializer
thread waits for the gradient program before a bucket's fetch can start (about
the gradient program's device time on the first bucket, nothing after).
Nothing to read with one group."""

LAYER = "cross-group exchange"
UNIT = "ms"
MOVES = "tokens_per_s.4g"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_spans

    return program_spans.median_per_step(ctx, program_spans.sum_of("d2h_ready"))
