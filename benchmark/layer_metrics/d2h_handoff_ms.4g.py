"""Median per step of the `allreduce_d2h` spans' self time: the spans summed less
their children (`d2h_ready`, `d2h_fetch`, `d2h_copy`) — the hand-off of each
bucket to the materializer thread and back, on a host whose cores the ring's
threads are using.  Never negative: the children lie inside the span."""

LAYER = "cross-group exchange"
UNIT = "ms"
MOVES = "tokens_per_s.4g"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_spans

    return program_spans.median_per_step(ctx, program_spans.d2h_handoff_ms)
