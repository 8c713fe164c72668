"""Median per step of what `step_summary.allreduce_lanes.hops[*].combine_s` grew
by: the ring's workers summing a received chunk into their own (thread-seconds)."""

LAYER = "cross-group exchange"
UNIT = "ms"
MOVES = "tokens_per_s.4g"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import program_spans

    v = program_spans.counter_per_step(ctx, "combine_s")
    return None if v is None else v * 1e3
