"""Median per step of the `d2h_copy` sub-spans summed: the second pass over a
bucket's bytes, from PJRT's host buffer into the averager's flat buffer."""

LAYER = "cross-group exchange"
UNIT = "ms"
MOVES = "tokens_per_s.4g"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_spans

    return program_spans.median_per_step(ctx, program_spans.sum_of("d2h_copy"))
