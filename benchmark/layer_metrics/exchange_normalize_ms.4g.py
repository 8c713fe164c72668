"""Median per step of the `normalize` sub-spans summed: `out / participants` and
the cast, a fresh array per bucket, on the thread that resolved the op's future."""

LAYER = "cross-group exchange"
UNIT = "ms"
MOVES = "tokens_per_s.4g"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_spans

    return program_spans.median_per_step(ctx, program_spans.sum_of("normalize"))
