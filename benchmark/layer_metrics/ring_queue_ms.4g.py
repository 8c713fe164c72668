"""Median per step of the `ring_queue` sub-spans summed: from
`Manager.allreduce` handing a bucket's op to the collective to a ring worker
taking it up.  Ops queue behind each other, so the sum can pass the wall."""

LAYER = "cross-group exchange"
UNIT = "ms"
MOVES = "tokens_per_s.4g"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_spans

    return program_spans.median_per_step(ctx, program_spans.sum_of("ring_queue"))
