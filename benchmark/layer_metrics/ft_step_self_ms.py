"""Median per step of the `ft_step` frame less what is named inside it on the
train thread (the exchange's and the vote's spans, `quorum_wait`, the two
dispatches, `h2d_put`): bucket planning, `copy_to_host_async` hints, the
averager's bookkeeping, the Manager's commit path outside the vote."""

LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_spans

    return program_spans.median_per_step(ctx, program_spans.ft_step_self_ms)
