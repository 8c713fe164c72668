"""Median per step of what the ring's `sent` counters grew by, all lanes and
tiers: the bytes one group really puts on the wire for a step's gradients
(2 (n-1)/n of the payload on a ring of n), where `exchange_wire_bytes` counts
the payload once."""

LAYER = "cross-group exchange"
UNIT = "bytes"
MOVES = "tokens_per_s.4g"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import program_spans

    return program_spans.counter_per_step(ctx, "sent")
