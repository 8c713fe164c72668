"""Bytes one group puts on the ring for one step's gradients, as
`GradientAverager.last_stats["wire_bytes"]` counts them in the first merged
step.  A count: it repeats exactly.  Nothing to read with one group."""

LAYER = "cross-group exchange"
UNIT = "bytes"
MOVES = "tokens_per_s.4g"
SOURCE = "program_counter"


def read(ctx):
    return ctx["averager_stats"].get("wire_bytes")
