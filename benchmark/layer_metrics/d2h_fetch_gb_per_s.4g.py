"""Median per step of the fetched bytes over the `d2h_fetch` sub-spans summed:
the rate of `np.asarray(device array)` alone, the DMA into PJRT's host buffer,
with the wait for the program and the second copy taken out.  Higher is better."""

LAYER = "cross-group exchange"
UNIT = "GB/s"
MOVES = "tokens_per_s.4g"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_spans

    return program_spans.median_per_step(ctx, program_spans.d2h_fetch_gb_per_s)
