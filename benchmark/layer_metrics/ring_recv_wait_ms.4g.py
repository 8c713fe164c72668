"""Median per step of what `step_summary.allreduce_lanes.hops[*].recv_wait_s` grew
by: the ring's workers blocked on a peer's frame.  Thread-seconds summed over
lanes and stripes, so it may pass the wall; against `ring_combine_ms.4g` it
says whether the ring waits for peers or computes."""

LAYER = "cross-group exchange"
UNIT = "ms"
MOVES = "tokens_per_s.4g"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import program_spans

    v = program_spans.counter_per_step(ctx, "recv_wait_s")
    return None if v is None else v * 1e3
