"""Median per step of the `quorum_wait` sub-spans summed: what the train thread
waits for the quorum (`Manager.wait_quorum`), where `quorum_ms` is the quorum
thread's RPC.  A step whose quorum had settled before it was asked for waits 0."""

LAYER = "control plane"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_spans

    return program_spans.median_per_step(ctx, program_spans.sum_of("quorum_wait", always=True))
