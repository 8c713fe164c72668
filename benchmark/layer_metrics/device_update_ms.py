"""Device time per step of the update program (`jit_apply`, speculative or
not: the same function), read like `device_grad_ms`.  An update runs after its
step's vote, so the capture has to outlast the traced step's end to hold one.
With six traced steps it holds the earlier steps' updates; the four-group
cell's one traced step ends before its update runs, so the metric has no `.4g`
twin there until that cell traces a second step."""

LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import program_spans

    return program_spans.program_ms(program_spans.of_traced_run(), program_spans.UPDATE_PROGRAM,
                                    int(ctx["traffic"].get("trace_skip_steps", 0)))
