"""Device time per step of the gradient program: the median duration of
`jit_value_and_grad`'s executions on the trace's `XLA Modules` line, over those
that start in the counted traced steps."""

LAYER = "train step"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import program_spans

    return program_spans.program_ms(program_spans.of_traced_run(), program_spans.GRAD_PROGRAM,
                                    int(ctx["traffic"].get("trace_skip_steps", 0)))
