"""Seconds before the window during which the gradient program
(`jit_value_and_grad`) was being traced or lowered in its first build: the
union of its `trace` and `lower` stages (`program_build` records of the
program's own stream, JAX's events) — the program's own Python, which a warm
compile cache does not shorten.  Stages that nest inside them (kernels'
jits, constants computed at trace time) are inside the union."""

LAYER = "train step"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_builds

    return program_builds.grad_trace_lower_s(ctx)
