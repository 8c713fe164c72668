"""Seconds before the window during which the update program (`jit_apply`,
donating or speculative) was being built: the union of all three stages of
every build of it."""

LAYER = "train step"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_builds

    return program_builds.update_build_s(ctx)
