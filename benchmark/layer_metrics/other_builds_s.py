"""Seconds before the window during which some OTHER program was being
built, outside the gradient program's first build and the update program's:
optimizer state, batch converts, the exchange's programs, a program built
again under other arguments.  The union of every `program_build` stage before
the window less what `grad_trace_lower_s`, `grad_load_s` and `update_build_s`
count, so the four add up to the time some build was under way.  The
reference's weights are made before the program's recorder exists and are not
in it (`benchmark/program_builds.py`)."""

LAYER = "train step"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_builds

    return program_builds.other_builds_s(ctx)
