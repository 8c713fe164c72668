"""Programs built before the window opened: the `backend` stages among the
`program_build` records — each a compile or a load from the persistent cache,
so the number a cache's eviction multiplies."""

LAYER = "train step"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import program_builds

    stages = program_builds.backend_stages(ctx)
    return None if stages is None else len(stages)
