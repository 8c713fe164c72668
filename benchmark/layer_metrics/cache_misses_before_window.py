"""Programs compiled before the window because the persistent cache did not
have them: `backend` stages with `cache: "miss"`.  0 in a warm run; in a
checkout's first run it is what `first_setup_s` has over `setup_s`."""

LAYER = "train step"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import program_builds

    stages = program_builds.backend_stages(ctx)
    return None if stages is None else sum(1 for r in stages if r.get("cache") == "miss")
