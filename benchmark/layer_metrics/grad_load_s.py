"""Seconds of the gradient program's first `backend` stage before the
window: a compile where the persistent cache missed, the cache's retrieval
(read, decompress, load onto the device) where it hit."""

LAYER = "train step"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_builds

    return program_builds.grad_load_s(ctx)
