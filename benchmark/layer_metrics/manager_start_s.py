"""Seconds of `Manager.__init__`, first line to return (the `manager_start`
sub-span): the store, the native ManagerServer's bind and its first word with
the lighthouse, the Manager's client — the control plane's share of a start."""

LAYER = "control plane"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    from benchmark import program_builds

    return program_builds.manager_start_s(ctx)
