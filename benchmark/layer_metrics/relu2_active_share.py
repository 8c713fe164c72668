"""The share of the held experts' hidden units that ReLU leaves above zero
under un-gated ReLU^2, median over the steady steps: `moe_active_units` /
`moe_units_held` of the program's `step_summary` records (counted inside the
gradient program over the rows that hold an assignment, all expert blocks).  A
kernel that skipped the dead units would do this share of the down projection's
work and of the up projection's gradient: near 0.5 at weights from the seed.
None where the program counts no such thing (a tree from before PR 56) or the
configuration is not of this family (`reglu_active_share` reads the gated one)."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    if ctx["config"].get("mlp_hidden_act") != "relu2":
        return None
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    shares = [s["moe_active_units"] / s["moe_units_held"] for s in summaries
              if s.get("moe_units_held") and "moe_active_units" in s]
    return stats.median(shares) if shares else None
