"""The share of the visible (query, key) pairs that attention kept, median
over the steady steps: `dsa_pairs_selected` / `dsa_pairs_visible` of the
program's `step_summary` records (the first counted inside the gradient
program from the masks the kernels read, over all layers; the second the causal
rule's count).  A query keeps min(position + 1, topk) keys, so at 32,768
positions and topk 2,048 it is 65,012,736 / 536,887,296 = 0.12109 exactly: any
other reading says the selection's count is off.  None where the program
counts no such thing."""

LAYER = "model"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    from benchmark import stats
    from benchmark.spec import reader_beside

    summaries = reader_beside(__file__, "moe_load_max_over_mean").steady_summaries(ctx)
    shares = [s["dsa_pairs_selected"] / s["dsa_pairs_visible"] for s in summaries if s.get("dsa_pairs_visible")]
    return stats.median(shares) if shares else None
