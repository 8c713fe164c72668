"""Model FLOP/s utilisation of the traced group: the benchmark's own count of
matmul and causal-attention operations per token (`flops/<architecture>.py`,
no embedding gather, no recomputation) times the group's tokens per second over
the steps outside the profiler's capture, over the chip's bf16 peak."""

LAYER = "model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def read(ctx):
    steps = [s for s in ctx["steady_steps"] if s["committed"]]
    seconds = sum(s["ms"] for s in ctx["steady_steps"]) / 1e3
    if not steps or seconds <= 0 or ctx["peaks"] is None:
        return None
    flops = ctx["bench"].flops(ctx["config"]["architecture"])
    per_token = flops.train_flops_per_token(ctx["config"], ctx["traffic"]["seq_len"])
    tokens_per_s = len(steps) * ctx["tokens_per_step"] / seconds
    return 100.0 * per_token * tokens_per_s / ctx["peaks"]["bf16_flops_per_s"]
