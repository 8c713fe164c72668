"""Device time per step of Gated DeltaNet's scan: the kernels that run the gated
delta rule with a decay a head (`kernel_names()["gdn"]` of the program: the
`tpuft_kda_*` kernels today, under a broadcast decay and repeated key heads) in
every Gated DeltaNet layer — the forward pass, the backward's forward pass that
makes the chunks' states again, and the backward — summed over the traced
steps' kernel events by name.  None where the program has no such kernel (a
tree from before PR 68, a model without such a layer)."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    seconds = ctx["trace"]["kernel_s_per_step"].get("gdn", 0.0)
    return seconds * 1e3 if seconds > 0 else None
