"""Median per traced step of the time inside the exchange's spans
(`allreduce_d2h` + `allreduce_merge` + `allreduce_h2d`) during which the device
trace shows no operation running.  With one group the averager returns before
any fetch and this is the control: about nothing."""

LAYER = "cross-group exchange"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    from benchmark import stats

    per_step = ctx["trace"]["exposed_exchange_s_per_step"]
    return stats.median(per_step) * 1e3 if per_step else None
