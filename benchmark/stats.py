"""The benchmark's arithmetic on step times: no JAX, no clock."""

from __future__ import annotations

import math
import statistics
from typing import List, Sequence


def percentile_nearest_rank(values: Sequence[float], p: float) -> float:
    """The smallest value with at least p% of the sample at or below it
    (nearest rank: ceil(p/100 * n), 1-indexed).  No interpolation: the
    answer is a step that happened."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    ordered = sorted(values)
    return ordered[max(1, math.ceil(p / 100.0 * len(ordered))) - 1]


def median(values: Sequence[float]) -> float:
    return statistics.median(values)


def whole_steps(step_ends_s: Sequence[float], seconds: float) -> int:
    """How many steps the window holds.  `step_ends_s` are the ends of the
    back-to-back steps, in seconds after the window opened; the window closes
    at the last end at or before `seconds`, so no step counts in part."""
    n = 0
    for end in step_ends_s:
        if end > seconds:
            break
        n += 1
    return n


def may_start(elapsed_s: float, longest_step_s: float, seconds: float, margin: float = 1.05) -> bool:
    """Whether another step can be expected to end inside the window: one
    that would end after it is not counted and need not be started."""
    return elapsed_s + margin * longest_step_s <= seconds


def span_median(steps: Sequence[dict], phase: str):
    """Median over the steps of one span's time in a step; None where no
    step had the span."""
    values = [s["spans"][phase] for s in steps if phase in s["spans"]]
    return statistics.median(values) if values else None


def roofline_percent(need: dict, peaks: dict, seconds: float) -> float:
    """The least time the chip could take for `need` (the larger of its
    operations over the bf16 peak and its bytes over the HBM peak) as a share
    of the time taken."""
    least = max(need["flops"] / peaks["bf16_flops_per_s"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by `statistics.quantiles(values, n=4)` as the contract has it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def spread_without_farthest(values: Sequence[float]) -> float:
    """`spread` with the run farthest from the median left out (what the
    driver does to each set when it judges tightness)."""
    m = statistics.median(values)
    kept: List[float] = sorted(values, key=lambda v: abs(v - m))[:-1]
    return spread(kept)
