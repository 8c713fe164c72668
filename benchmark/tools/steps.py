#!/usr/bin/env python3
"""Reads per-step dumps: which steps of a run were slow, and which span grew in them.

    python3 benchmark/tools/steps.py <steps.jsonl> [<steps.jsonl> ...]

For each file: the count, median, p90 (nearest rank) and maximum of the step
times, and every step more than `--over` ms above the median with its spans
beside the run's median of each span.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("files", nargs="+")
    parser.add_argument("--over", type=float, default=1.0)
    args = parser.parse_args()
    for path in args.files:
        with open(path, encoding="utf-8") as f:
            steps = [json.loads(l) for l in f]
        ms = [s["ms"] for s in steps]
        med = stats.median(ms)
        phases = sorted({p for s in steps for p in s.get("spans", {})})
        span_med = {p: stats.median([s["spans"].get(p, 0.0) for s in steps]) for p in phases}
        slow = [s for s in steps if s["ms"] > med + args.over]
        print(json.dumps({
            "file": os.path.basename(path), "steps": len(ms), "median_ms": med,
            "p90_ms": stats.percentile_nearest_rank(ms, 90), "max_ms": max(ms), "min_ms": min(ms),
            "over_median_by": args.over, "slow_steps": len(slow), "slow_share": len(slow) / len(ms),
            "span_medians_ms": span_med,
        }))
        for s in slow:
            grew = {p: round(s["spans"].get(p, 0.0) - span_med[p], 3) for p in phases
                    if s["spans"].get(p, 0.0) - span_med[p] > 0.2}
            print(json.dumps({"i": s["i"], "ms": round(s["ms"], 3), "over_ms": round(s["ms"] - med, 3),
                              "spans_grew_ms": grew, "host_ms": s.get("host_ms")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
