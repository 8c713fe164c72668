#!/usr/bin/env python3
"""Runs one cell of the benchmark once and prints its result.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process every time.  The cell's configuration, traffic mix and chips come
from `BENCHMARK.json`; the kind of job from the traffic file's `"job"`, which
names a file under `benchmark/jobs/`.  This process never touches JAX: the job
starts one process per chip.  Where JAX's first device there is not a TPU, or
its kind has no row in `benchmark/peaks.json`, the run fails and prints no
result; nothing falls back to the CPU.

Earlier lines say what was compared beside its limit, the sample counts and the
compile-cache hits and misses; the last line of standard output is the
contract's one JSON object.  `--trace 0` reports the cell's end-to-end metrics,
`--trace 1` its per-layer metrics from a run that profiles a few steps.
"""

from __future__ import annotations

import time

T0_WALL = time.time()  # the process's start, as near as Python gives it: set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark.spec import Benchmark

    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    traffic = bench.traffic(cell["traffic"])
    job = bench.job(traffic["job"])
    result = job.run(bench, cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace), t0_wall=T0_WALL)

    for name, check in result["checks"].items():
        print(json.dumps({"check": name, **check}))
    print(json.dumps({"samples": result["samples"], "compile_cache": result["cache"]}))
    if result["compiled_in_window"]:
        print(f"{result['compiled_in_window']} compilations inside the measured window: a shape was not "
              "warmed up — no result", file=sys.stderr)
        return 1
    wanted = bench.per_layer(cell["name"]) if args.trace else bench.end_to_end(cell["name"])
    source = result["per_layer"] if args.trace else result["end_to_end"]
    metrics = {m["name"]: {"value": source[m["name"]], "unit": m["unit"]} for m in wanted if m["name"] in source}
    if not args.trace and len(metrics) != len(wanted):
        print(f"the job gave no value for {[m['name'] for m in wanted if m['name'] not in source]}", file=sys.stderr)
        return 1
    line = {
        "correct": result["correct"], "attempted": result["attempted"], "failed": result["failed"],
        "metrics": metrics, "device": result["device"],
    }
    if args.trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
