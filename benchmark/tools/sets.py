#!/usr/bin/env python3
"""Runs a cell on a list of seeds, one new process each, and says how the runs spread.

    python3 benchmark/tools/sets.py --workload <cell> --seeds 11,12,13 [--seconds S] [--trace 0|1] [--label L]

Each run is `BENCHMARK.json`'s command as the driver gives it.  Every result
line goes to `chiprun_out/bench/<label>.jsonl` with the run's earlier lines,
and the per-step dumps are copied beside it (the chip tool brings back only
`chiprun_out/`).  The summary gives, per metric, the median and the spread as
the contract defines it.  Exit code 1 where a run failed or was not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--label")
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    seconds = args.seconds if args.seconds is not None else doc["run_seconds"]
    label = args.label or f"{args.workload}.t{args.trace}"
    out_dir = os.path.join(ROOT, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    lines, bad = [], 0
    with open(os.path.join(out_dir, label + ".jsonl"), "a", encoding="utf-8") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            cmd = [*doc["command"], "--workload", args.workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(args.trace)]
            t0 = time.time()
            proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            wall = time.time() - t0
            out = proc.stdout.strip().splitlines()
            record = {"seed": seed, "rc": proc.returncode, "wall_s": wall, "earlier": out[:-1]}
            try:
                record["result"] = json.loads(out[-1]) if out else None
            except ValueError:
                record["result"] = None
            if proc.returncode != 0 or not record["result"] or not record["result"].get("correct"):
                bad += 1
                record["stderr_tail"] = proc.stderr[-6000:]
                print(f"seed {seed}: rc {proc.returncode}\n{proc.stderr[-3000:]}", flush=True)
            log.write(json.dumps(record) + "\n")
            log.flush()
            if record["result"]:
                lines.append(record["result"])
                m = {k: v["value"] for k, v in record["result"]["metrics"].items()}
                print(json.dumps({"seed": seed, "wall_s": round(wall, 1), "correct": record["result"]["correct"],
                                  "attempted": record["result"]["attempted"], **m}), flush=True)
            tag = f"{args.workload}.{seed}" + (".trace" if args.trace else "")
            src = os.path.join(ROOT, doc["paths"][0], "out")
            steps = os.path.join(src, tag + ".steps.jsonl")
            if os.path.exists(steps):
                shutil.copy(steps, os.path.join(out_dir, f"{label}.{tag}.steps.jsonl"))
            events = os.path.join(src, tag + ".run", "trace_events.json")
            if os.path.exists(events) and os.path.getsize(events) < 30e6:
                shutil.copy(events, os.path.join(out_dir, f"{label}.{seed}.trace_events.json"))
    summary = {}
    for name in (lines[0]["metrics"] if lines else {}):
        values = [l["metrics"][name]["value"] for l in lines if name in l["metrics"]]
        summary[name] = {"n": len(values), "median": stats.median(values), "min": min(values), "max": max(values)}
        if len(values) >= 3:
            summary[name]["spread"] = stats.spread(values)
        if len(values) >= 4:
            summary[name]["spread_without_farthest"] = stats.spread_without_farthest(values)
        if len(values) > 1:
            summary[name]["after_first"] = {"median": stats.median(values[1:])}
    print(json.dumps({"label": label, "runs": len(lines), "bad": bad, "summary": summary}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
