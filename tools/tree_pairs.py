#!/usr/bin/env python3
"""Runs a benchmark cell from two (or more) trees in one call, in the order given, so that
a parent and a change are read on the same chip; one JSON line a run.

    git archive HEAD | tar -x -C parent_tree      # the parent, in a git-ignored directory of the repo
    chiprun --timeout 1500 -- python3 tools/tree_pairs.py --workload laguna-xs.2.steady-1g-16k \\
        --trees P=parent_tree C=. --runs P:11 C:11 C:12 P:12 C:13:t --label pairs_laguna

A run is `<tree>:<seed>[:t]` (`t`: `--trace 1`, and the tree's
`benchmark/tools/parts.py` table of the run kept beside its record, and its
`g0.device_parts.json` in `<nn>_<tree>.run/`, which `parts.py` reads again); each is
`BENCHMARK.json`'s command as the driver gives it, run from its tree's root
under that tree's own benchmark files.  A line holds the run's metrics, the
step's median, set-up phase by phase (seconds each phase TOOK, group 0's:
`setup_phases_s` holds when each ended), the allocator's peak after each
phase, the reference's `grad_rel` and the compile cache's hits and misses;
the whole record, per-layer metrics included, goes to
`chiprun_out/<label>/<nn>_<tree>.json`.  The parent tree builds its own native
library in its first run (about 15 s of that run's `imports` phase) unless
`torchft_tpu/_lib/` was copied into it.  No cell runs this; PERF.md's sections
2 and 6 cite its readings since PR 39 (step 0: `--seconds 3`).

Since PR 70 every run's group-0 stream and step dump are kept too
(`<nn>_<tree>.run/g0.metrics.jsonl`, `steps.jsonl`: the `program_build` records
and the `manager_start` sub-span are read from there), and `--fresh-cache`
gives the call's runs one new, empty compile cache: the first run is then a
checkout's first, the later ones warm from it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def took(phases: dict) -> dict:
    """`setup_phases_s` (seconds since the start at each phase's end) as seconds each phase took."""
    names, out, before = list(phases), {}, 0.0
    for name in names:
        out[name], before = round(phases[name] - before, 3), phases[name]
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--trees", nargs="+", required=True, help="NAME=directory (relative to the repo's root)")
    parser.add_argument("--runs", nargs="+", required=True, help="NAME:seed[:t]")
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--label", required=True)
    parser.add_argument("--fresh-cache", action="store_true", help="one new, empty compile cache for this call's runs")
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    seconds = args.seconds if args.seconds is not None else doc["run_seconds"]
    trees = {name: os.path.join(ROOT, path) for name, _, path in (t.partition("=") for t in args.trees)}
    out_dir = os.path.join(ROOT, "chiprun_out", args.label)
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    if args.fresh_cache:
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, "benchmark", "out", f"fresh_cache.{args.label}.{os.getpid()}")
    bad = 0
    for i, run in enumerate(args.runs):
        tree, seed, *traced = run.split(":")
        cmd = [*doc["command"], "--workload", args.workload, "--seed", seed, "--seconds", str(seconds), "--trace", str(int(bool(traced)))]
        t0 = time.time()
        proc = subprocess.run(cmd, cwd=trees[tree], env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        record = {"i": i, "tree": tree, "seed": int(seed), "traced": bool(traced), "rc": proc.returncode, "took_s": round(time.time() - t0, 1)}
        for text in proc.stdout.splitlines():
            try:
                line = json.loads(text)
            except ValueError:
                continue
            if "check" in line:
                record.setdefault("checks", {})[line.pop("check")] = line
            elif "samples" in line:
                record.update(samples=line["samples"], cache=line["compile_cache"])
            elif "metrics" in line:
                record["result"] = line
        result, samples = record.get("result") or {}, record.get("samples") or {}
        if proc.returncode != 0 or not result.get("correct"):
            bad += 1
            record["stderr_tail"] = proc.stderr[-6000:]
        stem = os.path.join(out_dir, f"{i:02d}_{tree}")
        tag = f"{args.workload}.{seed}" + (".trace" if traced else "")
        run_dir = os.path.join(trees[tree], "benchmark", "out", tag + ".run")
        os.makedirs(stem + ".run", exist_ok=True)
        for kept, name in ((os.path.join(run_dir, "g0.metrics.jsonl"), "g0.metrics.jsonl"),
                           (os.path.join(trees[tree], "benchmark", "out", tag + ".steps.jsonl"), "steps.jsonl")):
            if os.path.exists(kept):
                shutil.copy(kept, os.path.join(stem + ".run", name))
        if traced and proc.returncode == 0:
            parts = subprocess.run([sys.executable, "benchmark/tools/parts.py", run_dir], cwd=trees[tree], stdout=subprocess.PIPE,
                                   stderr=subprocess.STDOUT, text=True)
            with open(stem + ".parts.txt", "w", encoding="utf-8") as f:
                f.write(parts.stdout)
            every_instruction = os.path.join(run_dir, "g0.device_parts.json")  # for `parts.py <dir>` after the call
            if os.path.exists(every_instruction):
                shutil.copy(every_instruction, stem + ".run")
        with open(stem + ".json", "w", encoding="utf-8") as f:
            json.dump(record, f)
        reference = next(iter(record.get("checks", {}).values()), {})
        print(json.dumps({
            **{k: record[k] for k in ("i", "tree", "seed", "traced", "rc", "took_s")},
            "correct": result.get("correct"), "attempted": result.get("attempted"), "failed": result.get("failed"),
            "metrics": {k: v["value"] for k, v in (result.get("metrics") or {}).items()},
            "step_ms": samples.get("step_ms"), "phases_took_s": took(samples.get("setup_phases_s") or {}),
            "peak_after": samples.get("peak_bytes_after"), "grad_rel": reference.get("grad_rel"), "cache": record.get("cache"),
            **({"stderr_tail": proc.stderr[-1500:]} if "stderr_tail" in record else {}),
        }), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
