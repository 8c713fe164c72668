#!/usr/bin/env python3
"""A run's exchange, bucket by bucket: where each bucket's time went.

    python3 benchmark/tools/buckets.py <run_dir> [--group 0] [--step N ...] [--json]

`<run_dir>` is a run's directory (`benchmark/out/<cell>.<seed>[.trace].run`).
From group `--group`'s metrics stream, for each of the program's steps (all, or
those named by `--step`), one row a bucket: its bytes; when its fetch started
after the `ft_step` frame opened; the fetch span taken apart (ready: waiting
for the gradient program, fetch: `np.asarray`, copy: into the flat buffer,
hand-off: the rest); the ring op's queue and run with its start; `normalize`;
the bucket's way back (`h2d_put`).  Then the step's sums, how long the ring ran
beside the fetches, and what the frame holds.  All in ms on the program's own
monotonic clock.  With the traced group's profile beside the stream, one more
line says how far the `tpuft:` annotations lie from the stream's timestamps
moved by the harness's measured clock offset.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program_spans as ps  # noqa: E402
from benchmark.trace_reduce import length, union  # noqa: E402

COLUMNS = ("bucket", "MB", "d2h_at", "d2h", "ready", "fetch", "copy", "handoff", "GB/s",
           "ring_at", "queue", "run", "normalize", "h2d_put")


def step_tables(data):
    """{program step: {"rows": [...], "sums": {...}}} of one stream."""
    steps = sorted({s["step"] for s in data["subs"] if "bucket" in s})
    out = {}
    for step in steps:
        subs = [s for s in data["subs"] if s["step"] == step]
        spans = [s for s in data["spans"] if s["step"] == step]
        frames = [s for s in subs if s["name"] == "ft_step"]
        t0 = frames[0]["t0_ns"] if frames else min(s["t0_ns"] for s in subs)
        rows = []
        for k in sorted({s["bucket"] for s in subs if "bucket" in s}):
            mine = [s for s in subs if s.get("bucket") == k]
            fetch_spans = [s for s in spans if s["phase"] == "allreduce_d2h" and s.get("bucket") == k]
            ms = lambda name: ps.total_ms(mine, name)  # noqa: E731
            d2h = ps.total_ms(fetch_spans, "allreduce_d2h")
            runs = ps.intervals(mine, "ring_run")
            nbytes = max((s.get("bytes", 0) for s in mine), default=0)
            rows.append({
                "bucket": k, "MB": nbytes / 1e6,
                "d2h_at": (min(s["t0_ns"] for s in fetch_spans) - t0) / 1e6 if fetch_spans else None,
                "d2h": d2h, "ready": ms("d2h_ready"), "fetch": ms("d2h_fetch"), "copy": ms("d2h_copy"),
                "handoff": d2h - ms("d2h_ready") - ms("d2h_fetch") - ms("d2h_copy") if fetch_spans else None,
                "GB/s": nbytes / 1e6 / ms("d2h_fetch") if ms("d2h_fetch") > 0 else None,
                "ring_at": (min(a for a, _ in runs) - t0) / 1e6 if runs else None,
                "queue": ms("ring_queue"), "run": ms("ring_run"), "normalize": ms("normalize"),
                "h2d_put": ms("h2d_put"),
            })
        fetches = union(ps.intervals(spans, "allreduce_d2h"))
        ring = union(ps.intervals(subs, "ring_run"))
        both = length(union(fetches + ring))
        out[step] = {"rows": rows, "sums": {
            "ft_step": ps.total_ms(subs, "ft_step"),
            "allreduce_d2h": ps.total_ms(spans, "allreduce_d2h"),
            "allreduce_merge": ps.total_ms(spans, "allreduce_merge"),
            "allreduce_h2d": ps.total_ms(spans, "allreduce_h2d"),
            "commit_vote": ps.total_ms(spans, "commit_vote"),
            "quorum_wait": ps.total_ms(subs, "quorum_wait"),
            "ft_step_self": ps.ft_step_self_ms(subs, spans),
            "ring_busy": length(ring) / 1e6,
            "ring_busy_during_d2h": (length(fetches) + length(ring) - both) / 1e6,
            "h2d_put_final": ps.total_ms([s for s in subs if "bucket" not in s], "h2d_put"),
        }}
    return out


def render(step, table) -> str:
    cell = lambda v: "-" if v is None else (str(v) if isinstance(v, int) else f"{v:.2f}")  # noqa: E731
    lines = [f"step {step}", "  " + " ".join(f"{c:>9}" for c in COLUMNS)]
    lines += ["  " + " ".join(f"{cell(r[c]):>9}" for c in COLUMNS) for r in table["rows"]]
    lines.append("  " + "  ".join(f"{k}={cell(v)}" for k, v in table["sums"].items()))
    return "\n".join(lines)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("run_dir")
    parser.add_argument("--group", type=int, default=0)
    parser.add_argument("--step", type=int, action="append")
    parser.add_argument("--json", action="store_true", help="one JSON object a step, not a table")
    args = parser.parse_args()
    stream_path = os.path.join(args.run_dir, f"g{args.group}.metrics.jsonl")
    data = ps.stream(stream_path)
    if not data["subs"]:
        print(f"{stream_path} holds no sub-span: nothing to take apart", file=sys.stderr)
        return 1
    for step, table in step_tables(data).items():
        if args.step and step not in args.step:
            continue
        print(json.dumps({"step": step, **table}) if args.json else render(step, table))
    trace_file = ps.trace_path(stream_path) if args.group == 0 else None
    if trace_file:
        print(json.dumps(ps.clock_agreement(ps.trace(trace_file), data)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
