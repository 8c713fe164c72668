#!/usr/bin/env python3
"""The co-located groups' turns at the device-to-host fetch, from a run's streams.

    python3 tools/lease_turns.py <run_dir> [--step N] [--json]

`<run_dir>` holds one metrics stream a group (`g<i>.metrics.jsonl`: a
four-group run of the benchmark leaves them in
`benchmark/out/<cell>.<seed>[.trace].run`).  All groups of a host stamp their
sub-spans on the same monotonic clock, so their fetches can be laid side by
side.  For one step (the last whole one, or `--step`), one row a position of
the fetch order: the bucket and its bytes, the order in which the groups'
`d2h_fetch` began, each group's `d2h_lease_wait` and `d2h_fetch` in ms, when
the last group had landed the bucket and when each group's ring op began (ms
after the step's first fetch).  Then the host's view: the most `d2h_fetch`
sub-spans in flight at once, the time at least one was, the bytes a second
while one was, and whether the turns came in the order the groups asked
(`d2h_lease_wait` began).  No cell runs it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import program_spans as ps  # noqa: E402
from benchmark.trace_reduce import length, union  # noqa: E402


def streams(run_dir: str) -> dict:
    """{group: the benchmark's reading of its stream (`spans`, `subs`, `summaries`)}"""
    return {
        int(os.path.basename(path).split(".")[0][1:]): ps.stream(path)
        for path in sorted(glob.glob(os.path.join(run_dir, "g*.metrics.jsonl")))
    }


def most_at_once(intervals) -> int:
    edges = sorted([(a, 1) for a, _b in intervals] + [(b, -1) for _a, b in intervals])
    most = now = 0
    for _t, d in edges:
        now += d
        most = max(most, now)
    return most


def step_table(data: dict, step: int) -> dict:
    of = lambda g, name: sorted(  # noqa: E731
        (s for s in data[g]["subs"] if s["name"] == name and s["step"] == step), key=lambda s: s["t0_ns"])
    fetch_spans = {
        g: sorted((r for r in data[g]["spans"] if r["phase"] == "allreduce_d2h" and r["step"] == step and "pos" in r),
                  key=lambda r: r["pos"])
        for g in data
    }
    fetches = {g: of(g, "d2h_fetch") for g in data}
    t0 = min(s["t0_ns"] for subs in fetches.values() for s in subs)
    ms = lambda ns: round((ns - t0) / 1e6, 1)  # noqa: E731
    rows = []
    for pos in range(max(len(v) for v in fetch_spans.values())):
        per_group = {}
        for g in data:
            span = fetch_spans[g][pos]
            k = span["bucket"]
            mine = lambda name: [s for s in of(g, name) if s.get("bucket") == k]  # noqa: E731
            dur = lambda name: round(sum(s["t1_ns"] - s["t0_ns"] for s in mine(name)) / 1e6, 1)  # noqa: E731
            run = mine("ring_run")
            per_group[g] = {
                "fetch_at": ms(min(s["t0_ns"] for s in mine("d2h_fetch"))),
                "lease_wait": dur("d2h_lease_wait"), "fetch": dur("d2h_fetch"),
                "landed": ms(span["t1_ns"]),
                "ring_at": ms(run[0]["t0_ns"]) if run else None,
            }
        rows.append({
            "pos": pos, "bucket": fetch_spans[0][pos]["bucket"], "MB": round(fetch_spans[0][pos]["bytes"] / 1e6, 1),
            "order": sorted(per_group, key=lambda g: per_group[g]["fetch_at"]),
            "last_landed": max(v["landed"] for v in per_group.values()), "groups": per_group,
        })
    every = [(g, s) for g, subs in fetches.items() for s in subs]
    flights = [(s["t0_ns"], s["t1_ns"]) for _g, s in every]
    most, busy = most_at_once(flights), length(union(flights))
    moved = sum(s["bytes"] for _g, s in every)
    # First come, first served: the fetches begin in the order their waits did.
    waits = sorted(((s["t0_ns"], g, s.get("bucket")) for g in data for s in of(g, "d2h_lease_wait")))
    began = sorted(((s["t0_ns"], g, s.get("bucket")) for g, s in every))
    out_of_turn = sum(a[1:] != b[1:] for a, b in zip(waits, began)) if len(waits) == len(began) else None
    stream = {g: next((r.get("exchange_stream") for r in data[g]["summaries"] if r["step"] == step), None) for g in data}
    return {
        "step": step, "rows": rows,
        "host": {
            "fetches": len(every), "most_in_flight": most, "fetch_busy_ms": round(busy / 1e6, 1),
            "gb_per_s_while_fetching": round(moved / busy, 3) if busy else None,
            "gb_per_s_a_fetch": round(moved / sum(s["t1_ns"] - s["t0_ns"] for _g, s in every), 3),
            "lease_waits": len(waits), "out_of_turn": out_of_turn,
            "contended": sum(bool(s.get("contended")) for g in data for s in of(g, "d2h_lease_wait")),
        },
        "exchange_stream": stream,
    }


def render(table: dict) -> str:
    groups = sorted(table["rows"][0]["groups"])
    head = f"step {table['step']}   (ms after the step's first d2h_fetch; per group g: lease wait / fetch / ring op began)"
    lines = [head, "  pos bucket      MB  order     last_landed  " + "  ".join(f"g{g}: wait/fetch/ring_at".rjust(26) for g in groups)]
    for r in table["rows"]:
        cells = "  ".join(
            f"{v['lease_wait']:>8.1f}/{v['fetch']:>7.1f}/{v['ring_at'] if v['ring_at'] is not None else '-':>8}"
            for v in (r["groups"][g] for g in groups))
        lines.append(f"  {r['pos']:>3} {r['bucket']:>6} {r['MB']:>7.1f}  {''.join(map(str, r['order'])):<8} {r['last_landed']:>12.1f}  {cells}")
    lines.append("  host: " + "  ".join(f"{k}={v}" for k, v in table["host"].items()))
    lines.append("  exchange_stream: " + json.dumps(table["exchange_stream"]))
    return "\n".join(lines)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("run_dir")
    parser.add_argument("--step", type=int)
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args()
    data = streams(args.run_dir)
    if not data or not all(d["subs"] for d in data.values()):
        print(f"{args.run_dir}: no group's stream holds sub-spans", file=sys.stderr)
        return 1
    whole = set.intersection(*({r["step"] for r in d["summaries"]} for d in data.values()))
    whole = {s for s in whole if all(any(x["name"] == "d2h_fetch" and x["step"] == s for x in d["subs"]) for d in data.values())}
    step = args.step if args.step is not None else max(whole)
    table = step_table(data, step)
    print(json.dumps(table) if args.json else render(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
