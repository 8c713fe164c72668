#!/usr/bin/env python3
"""A traced run's gradient program, part by part: where its device time went.

    python3 benchmark/tools/parts.py <run_dir> [--program jit_value_and_grad] [--top 10] [--json]
    python3 benchmark/tools/parts.py --xplane <file.xplane.pb> --op-map <op_map.json> [...]
    python3 benchmark/tools/parts.py --xplane <file.xplane.pb> --event-stats

`<run_dir>` is a traced run's directory (`benchmark/out/<cell>.<seed>.trace.run`),
which holds `g0.device_parts.json` (`benchmark/device_parts.py`).  Prints, in ms
a step (medians over the program's executions in the counted traced steps): the
part x direction table — forward, backward, computed again under
`jax.checkpoint` — with what no part claims and the program's whole time, then
each part's longest instructions: time, the instruction as the trace names it,
its opcode, the parts fused into it where more than one, and the op_name that
says what it is.  `--program jit_apply` shows the update program (no part of the
model: everything there is unattributed).

The second form is for any `jax.profiler` trace of a job: `<op_map.json>` holds
what `TrainStep.op_map(detail=True)` returned in that job (asked after the
traced steps, written with `json.dump`); every execution in the trace counts.
The third prints what the profiler itself attaches to a device event (the stats
of the first `XLA Ops` and `XLA Modules` events): whether a trace names an
operation's origin without the program's map.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import device_parts  # noqa: E402

def load_xplane(path: str):
    """(`XLA Ops` events, `XLA Modules` executions) per device plane of an
    `.xplane.pb`, in `device_parts.attribute`'s form."""
    from jax.profiler import ProfileData

    from benchmark.program_spans import program_name
    from benchmark.trace_reduce import op_name

    ops, modules = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops.setdefault(plane.name, []).extend(
                    [op_name(e.name), float(e.start_ns), float(e.duration_ns)] for e in line.events)
            elif line.name == "XLA Modules":
                modules.setdefault(plane.name, []).extend(
                    [program_name(e.name), float(e.start_ns), float(e.duration_ns)] for e in line.events)
    return ops, modules


def event_stats(path: str, count: int = 3) -> str:
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines.append(f"{plane.name}: lines {[line.name for line in plane.lines]}")
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    for e in list(line.events)[:count]:
                        lines.append(f"  {line.name}: {e.name[:100]!r} {dict(e.stats)}")
    return "\n".join(lines)


def render(program: dict, top: int) -> str:
    from torchft_tpu.obs.opmap import DIRECTIONS, PARTS

    lines = [f"{'part':<14}" + "".join(f"{d:>11}" for d in DIRECTIONS) + f"{'all':>11}"]
    for part in [p for p in PARTS if p in program["table"]]:
        row = program["table"][part]
        lines.append(f"{part:<14}" + "".join(f"{row.get(d, 0.0):>11.3f}" for d in DIRECTIONS)
                     + f"{program['by_part'][part]:>11.3f}")
    by = program["by_direction"]
    named = sum(by.values())
    lines.append(f"{'all parts':<14}" + "".join(f"{by.get(d, 0.0):>11.3f}" for d in DIRECTIONS) + f"{named:>11.3f}")
    lines.append(f"{'unattributed':<14}{'':>33}{program['unattributed_ms']:>11.3f}")
    lines.append(f"{'device time':<14}{'':>33}{program['device_ms']:>11.3f}   "
                 f"(an execution start to end: {program['program_ms']:.3f}; {program['executions']} executions)")
    straddling = [i for i in program["instructions"].values() if i.get("straddles")]
    lines.append(f"fusions that hold more than one part: {len(straddling)}, "
                 f"{sum(i['ms'] for i in straddling):.3f} ms, each booked whole by its own op_name")
    for how, what in (("inside", "fusions without an op_name, booked by what is fused into them"),
                      ("near", "instructions without an op_name anywhere, booked by the nearest reader of their result")):
        those = [i for i in program["instructions"].values() if i.get("by") == how]
        lines.append(f"{what}: {len(those)}, {sum(i['ms'] for i in those):.3f} ms")
    def row(ms: float, name: str, inst: dict, part: str = "") -> str:
        also = f" [+{','.join(p for p in inst['straddles'] if p != inst['part'])}]" if inst.get("straddles") else ""
        how = {"inside": " (by what is fused into it)", "near": " (by its reader)"}.get(inst.get("by"), "")
        return (f"  {ms:>9.3f}  {name:<34} {inst['opcode'] or '?':<12} {part}{inst['direction']:<9} "
                f"{inst['op_name'] or '(no op_name)'}{also}{how}")

    ranked = sorted(((inst["ms"], name, inst) for name, inst in program["instructions"].items()), key=lambda r: -r[0])
    lines.append(f"\nthe program's {top} longest of {len(ranked)} instructions")
    lines += [row(ms, name, inst, f"{inst['part']:<14}") for ms, name, inst in ranked[:top]]
    for part in [p for p in (*PARTS, device_parts.UNATTRIBUTED) if any(i["part"] == p for _, _, i in ranked)]:
        mine = [r for r in ranked if r[2]["part"] == part]
        lines.append(f"\n{part}: the {top} longest of {len(mine)} instructions")
        lines += [row(*r) for r in mine[:top]]
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("run_dir", nargs="?")
    parser.add_argument("--xplane")
    parser.add_argument("--op-map")
    parser.add_argument("--program", default="jit_value_and_grad")
    parser.add_argument("--top", type=int, default=10)
    parser.add_argument("--json", action="store_true")
    parser.add_argument("--event-stats", action="store_true")
    args = parser.parse_args(argv)
    if args.xplane and args.event_stats:
        print(event_stats(args.xplane))
        return 0
    if args.xplane:
        with open(args.op_map, encoding="utf-8") as f:
            op_map = json.load(f)
        ops, modules = load_xplane(args.xplane)
        programs = device_parts.attribute(ops, modules, op_map)
    elif args.run_dir:
        with open(os.path.join(args.run_dir, device_parts.FILE), encoding="utf-8") as f:
            programs = json.load(f)["programs"]
    else:
        parser.error("a run's directory, or --xplane with --op-map")
    if args.program not in programs:
        print(f"no execution of {args.program!r}; the trace and the map share {sorted(programs)}", file=sys.stderr)
        return 1
    program = programs[args.program]
    print(json.dumps(program) if args.json else render(program, args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
