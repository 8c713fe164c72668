"""Device time of the gradient program by part of the model and by direction.

The trace names a device operation by its HLO instruction (`fusion.87`), a
number.  The program knows what each instruction came from: `TrainStep.op_map`
reads, from the compiled executable's own text, {instruction: op_name}, and the
op_name path holds the model's `jax.named_scope` (`torchft_tpu/obs/spans.PARTS`)
and JAX's transforms — `jvp(ffn)` forward, `transpose(jvp(ffn))` backward,
`checkpoint/rematted_computation/ffn` computed again.  Joined here:

- an `XLA Ops` event belongs to the program whose `XLA Modules` execution
  contains it (`fusion.3` exists in the gradient AND the update program);
- its time is its self time (`trace_reduce.self_times`: a `while` or a `call`
  counted without what it encloses), summed per instruction and execution;
- the instruction is booked to (part, direction) by `obs.opmap.booked`: by its
  own op_name — a fusion that straddles parts goes whole to the part XLA named
  it after — a fusion without one by what is fused into it, and what the
  compiler made with no name anywhere (a copy into another layout, a convert it
  moved, an asynchronous copy's two halves) by the nearest instruction that
  reads its result; what is not in the map or still has no part is
  `unattributed`;
- a number is the median over the program's executions that start in the
  counted traced steps (after `trace_skip_steps`), in ms: each step runs the
  program once, so it is ms a step.

The TPU's xplane carries no op_name per event to cross-check with (looked at on
the chip, PR 35: an `XLA Ops` event's stats are `device_offset_ps`,
`device_duration_ps` and `Time Scale Multiplier`, no `tf_op` or `hlo_op`;
`tools/parts.py --event-stats` prints them), so the map is the one source.

`of_run(ctx)` does this once a run, for the readers under `layer_metrics/`,
and leaves the whole table, each instruction's time and the map in
`<run_dir>/g0.device_parts.json`; `tools/parts.py` prints it.  It gives None —
and each reader then nothing — where the program has no op map (the parent of
the PR that added it), no `TrainStep` of this process ran, or the trace has no
device plane (a CPU rehearsal).
"""

from __future__ import annotations

import bisect
import json
import os
from typing import Any, Dict, List, Optional, Sequence

from benchmark import program_spans, stats
from benchmark.trace_reduce import self_times

UNATTRIBUTED = "unattributed"
FILE = "g0.device_parts.json"

_RUNS: Dict[str, Optional[Dict[str, Any]]] = {}


def attribute(ops: Dict[str, Sequence[Sequence[Any]]], modules: Dict[str, Sequence[Sequence[Any]]],
              op_map: Dict[str, Dict[str, Any]], lo: float = float("-inf")) -> Dict[str, Any]:
    """`ops` {plane: [[instruction, start_ns, dur_ns], ...]} (the `XLA Ops`
    events), `modules` {plane: [[program, start_ns, dur_ns], ...]} (the `XLA
    Modules` executions), `op_map` {program: {instruction: op_name, or the
    detailed entry}}.  Per program of the map with an execution starting at or
    after `lo`: {"executions", "program_ms" (an execution, start to end),
    "device_ms" (its operations' self times), "table" {part: {direction: ms}},
    "by_direction", "by_part", "unattributed_ms", "per_execution" [{"<part>/
    <direction>" or "unattributed": ms}], "instructions" {name: {"ms", "part",
    "direction", "op_name", "opcode", "by" (what decided the part: "name",
    "inside", "near"), "straddles" (the parts fused into it, where more than
    one)}}} — each number but `per_execution`'s the median over the executions."""
    from torchft_tpu.obs import opmap

    runs: Dict[str, List[Dict[str, Any]]] = {}
    for plane, executions in modules.items():
        events = sorted(ops.get(plane, ()), key=lambda e: e[1])
        starts = [e[1] for e in events]
        for program, start, dur in executions:
            if start < lo or program not in op_map:
                continue
            inside = events[bisect.bisect_left(starts, start):bisect.bisect_left(starts, start + dur)]
            runs.setdefault(program, []).append({"ms": dur / 1e6, "self_s": self_times(inside)})
    out: Dict[str, Any] = {}
    for program, executions in runs.items():
        entries = op_map[program]
        ran = {name for execution in executions for name in execution["self_s"]}
        booked = {name: opmap.booked(entries[name]) if name in entries else (None, "fwd") for name in ran}
        cells: List[Dict[str, float]] = []  # per execution: {"<part>/<direction>" or "unattributed": ms}
        for execution in executions:
            cell: Dict[str, float] = {}
            for name, seconds in execution["self_s"].items():
                part, direction = booked[name]
                key = f"{part}/{direction}" if part is not None else UNATTRIBUTED
                cell[key] = cell.get(key, 0.0) + seconds * 1e3
            cells.append(cell)
        named = sorted({tuple(k.split("/")) for cell in cells for k in cell if k != UNATTRIBUTED})
        table: Dict[str, Dict[str, float]] = {}
        for part, direction in named:
            table.setdefault(part, {})[direction] = median_ms(cells, parts=(part,), direction=direction)
        instructions = {}
        for name, (part, direction) in booked.items():
            entry = entries.get(name)
            detail = entry if isinstance(entry, dict) else {"op_name": entry or ""}
            inside = {opmap.part_of(path + "/") for path in detail.get("inside", {})} - {None}
            instructions[name] = {
                "ms": stats.median([e["self_s"].get(name, 0.0) * 1e3 for e in executions]),
                "part": part or UNATTRIBUTED, "direction": direction,
                "op_name": detail["op_name"], "opcode": detail.get("opcode"),
                # what decided the part: the instruction's own op_name, those fused into it, or its nearest reader
                "by": None if part is None else "name" if opmap.part_of(detail["op_name"]) else "inside" if inside else "near",
            }
            if len(inside) > 1:
                instructions[name]["straddles"] = sorted(inside)
        out[program] = {
            "executions": len(executions),
            "program_ms": stats.median([e["ms"] for e in executions]),
            "device_ms": stats.median([sum(cell.values()) for cell in cells]),
            "table": table,
            "by_direction": {d: median_ms(cells, direction=d) for d in sorted({d for _, d in named})},
            "by_part": {p: median_ms(cells, parts=(p,)) for p in sorted({p for p, _ in named})},
            "unattributed_ms": median_ms(cells, parts=(UNATTRIBUTED,)),
            "per_execution": cells,
            "instructions": instructions,
        }
    return out


def median_ms(cells: Sequence[Dict[str, float]], *, parts: Sequence[str] = (), direction: Optional[str] = None) -> float:
    """Median over the executions of the time booked to `parts` (all, if
    none is named; "unattributed" is one) in `direction` (all, if None)."""
    def wanted(key: str) -> bool:
        part, _, way = key.partition("/")
        if key == UNATTRIBUTED:
            return UNATTRIBUTED in parts
        return (not parts or part in parts) and (direction is None or way == direction)

    return stats.median([sum(v for k, v in cell.items() if wanted(k)) for cell in cells])


def _live_op_map() -> Optional[Dict[str, Dict[str, Any]]]:
    """The detailed op map of this process's `TrainStep` that ran last, or
    None where the program has none."""
    try:
        from torchft_tpu.obs import opmap
    except ImportError:
        return None
    for step in reversed(opmap.train_steps()):
        found = step.op_map(detail=True)
        if found:
            return found
    return None


def of_run(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The run's attribution (`attribute`'s result, with "op_map" beside it),
    computed and written once; None where there is nothing to read."""
    path = program_spans.trace_path()
    if path is None:
        return None
    if path not in _RUNS:
        _RUNS[path] = _read(ctx, path)
    return _RUNS[path]


def _read(ctx: Dict[str, Any], path: str) -> Optional[Dict[str, Any]]:
    loaded = program_spans.trace(path)
    skip = int(ctx["traffic"].get("trace_skip_steps", 0))
    if not loaded["modules"] or len(loaded["steps"]) <= skip:
        return None
    run_dir = os.path.dirname(os.environ.get(program_spans.STREAM_ENV, ""))
    try:
        with open(os.path.join(run_dir, "trace_events.json"), encoding="utf-8") as f:
            ops = json.load(f)["devices"]
    except (OSError, ValueError, KeyError):
        return None
    op_map = _live_op_map()
    if not op_map:
        return None
    found = attribute(ops, loaded["modules"], op_map, lo=loaded["steps"][skip][0])
    if program_spans.GRAD_PROGRAM not in found:
        return None
    result = {"cell": ctx["cell"]["name"], "programs": found, "op_map": op_map}
    tmp = os.path.join(run_dir, FILE + ".tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(result, f)
    os.replace(tmp, os.path.join(run_dir, FILE))
    return result


def grad_ms(ctx: Dict[str, Any], *, direction: Optional[str] = None, parts: Sequence[str] = (),
            unattributed: bool = False) -> Optional[float]:
    """Of the gradient program, ms a step: one direction's time over all parts,
    some parts' time over all directions, or what no part claims.  None where
    the run has no attribution, or the program nothing of the kind asked for
    (no rematerialised layer, no dense feed-forward): the cell then does not
    list the metric."""
    found = of_run(ctx)
    if found is None:
        return None
    program = found["programs"][program_spans.GRAD_PROGRAM]
    if unattributed:
        return program["unattributed_ms"]
    if direction is not None:
        return median_ms(program["per_execution"], direction=direction) if direction in program["by_direction"] else None
    held = [p for p in parts if p in program["by_part"]]
    return median_ms(program["per_execution"], parts=held) if held else None
