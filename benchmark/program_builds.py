"""What the program records of its own start-up, read once per run.

Beside the spans and sub-spans (`program_spans.py`), the program's metrics
stream holds since PR 70:

- `program_build` records (`torchft_tpu/obs/builds.py`): one for each stage —
  `trace`, `lower`, `backend` — of each program JAX built in the process, from
  JAX's own events, with `t0_ns` / `t1_ns` on the monotonic clock of the spans,
  the program's name where `TrainStep` knows it (`program`:
  `jit_value_and_grad`, `jit_apply`), the outermost stage it fell in (`outer`)
  and, on a `backend` stage, what the persistent cache did (`cache`: `hit`,
  `miss` or `off`);
- a `manager_start` sub-span: `Manager.__init__` from its first line to its return.

A set-up is everything before the window opens, so the readers take the
records that ENDED before the first window step started.  Stages nest (a
kernel's own `jax.jit` traced inside the gradient program's trace, a constant
computed eagerly at trace time, which is a whole small build) and threads
overlap, so every time here is the length of a UNION of intervals: seconds of
wall clock during which some build of the kind was under way.  The four
times of the train step add up to the union of all builds before the window.

The recorder starts listening when the program's first `TrainStep` (or
`Manager`) is constructed.  What the job builds before that — `steady.py`
makes the reference's weights first — is outside the program and not here.

A reader gives None where there is nothing to read: a stream without
`program_build` records (the parent of the PR that added them) gives None for
every metric, not 0.
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any, Dict, Iterable, List, Optional

from benchmark import program_spans
from benchmark.program_spans import GRAD_PROGRAM, STREAM_ENV, UPDATE_PROGRAM
from benchmark.trace_reduce import length, union


@functools.lru_cache(maxsize=4)
def builds(path: str) -> List[Dict[str, Any]]:
    """The stream's `program_build` records, in its order."""
    out: List[Dict[str, Any]] = []
    try:
        with open(path, encoding="utf-8") as f:
            lines = f.readlines()
    except OSError:
        lines = []
    for line in lines:
        if '"program_build"' not in line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if rec.get("event") == "program_build":
            out.append(rec)
    return out


def window_opens_ns(ctx: Dict[str, Any]) -> Optional[float]:
    return ctx["steps"][0]["start_mono_ns"] if ctx.get("steps") else None


def before_window(ctx: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The builds' stages that ended before the window opened; None where the
    stream holds no `program_build` record at all, or the window no step."""
    records, opens = builds(os.environ.get(STREAM_ENV, "")), window_opens_ns(ctx)
    if not records or opens is None:
        return None
    return [r for r in records if r["t1_ns"] <= opens]


def first_build(records: Iterable[Dict[str, Any]], program: str) -> List[Dict[str, Any]]:
    """The stages tagged `program` up to its first `backend` stage: the build
    that made the program, not one that came again under other arguments."""
    out = []
    for r in records:
        if r.get("program") == program:
            out.append(r)
            if r["stage"] == "backend":
                break
    return out


def seconds(records: Iterable[Dict[str, Any]], stages: Iterable[str] = ("trace", "lower", "backend")) -> float:
    """Wall-clock seconds during which some of the `records`' `stages` ran."""
    return length(union((r["t0_ns"], r["t1_ns"]) for r in records if r["stage"] in stages)) / 1e9


def _counted(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """What the gradient program's and the update program's metrics count."""
    return first_build(records, GRAD_PROGRAM) + [r for r in records if r.get("program") == UPDATE_PROGRAM]


def grad_trace_lower_s(ctx: Dict[str, Any]) -> Optional[float]:
    grad = first_build(before_window(ctx) or [], GRAD_PROGRAM)
    return seconds(grad, ("trace", "lower")) if grad else None


def grad_load_s(ctx: Dict[str, Any]) -> Optional[float]:
    backend = [r for r in first_build(before_window(ctx) or [], GRAD_PROGRAM) if r["stage"] == "backend"]
    return seconds(backend) if backend else None


def update_build_s(ctx: Dict[str, Any]) -> Optional[float]:
    update = [r for r in before_window(ctx) or [] if r.get("program") == UPDATE_PROGRAM]
    return seconds(update) if update else None


def other_builds_s(ctx: Dict[str, Any]) -> Optional[float]:
    """The union of every build before the window less the part the three
    metrics above count (which lies inside it)."""
    records = before_window(ctx)
    return None if records is None else seconds(records) - seconds(_counted(records))


def backend_stages(ctx: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """One a program built before the window: a trace alone (a function
    inlined into its caller's program) is no build."""
    records = before_window(ctx)
    return None if records is None else [r for r in records if r["stage"] == "backend"]


def manager_start_s(ctx: Dict[str, Any]) -> Optional[float]:
    opens = window_opens_ns(ctx)
    starts = [s for s in program_spans.of_run()["subs"] if s["name"] == "manager_start"]
    if not starts or opens is None or starts[0]["t1_ns"] > opens:
        return None
    return (starts[0]["t1_ns"] - starts[0]["t0_ns"]) / 1e9
