"""What the program records below its phases, read once per run.

Two sources, both written by the program itself and found beside each other
in a run's directory:

- its metrics stream (`TPUFT_METRICS_PATH`): the `span` records (one per phase,
  with `bucket` on the per-bucket fetches), the `subspan` records (one per
  step: `d2h_ready` / `d2h_fetch` / `d2h_copy`, `ring_queue` / `ring_run`,
  `normalize`, `h2d_put`, `quorum_wait`, the `ft_step` frame and its two
  dispatches; `torchft_tpu/obs/spans.SUBSPANS`) and the `step_summary`
  records with the ring's own counters (`allreduce_lanes`);
- the profile of the traced steps (`g0.trace/.../*.xplane.pb`): the device's
  `XLA Modules` line (one event per execution of a jitted program, named
  `jit_<function>(<fingerprint>)`) and the program's `tpuft:<name>`
  annotations on the host threads, which sit on the device's clock with no
  offset to measure.

Stream metrics are medians over the steps outside the capture
(`ctx["steady_steps"]`), a sub-span counted in the step in which it ended.
Trace metrics are over the executions that start in the traced steps left
after `trace_skip_steps`.  A reader gives None where there is nothing to read:
a program without sub-spans (the parent of the PR that added them), one group
(no exchange), a trace without a device plane (a CPU rehearsal).
"""

from __future__ import annotations

import functools
import glob
import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmark import stats
from benchmark.trace_reduce import STEP, clock_offset, length, union

STREAM_ENV = "TPUFT_METRICS_PATH"
# The names JAX gives TrainStep's two programs (`jax.jit` of `value_and_grad`
# and of `apply`); the speculative update is the same function, so the same name.
GRAD_PROGRAM = "jit_value_and_grad"
UPDATE_PROGRAM = "jit_apply"
# Phases that run on the train thread inside `ft_step` (the quorum thread's
# `quorum`, `configure` and `heal` and the overlapped phases do not).
TRAIN_THREAD_PHASES = ("allreduce_d2h", "allreduce_merge", "allreduce_h2d", "commit_vote")

Interval = Tuple[float, float]


# -- the stream ----------------------------------------------------------------


@functools.lru_cache(maxsize=4)
def stream(path: str) -> Dict[str, Any]:
    """{"spans": [{phase, t0_ns, t1_ns, step, bucket?, bytes?}], "subs":
    [{name, parent, step, t0_ns, t1_ns, thread, bucket?, bytes?, ...}],
    "summaries": [step_summary records, in the stream's order]}."""
    spans: List[Dict[str, Any]] = []
    subs: List[Dict[str, Any]] = []
    summaries: List[Dict[str, Any]] = []
    try:
        with open(path, encoding="utf-8") as f:
            lines = f.readlines()
    except OSError:
        lines = []
    for line in lines:
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        event = rec.get("event")
        if event == "span":
            end = rec["t_mono"] * 1e9
            # `t_start_mono` is the span's own start; without it the start is
            # the record's stamp less the duration, late by the emit's time.
            start = rec["t_start_mono"] * 1e9 if "t_start_mono" in rec else end - rec["duration_ms"] * 1e6
            spans.append(dict(rec, t0_ns=start, t1_ns=start + rec["duration_ms"] * 1e6))
        elif event == "subspan":
            subs.extend(rec.get("spans", []))
        elif event == "step_summary":
            summaries.append(rec)
    return {"spans": spans, "subs": subs, "summaries": summaries}


def of_run() -> Dict[str, Any]:
    """The stream of the run whose readers are running."""
    return stream(os.environ.get(STREAM_ENV, ""))


def _window(step: Dict[str, Any]) -> Interval:
    return step["start_mono_ns"], step["start_mono_ns"] + step["ms"] * 1e6


def in_step(items: Sequence[Dict[str, Any]], step: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Those that ended inside the step (a harness step record)."""
    lo, hi = _window(step)
    return [s for s in items if lo <= s["t1_ns"] <= hi]


def total_ms(items: Sequence[Dict[str, Any]], name: str) -> float:
    return sum(s["t1_ns"] - s["t0_ns"] for s in items if s.get("name", s.get("phase")) == name) / 1e6


def median_per_step(ctx: Dict[str, Any], value: Callable[[List[Dict[str, Any]], List[Dict[str, Any]]], Optional[float]]) -> Optional[float]:
    """Median over the steady steps of `value(sub-spans, spans)` of each step,
    steps where it gives None left out; None where no step gives a value or
    the stream holds no sub-span at all."""
    data = of_run()
    if not data["subs"]:
        return None
    values = [value(in_step(data["subs"], s), in_step(data["spans"], s)) for s in ctx["steady_steps"]]
    values = [v for v in values if v is not None]
    return stats.median(values) if values else None


def sum_of(name: str, always: bool = False) -> Callable[..., Optional[float]]:
    """Per step: the summed duration (ms) of the sub-spans called `name`; a
    step without one gives None, or 0 with `always` (a wait that did not
    happen is a wait of nothing)."""
    def value(subs, _spans):
        if not always and not any(s["name"] == name for s in subs):
            return None
        return total_ms(subs, name)
    return value


def intervals(items: Sequence[Dict[str, Any]], name: str) -> List[Interval]:
    return [(s["t0_ns"], s["t1_ns"]) for s in items if s.get("name", s.get("phase")) == name]


def d2h_handoff_ms(subs, spans) -> Optional[float]:
    """The fetches' self time: the `allreduce_d2h` spans less their children
    (ready, fetch, copy) — the hand-off to the materializer thread and back."""
    if not any(s["name"] == "d2h_fetch" for s in subs):
        return None
    children = sum(total_ms(subs, n) for n in ("d2h_ready", "d2h_fetch", "d2h_copy"))
    return total_ms(spans, "allreduce_d2h") - children


def d2h_fetch_gb_per_s(subs, _spans) -> Optional[float]:
    fetches = [s for s in subs if s["name"] == "d2h_fetch"]
    seconds = sum(s["t1_ns"] - s["t0_ns"] for s in fetches) / 1e9
    return sum(s.get("bytes", 0) for s in fetches) / 1e9 / seconds if seconds > 0 else None


def ring_busy_ms(subs, _spans) -> Optional[float]:
    """How long some ring op was running: the union of the `ring_run`s."""
    runs = intervals(subs, "ring_run")
    return length(union(runs)) / 1e6 if runs else None


def ft_step_self_ms(subs, spans) -> Optional[float]:
    """The `ft_step` frame less what is named inside it on its own thread:
    the train thread's phases and the sub-spans recorded from that thread."""
    frames = [s for s in subs if s["name"] == "ft_step"]
    if not frames:
        return None
    own = 0.0
    for frame in frames:
        lo, hi = frame["t0_ns"], frame["t1_ns"]
        inside = [(s["t0_ns"], s["t1_ns"]) for s in spans if s["phase"] in TRAIN_THREAD_PHASES]
        inside += [(s["t0_ns"], s["t1_ns"]) for s in subs
                   if s["thread"] == frame["thread"] and s["name"] not in ("ft_step", "ring_queue", "ring_run")]
        covered = length(union((max(a, lo), min(b, hi)) for a, b in inside if min(b, hi) > max(a, lo)))
        own += (hi - lo - covered) / 1e6
    return own


# -- the ring's own counters -----------------------------------------------------


def lane_totals(summary: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Of one `step_summary`: bytes sent (every lane of the flat ring and of
    the tiers) and the hops' thread-seconds, cumulative since the ring was configured."""
    lanes = summary.get("allreduce_lanes")
    if not lanes:
        return None
    sent = sum(lanes.get("sent", [])) + sum(sum(t.get("sent", [])) for t in lanes.get("tiers", {}).values())
    hops = lanes.get("hops", {}).values()
    return {
        "sent": float(sent),
        "recv_wait_s": sum(h.get("recv_wait_s", 0.0) for h in hops),
        "combine_s": sum(h.get("combine_s", 0.0) for h in hops),
    }


def counter_per_step(ctx: Dict[str, Any], key: str) -> Optional[float]:
    """Median over the steady steps of what `key` of `lane_totals` grew by in
    the step: its `step_summary` less the one before it."""
    summaries = of_run()["summaries"]
    grew = []
    for step in ctx["steady_steps"]:
        lo, hi = _window(step)
        for before, this in zip(summaries, summaries[1:]):
            if lo <= this["t_mono"] * 1e9 <= hi:
                a, b = lane_totals(before), lane_totals(this)
                if a is not None and b is not None and b["sent"] >= a["sent"]:
                    grew.append(b[key] - a[key])
    return stats.median(grew) if grew else None


# -- the profile -----------------------------------------------------------------


def trace_path(stream_path: Optional[str] = None) -> Optional[str]:
    """The traced group's `.xplane.pb` beside the stream (the newest, if several)."""
    run_dir = os.path.dirname(stream_path or os.environ.get(STREAM_ENV, ""))
    files = sorted(glob.glob(os.path.join(run_dir, "g0.trace", "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


@functools.lru_cache(maxsize=2)
def trace(path: str) -> Dict[str, Any]:
    """{"modules": {device plane: [[program, start_ns, dur_ns], ...]},
    "annotations": [[name, start_ns, dur_ns, {stat: value}], ...] of the
    program's `tpuft:` annotations, "steps": [[start_ns, dur_ns, mono_ns], ...]
    of the harness's step annotations (`mono_ns`: the monotonic clock just
    before each opened)} — plain lists, the form of the recorded fixture."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules: Dict[str, List[List[Any]]] = {}
    annotations: List[List[Any]] = []
    steps: List[List[Any]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(
                        [program_name(e.name), float(e.start_ns), float(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("tpuft:"):
                        annotations.append([e.name[6:], float(e.start_ns), float(e.duration_ns), dict(e.stats)])
                    elif e.name == STEP:
                        steps.append([float(e.start_ns), float(e.duration_ns), dict(e.stats).get("mono_ns")])
    return {"modules": modules, "annotations": annotations, "steps": sorted(steps)}


def of_traced_run() -> Optional[Dict[str, Any]]:
    path = trace_path()
    return trace(path) if path else None


def program_name(event_name: str) -> str:
    """`jit_apply(18263670078141465506)` -> `jit_apply`."""
    return event_name.split("(", 1)[0]


def program_ms(loaded: Optional[Dict[str, Any]], program: str, skip_steps: int) -> Optional[float]:
    """Median device time (ms) of one execution of `program`, over those that
    start at or after the first counted traced step.  Each step runs each of
    the two programs once, so this is the program's device time per step."""
    if not loaded or not loaded["modules"] or len(loaded["steps"]) <= skip_steps:
        return None
    lo = loaded["steps"][skip_steps][0]
    runs = [d for events in loaded["modules"].values() for name, s, d in events if name == program and s >= lo]
    return stats.median(runs) / 1e6 if runs else None


def clock_agreement(loaded: Dict[str, Any], data: Dict[str, Any]) -> Dict[str, Any]:
    """How far each `tpuft:` annotation's start lies from the same span's or
    sub-span's start in the stream moved by the harness's measured offset
    (trace - monotonic, `trace_reduce.clock_offset` over the step
    annotations): {"clock_offset_ns", "matched", "max_abs_ms", "median_ms"
    (signed: annotation minus moved stream)}.  The annotation opens just
    before the stream's clock read, so a sound offset leaves microseconds."""
    offset_ns = clock_offset([[STEP, start, dur, mono] for start, dur, mono in loaded["steps"]])
    if offset_ns is None:
        return {"clock_offset_ns": None, "matched": 0, "max_abs_ms": None, "median_ms": None}
    by_name: Dict[str, List[float]] = {}
    for s in data["subs"]:
        by_name.setdefault(s["name"], []).append(s["t0_ns"] + offset_ns)
    for s in data["spans"]:
        by_name.setdefault(s["phase"], []).append(s["t0_ns"] + offset_ns)
    diffs = []
    for name, start, _dur, _stats in loaded["annotations"]:
        starts = by_name.get(name)
        if starts:
            diffs.append(min((start - t for t in starts), key=abs) / 1e6)
    return {"clock_offset_ns": offset_ns, "matched": len(diffs),
            "max_abs_ms": max(map(abs, diffs), default=None), "median_ms": stats.median(diffs) if diffs else None}
