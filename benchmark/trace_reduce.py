"""From a profiler trace and the host's spans to the device's numbers.

`load` reads an `.xplane.pb` with nothing but JAX and keeps what the reduction
needs as plain lists (which is also the form of the small recorded trace under
`tests/data/`): the device's operations, and the harness's own annotations on
the host.  `reduce` turns that, with the program's spans placed on the same
clock, into busy time, per-kernel time, idle gaps named by what the host was
doing, and the exposed part of the exchange.  No number here is invented: a
trace without device operations reduces to nothing and the caller fails.

Clocks.  The profiler puts host threads and device lines on one timeline.  The
program's spans (`Manager.spans`, read from its metrics stream) are on
`time.monotonic`.  Every `bench_step` annotation carries the monotonic time
taken just before it opened, so the offset between the two clocks is measured
in each trace (median over the traced steps), not assumed.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

STEP = "bench_step"
HOST_NAMES = (STEP, "next_batch", "ft_step", "wait_device")
# Spans of the program that name an idle gap before the harness's own do.
EXCHANGE_SPANS = ("allreduce_d2h", "allreduce_merge", "allreduce_h2d")

Interval = Tuple[float, float]


# -- reading -----------------------------------------------------------------


def load(path: str, platform: str) -> Dict[str, Any]:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]}, "host": [[name,
    start_ns, dur_ns, mono_ns or None], ...]} from an xplane file.  On a TPU
    the devices are the trace's `/device:TPU:` planes and nothing else: a
    trace without one raises, so no host event is ever reported as the chip's."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[List[Any]]] = {}
    host: List[List[Any]] = []
    fallback: List[List[Any]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.setdefault(plane.name, []).extend(
                        [op_name(e.name), float(e.start_ns), float(e.duration_ns)] for e in line.events
                    )
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_NAMES:
                        stats = dict(e.stats)
                        host.append([e.name, float(e.start_ns), float(e.duration_ns), stats.get("mono_ns")])
                    elif not e.name.startswith("end: "):
                        stats = dict(e.stats)
                        if "hlo_op" in stats and e.duration_ns > 0:
                            fallback.append([e.name, float(e.start_ns), float(e.duration_ns)])
    if not devices and platform == "tpu":
        raise RuntimeError(f"{path} holds no /device:TPU: plane with XLA operations — no result")
    if not devices and fallback:
        # A rehearsal on the CPU backend: its XLA operations run on host threads.
        devices["/host:CPU (XLA operations)"] = fallback
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    """The operation's own name.  The TPU's trace names an event by its whole
    HLO line (`%fusion.3 = f32[...] fusion(... %tpuft_ce_dlogits.1)`): what
    comes before ` = ` is the operation, the rest are its operands, which must
    not make a matmul that reads a kernel's output count as that kernel."""
    return event_name.split(" = ", 1)[0].lstrip("%")


# -- interval arithmetic -------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for a, b in clip(busy, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: Sequence[Sequence[Any]]) -> Dict[str, float]:
    """Seconds per operation name, an operation that encloses others (a loop,
    a call) counted without what it encloses."""
    total: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return total


# -- the reduction -------------------------------------------------------------


def clock_offset(host: Sequence[Sequence[Any]]) -> Optional[float]:
    """trace_ns - monotonic_ns, from the annotations that carry both."""
    pairs = [start - mono for name, start, _, mono in host if name == STEP and mono is not None]
    return statistics.median(pairs) if pairs else None


def reduce(trace: Dict[str, Any], spans: Sequence[Tuple[str, float, float]],
           kernels: Optional[Dict[str, Any]] = None, skip_steps: int = 0) -> Optional[Dict[str, Any]]:
    """`spans` are the program's: (phase, start_mono_ns, end_mono_ns).
    `kernels` maps a group's name to a predicate on operation names.
    `skip_steps` leaves out the first traced steps: starting the profiler
    drains the device's queue, so the first traced step lacks the previous
    step's update, which in a steady step runs at its start.  Returns None
    where the trace holds no traced step or no device operation."""
    steps = sorted((s, s + d) for name, s, d, _ in trace["host"] if name == STEP)[skip_steps:]
    if not steps or not trace["devices"]:
        return None
    lo, hi = steps[0][0], steps[-1][1]
    offset = clock_offset(trace["host"])
    named: List[Tuple[int, str, float, float]] = []  # (priority, name, start, end) on the trace's clock
    if offset is not None:
        named += [(0, phase, a + offset, b + offset) for phase, a, b in spans]
    leaf = {"next_batch": 1, "wait_device": 1, "ft_step": 2}
    named += [(leaf[n], n, s, s + d) for n, s, d, _ in trace["host"] if n in leaf]

    exchange = union((s, e) for _, n, s, e in named if n in EXCHANGE_SPANS)
    busy_s, idle_by_name, exposed_per_step = [], {}, []
    kernel_s = {k: 0.0 for k in (kernels or {})}
    ops: Dict[str, float] = {}
    for events in trace["devices"].values():
        inside = [e for e in events if e[1] + e[2] > lo and e[1] < hi]
        if not inside:
            continue
        busy = union((s, s + d) for _, s, d in inside)
        busy_s.append(length(clip(busy, lo, hi)) / 1e9)
        for name, seconds in self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + seconds
            for group, belongs in (kernels or {}).items():
                if belongs(name):
                    kernel_s[group] += seconds
        idle = gaps(busy, lo, hi)
        for a, b in idle:
            # Cut the gap where a host span starts or ends; each piece goes to
            # the most specific span that covers it.
            cuts = sorted({a, b, *(t for _, _, s, e in named for t in (s, e) if a < t < b)})
            for x, y in zip(cuts, cuts[1:]):
                mid = (x + y) / 2
                covering = sorted((p, n) for p, n, s, e in named if s <= mid < e)
                name = covering[0][1] if covering else "outside_step"
                idle_by_name[name] = idle_by_name.get(name, 0.0) + (y - x) / 1e9
        for a, b in steps:
            exposed_per_step.append(
                sum(length(clip(idle, s, e)) for s, e in clip(exchange, a, b)) / 1e9
            )
    if not busy_s:
        return None
    n_dev = len(busy_s)
    top = lambda d: [[k, v / n_dev] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n_dev,
        "steps": len(steps),
        "devices": n_dev,
        "device_step_s": sum(busy_s) / n_dev / len(steps),
        "exposed_exchange_s_per_step": exposed_per_step,
        "kernel_s_per_step": {k: v / n_dev / len(steps) for k, v in kernel_s.items()},
        "clock_offset_ns": offset,
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(idle_by_name)},
    }
