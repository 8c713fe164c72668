"""Program builds: what JAX spends making a program, from JAX's own events.

A start-up is mostly builds — the gradient program traced and lowered in
Python, then compiled or loaded from the persistent cache; the update
program; dozens of small ones (random draws, optimizer state, converts, the
exchange's programs) — and so is a step that suddenly takes seconds: a shape
changed and something compiled again.  JAX reports every stage of every build
through ``jax.monitoring`` with the function's name
(``jax/_src/dispatch.py`` ``log_elapsed_time``: a scalar at the stage's start,
a duration at its end), and inside a backend stage what the persistent cache
did (``jax/_src/compiler.py`` ``compile_or_get_cached``).  This module listens
and keeps one record a stage::

    {fun_name, stage: "trace" | "lower" | "backend", program, outer,
     t0_ns, t1_ns, step, thread}    # + cache, retrieval_s, saved_s on a backend stage;
                                    # + nested_short, nested_short_s where it enclosed short traces

- ``t0_ns`` / ``t1_ns`` are ``time.monotonic_ns()``, the clock of the spans and
  sub-spans: the callback runs at the stage's end, so ``t1`` is now and ``t0``
  is ``t1`` less the duration JAX reports (its own stamps are ``time.time()``).
- ``fun_name`` is JAX's: the Python function for a ``trace`` stage
  (``value_and_grad``), the module for ``lower`` and ``backend``
  (``jit(value_and_grad)``).
- ``program`` is set where a function said which program it is while JAX
  traced it (:func:`tag`; ``TrainStep`` does for ``jit_value_and_grad``,
  ``jit_apply`` and ``jit_full`` — the names the compiled modules have, so
  those of a profile's ``XLA Modules`` line), and on the ``lower`` and
  ``backend`` stages of a module that compiles under that name; None on every
  other build.
- ``outer``: stages nest — a kernel's own ``jax.jit`` traced inside the
  gradient program's trace, a constant computed eagerly while tracing, which is
  a whole small build.  Each is kept as it comes, with the ``fun_name`` of the
  outermost stage open on its thread (None at the top).  A total is therefore
  a UNION of intervals, never a sum.  One kind is counted and not kept: a
  trace stage INSIDE another stage that took under a millisecond
  (:data:`SHORT_S`) — ``jnp``'s own jitted helpers, thousands of them in a
  large model's trace, 8,400 of some 8,550 records in the Nemotron cell's
  start — goes into ``nested_short`` / ``nested_short_s`` (how many; their
  seconds, summed though they nest among themselves) of the outermost
  stage's record, whose interval holds them.
- ``cache`` on a ``backend`` stage is JAX's own count: ``"hit"`` (loaded;
  ``retrieval_s`` is the load, ``saved_s`` the compile time the entry
  recorded less the load), ``"miss"`` (compiled and written), ``"off"``
  (neither: no cache directory, or JAX's thresholds keep so quick a compile
  out of it).
- ``step`` is the Manager step in flight when the stage ended, None while the
  process has no Manager: a record with a step number after warm-up says which
  step recompiled, which function, for how long, and whether the cache had it.

The records are the process's, not a Manager's: builds start before one
exists.  They wait in a bounded ring (:data:`RING`); a ``SpanTracker`` with a
stream takes them (:func:`take`) and writes them as ``program_build`` records
in the same ``write()`` as its next ``step_summary``.  Without a stream
nothing is taken or written and the ring keeps the newest.  No knob: the
listeners run when JAX builds something and at no other time.
"""

from __future__ import annotations

import sys
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional

__all__ = ["RING", "SHORT_S", "STAGES", "register", "tag", "take", "records"]

STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}

# Records kept while nobody takes them; the oldest go first.  A large model's
# start leaves a few hundred.
RING = 8192
# A trace stage nested in another and shorter than this is counted on the
# outermost stage's record, not kept.
SHORT_S = 1e-3

_lock = threading.Lock()
_ring: "deque[Dict[str, Any]]" = deque(maxlen=RING)
_registered = False
_step_of: Optional["weakref.WeakMethod"] = None
_programs: set = set()
_local = threading.local()  # .open: this thread's stages begun and not ended, outermost first


class _Open:
    """A stage between JAX's two calls: what its record will need that only
    arrives meanwhile."""

    __slots__ = ("stage", "fun_name", "program", "short", "short_s", "cache")

    def __init__(self, stage: str, fun_name: str) -> None:
        self.stage, self.fun_name = stage, fun_name
        self.program: Optional[str] = None  # set by `tag`
        self.short, self.short_s = 0, 0.0  # short traces nested in it (counted on the outermost alone)
        self.cache: Dict[str, Any] = {}  # what the cache said inside it (a backend stage)


def register(step_of: Optional[Callable[[], int]] = None) -> None:
    """Starts listening, once a process, however often it is called
    (``TrainStep`` and ``Manager`` both call it at construction).  `step_of`
    — a Manager's bound ``current_step`` — becomes the source of the
    records' ``step``; it is held weakly.  A process that has not imported
    JAX builds nothing, and importing it is not this module's to do: the call
    then only notes `step_of`, and a later one listens."""
    global _registered, _step_of
    if step_of is not None:
        _step_of = weakref.WeakMethod(step_of)
    if "jax" not in sys.modules:
        return
    with _lock:
        if _registered:
            return
        _registered = True
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def tag(program: str) -> None:
    """For a function to call while JAX traces it: the trace stage it runs in
    — the innermost open on this thread, unless a function that encloses it
    has named that stage already — and from now on the ``lower`` and
    ``backend`` stages of a module called `program` carry ``program``."""
    _programs.add(program)
    for entry in reversed(_open()):
        if entry.stage == "trace":
            entry.program = entry.program or program
            return


def take() -> List[Dict[str, Any]]:
    """The records kept so far, oldest first; the ring is left empty."""
    with _lock:
        out = list(_ring)
        _ring.clear()
    return out


def records() -> List[Dict[str, Any]]:
    """A copy of what the ring holds (nothing is taken)."""
    with _lock:
        return list(_ring)


def _open() -> List[_Open]:
    try:
        return _local.open
    except AttributeError:
        _local.open = []
        return _local.open


def _on_start(event: str, _value: float, fun_name: str = "", **_: Any) -> None:
    if event in STAGES:
        _open().append(_Open(STAGES[event], fun_name))


def _said_by_the_cache(key: str, value: Any) -> None:
    open_ = _open()
    if open_ and open_[-1].stage == "backend":  # JAX asks the cache inside a backend stage, on its thread
        open_[-1].cache[key] = value


def _on_event(event: str, **_: Any) -> None:
    if event in _CACHE_EVENTS:
        _said_by_the_cache("cache", _CACHE_EVENTS[event])


def _on_duration(event: str, seconds: float, fun_name: str = "", **_: Any) -> None:
    stage = STAGES.get(event)
    if stage is None:
        if event in _CACHE_SECONDS:
            _said_by_the_cache(_CACHE_SECONDS[event], seconds)
        return
    t1 = time.monotonic_ns()
    open_ = _open()
    ended = _Open(stage, fun_name)  # stands in where the listeners came after the stage's start
    for i in range(len(open_) - 1, -1, -1):
        if (open_[i].stage, open_[i].fun_name) == (stage, fun_name):
            ended = open_[i]
            del open_[i:]  # and whatever it enclosed that never ended
            break
    if open_ and stage == "trace" and seconds < SHORT_S and ended.program is None:
        open_[0].short += 1
        open_[0].short_s += seconds
        return
    program = ended.program
    if program is None and stage != "trace":
        module = fun_name.replace("(", "_").replace(")", "")  # jit(apply) compiles as jit_apply
        program = module if module in _programs else None
    step_of = _step_of() if _step_of is not None else None
    record = {
        "fun_name": fun_name, "stage": stage, "program": program,
        "outer": open_[0].fun_name if open_ else None,
        "t0_ns": t1 - int(seconds * 1e9), "t1_ns": t1,
        "step": step_of() if step_of is not None else None,
        "thread": threading.current_thread().name,
    }
    if ended.short:
        record.update(nested_short=ended.short, nested_short_s=round(ended.short_s, 6))
    if stage == "backend":
        record.update({"cache": "off"}, **ended.cache)
    with _lock:
        _ring.append(record)
