"""Future timeout plumbing.

Reference parity: torchft/futures.py — a singleton timeout manager running a
single background thread arms deadlines for futures and context blocks so
that a stuck collective or RPC surfaces as a ``TimeoutError`` on the wrapped
future instead of hanging the train loop.  The reference drives torch Futures
and CUDA events from an asyncio loop thread (torchft/futures.py:88-210); here
the unit of work is a ``concurrent.futures.Future`` and device-side waits are
handled by JAX's async dispatch, so a heap-of-deadlines timer thread suffices.
"""

from __future__ import annotations

import heapq
import itertools
import threading
from concurrent.futures import Future
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Generator, Optional, TypeVar

T = TypeVar("T")


class _TimeoutManager:
    """Singleton deadline scheduler (reference: _TimeoutManager,
    torchft/futures.py:88-207)."""

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._heap: list[tuple[float, int, Callable[[], None]]] = []
        self._counter = itertools.count()
        self._thread: Optional[threading.Thread] = None
        self._cancelled: set[int] = set()

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="tpuft_timeout_manager", daemon=True
            )
            self._thread.start()

    def register(self, delay: float, callback: Callable[[], None]) -> int:
        """Schedules callback to fire after `delay` seconds; returns a handle
        usable with cancel()."""
        import time

        with self._cond:
            handle = next(self._counter)
            heapq.heappush(self._heap, (time.monotonic() + delay, handle, callback))
            self._ensure_thread()
            self._cond.notify()
        return handle

    def cancel(self, handle: int) -> None:
        with self._cond:
            self._cancelled.add(handle)
            self._cond.notify()

    def _run(self) -> None:
        import time

        while True:
            with self._cond:
                while not self._heap:
                    self._cond.wait()
                deadline, handle, callback = self._heap[0]
                now = time.monotonic()
                if handle in self._cancelled:
                    heapq.heappop(self._heap)
                    self._cancelled.discard(handle)
                    continue
                if deadline > now:
                    self._cond.wait(timeout=deadline - now)
                    continue
                heapq.heappop(self._heap)
            try:
                callback()
            except Exception:
                pass


_TIMEOUT_MANAGER = _TimeoutManager()


def future_timeout(fut: Future, timeout: float) -> Future:
    """Returns a future that mirrors `fut` but fails with TimeoutError if it
    does not complete within `timeout` seconds (reference:
    future_timeout, torchft/futures.py:210-222)."""
    out: Future = Future()

    def on_timeout() -> None:
        if not out.done():
            out.set_exception(
                TimeoutError(f"future did not complete within {timeout}s")
            )

    handle = _TIMEOUT_MANAGER.register(timeout, on_timeout)

    def on_done(f: Future) -> None:
        _TIMEOUT_MANAGER.cancel(handle)
        if out.done():
            return
        exc = f.exception()
        if exc is not None:
            out.set_exception(exc)
        else:
            out.set_result(f.result())

    fut.add_done_callback(on_done)
    return out


def future_wait(fut: Future, timeout: float) -> Any:
    """Blocking wait with a deadline (reference: future_wait,
    torchft/futures.py:225-252).  The deadline surfaces as the BUILTIN
    TimeoutError: on Python < 3.11 ``Future.result`` raises the distinct
    ``concurrent.futures.TimeoutError``, which ``except TimeoutError``
    handlers across the codebase would silently miss."""
    import concurrent.futures

    try:
        return fut.result(timeout=timeout)
    except concurrent.futures.TimeoutError as e:
        if isinstance(e, TimeoutError):  # 3.11+: already the builtin
            raise
        raise TimeoutError(f"future did not complete within {timeout}s") from None


@contextmanager
def context_timeout(callback: Callable[[], None], timeout: float) -> Generator[None, None, None]:
    """Runs `callback` (typically an abort) if the with-block does not finish
    within `timeout` seconds (reference: context_timeout,
    torchft/futures.py:270-282)."""
    handle = _TIMEOUT_MANAGER.register(timeout, callback)
    try:
        yield
    finally:
        _TIMEOUT_MANAGER.cancel(handle)


class _Materializer:
    """Deadline-guarded device->host materialization (the ``stream_timeout``
    analogue, torchft/futures.py:129-148,255).

    ``np.asarray(jax_array)`` blocks indefinitely if the device computation
    feeding it wedges; the reference arms a CUDA-event timer for the same
    edge.  Here the transfer runs on a dedicated **daemon** thread with a
    deadline: on timeout the caller gets ``TimeoutError`` (to latch into the
    step error) and the wedged thread is abandoned — a fresh one serves later
    calls, so one stuck transfer cannot poison the next step's path, and a
    genuinely wedged worker cannot block interpreter shutdown the way a
    ThreadPoolExecutor worker (joined at exit since Python 3.9) would."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._queue = None  # type: Optional[object]
        self._thread: Optional[threading.Thread] = None

    @staticmethod
    def _worker(q) -> None:
        while True:
            item = q.get()
            if item is None:
                return
            fn, fut = item
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn())
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

    def _get_queue(self):
        import queue as _queue

        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._queue = _queue.SimpleQueue()
                self._thread = threading.Thread(
                    target=self._worker,
                    args=(self._queue,),
                    name="tpuft_materialize",
                    daemon=True,
                )
                self._thread.start()
            return self._queue

    def _abandon(self, q) -> None:
        with self._lock:
            if self._queue is not q:
                # Another timed-out caller already abandoned this generation
                # (and drained it); a fresh queue is serving new work.
                return
            old, self._queue = self._queue, None
            self._thread = None
        # Concurrent callers may have queued work behind the wedged item;
        # fail it now rather than letting those callers burn their full
        # deadline on futures nothing will ever run.
        while True:
            try:
                item = old.get_nowait()
            except Exception:  # queue.Empty
                break
            if item is None:
                continue
            _, fut = item
            if not fut.done():
                fut.set_exception(
                    TimeoutError(
                        "materializer abandoned after a concurrent timeout; "
                        "transfer not attempted"
                    )
                )
        old.put(None)  # exit signal, honored if the worker ever unwedges

    def get(self, fn: Callable[[], T], timeout: float) -> T:
        import concurrent.futures

        fut: Future = Future()
        q = self._get_queue()
        q.put((fn, fut))
        try:
            return fut.result(timeout=timeout)
        except concurrent.futures.TimeoutError:
            # concurrent.futures.TimeoutError, NOT the builtin: on Python
            # < 3.11 they are distinct classes, and catching the builtin
            # here silently skipped the abandon (the wedged worker kept the
            # queue, poisoning every later transfer) while callers' `except
            # TimeoutError` error-latching missed the escape entirely.
            self._abandon(q)
            raise TimeoutError(
                f"device->host materialization did not complete within {timeout}s "
                "(stuck device computation?)"
            ) from None


_MATERIALIZER = _Materializer()


def device_get(x: Any, timeout: float) -> Any:
    """Materializes a (possibly device-backed) array to host numpy with a
    deadline; raises TimeoutError instead of hanging on wedged device work."""
    import numpy as np

    return _MATERIALIZER.get(lambda: np.asarray(x), timeout)


def device_get_tree(leaves: list, timeout: float) -> list:
    """Materializes a list of arrays with one shared deadline."""
    import numpy as np

    return _MATERIALIZER.get(lambda: [np.asarray(l) for l in leaves], timeout)


def _copy_into(dst, src_host, cast: bool) -> None:
    """One dtype-checked copy of a materialized source into its destination
    view.  Same-dtype is the fast path; a mismatch raises a ValueError that
    names both dtypes (``np.copyto(casting="no")`` raises a bare TypeError
    the moment a device buffer's dtype diverges from its planned host
    buffer — e.g. a bf16 wire-prepped bucket landing in an f32 buffer —
    which reads like a numpy bug, not a planning bug) unless the caller
    explicitly opted into value conversion with ``cast=True``."""
    import numpy as np

    src_host = src_host.reshape(dst.shape)
    if src_host.dtype == dst.dtype:
        try:
            np.copyto(dst, src_host, casting="no")
        except TypeError:
            # Some numpy/ml_dtypes combinations reject casting="no" even for
            # identical custom dtypes (bfloat16, float8 variants).  Equal
            # dtypes make a raw byte copy exactly equivalent.
            np.copyto(
                dst.view(np.uint8),
                np.ascontiguousarray(src_host).view(np.uint8),
                casting="no",
            )
        return
    if not cast:
        raise ValueError(
            f"device_get_into: source dtype {src_host.dtype} does not match "
            f"destination buffer dtype {dst.dtype}; plan the host buffer in "
            "the dtype the device hands back (device wire prep fetches the "
            "wire dtype), or pass cast=True to convert values explicitly"
        )
    np.copyto(dst, src_host, casting="unsafe")


def _fetch_is_d2h(src) -> bool:
    """Whether materializing ``src`` is a device-to-host transfer: a jax
    array in an accelerator's own memory.  Numpy leaves, CPU-backend arrays
    and ``pinned_host`` leaves are in host memory already."""
    devices = getattr(src, "devices", None)
    if not callable(devices):
        return False
    if any(d.platform == "cpu" for d in devices()):
        return False
    return getattr(getattr(src, "sharding", None), "memory_kind", None) != "pinned_host"


# What device_get_into counts in the ``lease_counts`` dict it is handed, by the
# outcome of each accelerator-resident fetch: fetches that went through the
# host's D2H lease, those of them that had to wait, those whose bounded wait
# expired (fetched without the lease), and fetches on a host where the lease
# could not be opened.
LEASE_COUNTERS = ("lease_fetches", "lease_contended", "lease_timeouts", "lease_unavailable")
_COUNTED_AS = {
    "free": LEASE_COUNTERS[:1],
    "waited": LEASE_COUNTERS[:2],
    "timeout": LEASE_COUNTERS[:3],
    "unavailable": LEASE_COUNTERS[3:],
}


def device_get_into(
    pairs: list,
    timeout: float,
    cast: bool = False,
    sub: Optional[Callable[..., Any]] = None,
    lease_counts: Optional[dict] = None,
) -> None:
    """Materializes ``(src, dst)`` pairs host-side under one shared deadline,
    landing each source directly in its destination view — the bucket-
    pipelined D2H path: every gradient leaf is copied straight into its slot
    of a persistent flat buffer, with no per-step concatenate or fresh
    allocation.  ``dst`` must be a writable numpy view shaped like ``src``.

    Dtypes are checked explicitly: matching dtypes take a fast path (with a
    byte-copy fallback for ml_dtypes destinations numpy's ``casting="no"``
    rejects), and a mismatch raises a clear ValueError unless ``cast=True``
    opts into value conversion — the device wire-prep path fetches bf16
    bytes into bf16 buffers, and a silent f32<->bf16 convert here would
    hide a mis-planned buffer at half or double the intended D2H bytes.

    A source in an accelerator's memory is fetched under the host's D2H
    lease (``torchft_tpu/d2h_lease.py``): every tpu-ft process of the machine
    takes turns at ``np.asarray``, first come, first served, because
    transfers side by side are slower together than one after the other.
    The lease is held for the transfer alone — released before the copy into
    ``dst``, so another group's transfer runs under this group's copy, ring
    op and puts — and the wait for it is bounded (a quarter of ``timeout`` at
    most), after which the fetch runs without it.  ``lease_counts`` (optional:
    a dict) counts what happened under :data:`LEASE_COUNTERS`.  Sources in
    host memory never touch it.

    ``sub`` (optional: ``sub(name, **fields)`` returning a context manager,
    the GradientAverager passes its tracker's) takes each pair's fetch apart
    where it happens, on the materializer thread: ``d2h_ready`` (the wait for
    the program that produces ``src`` — the one call this adds, same result
    and order), ``d2h_lease_wait`` (accelerator sources only; ``contended``:
    whether it had to wait), ``d2h_fetch`` (``np.asarray``: the DMA into
    PJRT's host buffer) and ``d2h_copy`` (the second pass into ``dst``).
    """
    import types

    import numpy as np

    from torchft_tpu.d2h_lease import host_lease

    if sub is None:
        def sub(_name: str, **fields: Any):
            return nullcontext(types.SimpleNamespace(fields=fields))

    counts = lease_counts if lease_counts is not None else {}

    def run() -> None:
        import jax

        for src, dst in pairs:
            with sub("d2h_ready"):
                jax.block_until_ready(src)
            turn = host_lease() if _fetch_is_d2h(src) else None
            if turn is not None:
                with sub("d2h_lease_wait", bytes=dst.nbytes) as wait:
                    held = turn.acquire(dst.nbytes, timeout / 4)
                    wait.fields["contended"] = held.outcome in ("waited", "timeout")
                for name in _COUNTED_AS[held.outcome]:
                    counts[name] = counts.get(name, 0) + 1
            try:
                with sub("d2h_fetch", bytes=dst.nbytes):
                    host = np.asarray(src)
            finally:
                if turn is not None:
                    turn.release(held)
            with sub("d2h_copy", bytes=dst.nbytes):
                _copy_into(dst, host, cast)

    _MATERIALIZER.get(run, timeout)


def completed_future(value: T = None) -> Future:
    """A future already resolved with `value`."""
    fut: Future = Future()
    fut.set_result(value)
    return fut


def failed_future(exc: Exception) -> Future:
    """A Future already resolved to the given exception."""
    fut: Future = Future()
    fut.set_exception(exc)
    return fut


def then(fut: Future, fn: Callable[[Any], T]) -> Future:
    """Chains a continuation onto `fut`, producing a new future with fn's
    result (the torch Future.then analogue used for grad normalization,
    torchft/manager.py:297-311)."""
    out: Future = Future()

    def on_done(f: Future) -> None:
        exc = f.exception()
        if exc is not None:
            out.set_exception(exc)
            return
        try:
            out.set_result(fn(f.result()))
        except Exception as e:  # noqa: BLE001
            out.set_exception(e)

    fut.add_done_callback(on_done)
    return out
