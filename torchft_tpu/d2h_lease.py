"""The host's device-to-host lease: co-located replica groups take turns at
the gradient fetch.

Device-to-host transfers hold each other back on a TPU host, across processes
as inside one: four v5e groups fetching at once move fewer bytes a second
*together* than one does alone (PERF.md section 7).  So around each
``np.asarray`` of an accelerator-resident gradient leaf
(``futures.device_get_into``) a group holds this lease, and every tpu-ft
process on the machine contends for the same one.

The whole state is the kernel's table of byte-range locks on one file that is
never written (``<tmp>/tpuft-d2h.lease``, open-file-description locks,
``fcntl(F_OFD_SETLK)``), so there is nothing to configure, nothing a crash
leaves behind and nothing a membership change touches:

- a fetch of ``n`` bytes **enqueues** by locking the ``n`` lock-bytes above
  the highest segment anyone holds: its place in the queue is the segment's
  start, and the bytes queued ahead of it are the lengths of the segments
  below it;
- it **holds the lease** once fewer than :data:`HOLDERS` segments lie below
  its own (first come, first served: a group that has just fetched re-enqueues
  above everyone who is waiting);
- **release** unlocks the segment.  A process that dies — SIGKILL included —
  has its segment dropped by the kernel at once, so the next in line goes
  ahead without any bound expiring.

A holder that is alive but wedged (SIGSTOP, a hung DMA) costs a waiter one
**bounded** wait, ``SLACK_S + bytes ahead / FLOOR_BYTES_PER_S`` and never more
than the share of the fetch's own deadline the caller passes: then the waiter
fetches without the lease, remembers the segments it gave up on and looks
past them for as long as they stay.  The lease is advisory: where it cannot be
had (no such fcntl, a path that cannot be opened) the fetch runs as it did
without it, and the outcome says so.
"""

from __future__ import annotations

import errno
import fcntl
import os
import struct
import tempfile
import time
from typing import List, NamedTuple, Optional, Set, Tuple

__all__ = ["D2HLease", "Held", "HOLDERS", "default_path", "host_lease"]

# How many transfers the host runs at a time.  Measured, not a switch
# (tools/d2h_probe.py on the four-chip v5e host, PERF.md section 7): with the
# other processes as busy as they are in an exchange, two at once move 2.7
# GB/s together where one alone moves 3.1.
HOLDERS = 1
# The bound on a wait: every device-to-host rate the records hold for this
# kind of host lies above the floor (0.28 GB/s, four processes with two
# transfers each), so a queue that has not drained by then is not moving.
FLOOR_BYTES_PER_S = 0.2e9
SLACK_S = 0.5
_POLL_S = 0.0005
# A place is lost only to a newcomer that took it between the look and the
# lock: more often than there are processes on a host means a stranger's lock.
_ENQUEUE_TRIES = 64
# Lock-byte 0 is never used (a whole-file lock by a stranger starts there);
# segments live in [_FIRST, _LAST).
_FIRST, _LAST = 1, 1 << 62
_FLOCK = "hhqqi4x"  # struct flock on 64-bit Linux: type, whence, start, len, pid

Segment = Tuple[int, int]  # (start, length) in lock-bytes


class Held(NamedTuple):
    """What :meth:`D2HLease.acquire` hands back and :meth:`release` takes.
    ``outcome``: ``"free"`` (no wait), ``"waited"``, ``"timeout"`` (the bound
    expired: fetching without the lease, the segment still marks the place) or
    ``"unavailable"`` (no lease on this host: nothing is held)."""

    outcome: str
    segment: Optional[Segment] = None


def default_path() -> str:
    """Where every process of this machine meets: the temporary directory
    the environment names, else ``/tmp``."""
    return os.path.join(tempfile.gettempdir(), "tpuft-d2h.lease")


class D2HLease:
    """One process's handle on the host's lease (see the module docstring).
    Opened on first use; a forked child opens its own (a shared open file
    description would share the locks)."""

    def __init__(self, path: Optional[str] = None) -> None:
        self._path = path
        self._fd: Optional[int] = None
        self._pid = 0
        # Segments this process gave up waiting for, while they stay.
        self._wedged: Set[Segment] = set()

    def _open(self) -> bool:
        if self._fd is not None and self._pid == os.getpid():
            return True
        if not hasattr(fcntl, "F_OFD_SETLK"):
            return False
        try:
            fd = os.open(
                self._path or default_path(),
                os.O_RDWR | os.O_CREAT | os.O_NOFOLLOW | os.O_CLOEXEC,
                0o666,
            )
        except OSError:
            return False
        try:
            os.fchmod(fd, 0o666)  # past the umask, for the other users' groups
        except OSError:
            pass  # someone else's file, already open to us
        self._fd, self._pid = fd, os.getpid()
        return True

    def _fcntl(self, cmd: int, kind: int, start: int, length: int) -> Tuple[int, int, int]:
        raw = fcntl.fcntl(self._fd, cmd, struct.pack(_FLOCK, kind, os.SEEK_SET, start, length, 0))
        kind, _whence, start, length, _pid = struct.unpack(_FLOCK, raw)
        return kind, start, length

    def _lock(self, start: int, length: int) -> bool:
        try:
            self._fcntl(fcntl.F_OFD_SETLK, fcntl.F_WRLCK, start, length)
            return True
        except OSError as e:
            if e.errno in (errno.EAGAIN, errno.EACCES):
                return False
            raise

    def _others(self, lo: int, hi: int) -> List[Segment]:
        """The segments other holders have in ``[lo, hi)``."""
        found: List[Segment] = []
        todo = [(lo, hi)]
        while todo:
            a, b = todo.pop()
            if a >= b:
                continue
            kind, start, length = self._fcntl(fcntl.F_OFD_GETLK, fcntl.F_WRLCK, a, b - a)
            if kind == fcntl.F_UNLCK:
                continue
            end = start + length if length else _LAST
            found.append((start, end - start))
            todo += [(a, start), (end, b)]
        return found

    def enqueue(self, nbytes: int) -> Optional[Segment]:
        """Takes the place behind everyone who holds or waits; None where
        this host has no lease."""
        if not self._open():
            return None
        length = max(1, int(nbytes))
        try:
            for _try in range(_ENQUEUE_TRIES):
                queue = self._others(_FIRST, _LAST)
                self._wedged.intersection_update(queue)
                start = max([_FIRST] + [s + n for s, n in queue])
                if self._lock(start, length):  # else: a newcomer took it first
                    return start, length
        except OSError:
            pass
        return None

    def await_turn(self, segment: Segment, max_wait_s: float) -> str:
        """Waits until fewer than HOLDERS live segments lie below ``segment``:
        ``"free"``, ``"waited"`` or, the bound expired, ``"timeout"``."""
        start = segment[0]
        deadline = None
        try:
            while True:
                ahead = [s for s in self._others(_FIRST, start) if s not in self._wedged]
                if len(ahead) < HOLDERS:
                    return "free" if deadline is None else "waited"
                now = time.monotonic()
                if deadline is None:
                    waits_behind = sum(n for _s, n in ahead)
                    deadline = now + min(max_wait_s, SLACK_S + waits_behind / FLOOR_BYTES_PER_S)
                elif now >= deadline:
                    self._wedged.update(ahead)
                    return "timeout"
                time.sleep(_POLL_S)
        except OSError:
            return "timeout"

    def acquire(self, nbytes: int, max_wait_s: float) -> Held:
        """Enqueues and waits for the turn, for at most the bound."""
        segment = self.enqueue(nbytes)
        if segment is None:
            return Held("unavailable")
        return Held(self.await_turn(segment, max_wait_s), segment)

    def release(self, held: Held) -> None:
        if held.segment is None or self._fd is None:
            return
        try:
            self._fcntl(fcntl.F_OFD_SETLK, fcntl.F_UNLCK, *held.segment)
        except OSError:
            pass  # the kernel drops it with the process at the latest


_HOST_LEASE = D2HLease()


def host_lease() -> D2HLease:
    """The process's handle on the machine-wide lease."""
    return _HOST_LEASE
