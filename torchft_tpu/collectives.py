"""Reconfigurable collectives for the fault-tolerant replica dimension.

Reference parity: torchft/process_group.py.  The reference reconfigures torch
c10d ProcessGroups (Gloo/NCCL) on every quorum change; XLA has no notion of a
dynamically sized mesh — a compiled program's collectives are fixed at trace
time — so the cross-replica-group dimension lives at the host layer: a
``Collective`` moves host buffers between replica groups over TCP (the DCN
path), while all intra-group parallelism stays inside the pjit-compiled
program over ICI (see torchft_tpu/parallel/).

Semantics carried over from the reference:
  - ``configure(store_addr, rank, world_size)`` tears down the old
    communicator and rendezvouses a new one; safe to call at every quorum
    change (torchft/process_group.py:253-268).
  - operations return ``Work`` futures; errors are latched and surfaced via
    ``errored()`` rather than raised into the train loop
    (torchft/process_group.py:333-349).
  - ``abort()`` cancels in-flight operations without killing the process —
    the analogue of NCCL abort (torchft/process_group.py:650-727).
"""

from __future__ import annotations

import collections
import math
import mmap
import os
import select
import socket
import struct
import threading
import time
from abc import ABC, abstractmethod
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence, cast

import numpy as np

from torchft_tpu._native import StoreClient
from torchft_tpu.futures import completed_future, failed_future

__all__ = [
    "Work",
    "Collective",
    "DummyCollective",
    "TCPCollective",
    "ErrorSwallowingCollective",
    "ManagedCollective",
    "WIRE_CODECS",
    "quantize_int8",
    "quantize_int4",
    "pack_int4",
    "unpack_int4",
]

# Elementwise combine per reduce op ("avg" divides by world size after the
# sum).  Membership doubles as the validity check for allreduce/
# reduce_scatter op arguments.
_REDUCE_COMBINE = {
    "sum": np.add,
    "avg": np.add,
    "max": np.maximum,
    "min": np.minimum,
}


def _bad_reduce_op(op: str) -> ValueError:
    return ValueError(
        f"unsupported reduce op {op!r}; expected one of {sorted(_REDUCE_COMBINE)}"
    )


# Optional per-call wire codecs (TCPCollective.allreduce(wire_codec=...)).
# "int8": symmetric linear quantization, per-chunk scale = amax/127,
# accumulation in float32 — ~0.25x the f32 wire (plus 4 scale bytes per
# frame).  "int4": the same shape packed two values per byte, per-chunk
# scale = amax/7 — 0.125x the f32 wire, the Streaming-DiLoCo design point
# (arXiv:2501.18512 quantizes outer gradients to 4 bits).  Both are lossy
# per hop exactly like the bf16 wire; meant for payloads with a
# source-side error-feedback loop (the semisync pseudogradient plane,
# torchft_tpu/semisync), never for raw weights.
WIRE_CODECS = ("int8", "int4")


def quantize_int8(x: np.ndarray):
    """``(scale, q)`` — THE symmetric int8 quantizer (host side): scale =
    amax/127, round-to-nearest, clipped to [-127, 127].  One
    implementation shared by the ring codec, the semisync EF codec's host
    path, and the bench's drift cells, so the guard rules cannot drift
    between them.  Non-finite handling: an inf/NaN amax falls back to
    scale 1 (a NaN scale would silently zero the whole chunk); inf
    elements saturate to +/-127; NaN elements encode as 0 EXPLICITLY
    (np.rint(nan).astype(int8) is 0 only by C-cast accident) — the wire
    cannot represent NaN, so divergence must be caught by loss/grad-norm
    monitoring, and the EF codec zeroes those elements' residuals rather
    than carrying NaN forward.  The jitted device twin lives in
    torchft_tpu/semisync/codec.py."""
    x = np.asarray(x)
    if x.dtype != np.float32:
        x = x.astype(np.float32)
    amax = float(np.max(np.abs(x))) if x.size else 0.0
    scale = amax / 127.0 if (amax > 0.0 and math.isfinite(amax)) else 1.0
    q = np.clip(
        np.rint(np.nan_to_num(x / scale, nan=0.0)), -127, 127
    ).astype(np.int8)
    return scale, q


def quantize_int4(x: np.ndarray):
    """``(scale, q)`` — the symmetric int4 quantizer (host side): scale =
    amax/7, round-to-nearest, clipped to [-7, 7].  ``q`` is int8-typed but
    every value fits a signed nibble; :func:`pack_int4` is the wire
    packing.  The same non-finite guard rules as :func:`quantize_int8`
    (one shared contract pinned by the codec tests); the jitted device
    twin lives in torchft_tpu/semisync/codec.py."""
    x = np.asarray(x)
    if x.dtype != np.float32:
        x = x.astype(np.float32)
    amax = float(np.max(np.abs(x))) if x.size else 0.0
    scale = amax / 7.0 if (amax > 0.0 and math.isfinite(amax)) else 1.0
    q = np.clip(
        np.rint(np.nan_to_num(x / scale, nan=0.0)), -7, 7
    ).astype(np.int8)
    return scale, q


def pack_int4(q: np.ndarray) -> np.ndarray:
    """Packs signed-nibble values (int8 in [-7, 7]) two per byte: element
    2i in the LOW nibble, 2i+1 in the HIGH nibble, two's complement — the
    exact frame layout native/src/ring.cc's Int4Encode emits, so both
    engines' int4 wire bytes are bitwise-identical.  An odd tail leaves
    the final high nibble zero."""
    u = (q.astype(np.int16) & 0xF).astype(np.uint8)
    if u.size % 2:
        u = np.concatenate([u, np.zeros(1, dtype=np.uint8)])
    return (u[0::2] | (u[1::2] << 4)).astype(np.uint8)


def unpack_int4(raw, n: int) -> np.ndarray:
    """Inverse of :func:`pack_int4`: ``n`` signed int8 values from the
    packed nibble stream (sign-extended via ``(nib ^ 8) - 8``)."""
    b = np.frombuffer(raw, dtype=np.uint8)
    nib = np.empty(b.size * 2, dtype=np.int16)
    nib[0::2] = b & 0xF
    nib[1::2] = b >> 4
    return ((nib[:n] ^ 8) - 8).astype(np.int8)


def _is_bf16(dtype) -> bool:
    """True for the ml_dtypes bfloat16 dtype.  bf16 does NOT register under
    ``np.issubdtype(..., np.floating)`` — every floating-dtype gate in this
    module that must also admit already-wire-dtype payloads checks this
    explicitly."""
    import ml_dtypes

    return np.dtype(dtype) == np.dtype(ml_dtypes.bfloat16)


class Work:
    """Handle for an async collective operation (the c10d Work analogue).

    ``times`` is ``[started_ns, done_ns]`` on ``time.monotonic_ns()``: when
    one of the engine's workers took the op up and when it finished,
    stamped by that worker BEFORE the future resolves (so a continuation
    reads both); 0 where the op never ran on a worker (a world of one, a
    failure before submission).  The Manager's ``ring_queue`` / ``ring_run``
    sub-spans read it."""

    def __init__(self, future: Future, times: Optional[List[int]] = None) -> None:
        self._future = future
        self.times: List[int] = times if times is not None else [0, 0]

    def wait(self, timeout: Optional[float] = None):
        return self._future.result(timeout=timeout)

    def result(self, timeout: Optional[float] = None):
        return self._future.result(timeout=timeout)

    def done(self) -> bool:
        return self._future.done()

    def exception(self, timeout: Optional[float] = None):
        return self._future.exception(timeout=timeout)

    def future(self) -> Future:
        return self._future

    def add_done_callback(self, fn: Callable[[Future], None]) -> None:
        self._future.add_done_callback(fn)


class Collective(ABC):
    """Abstract reconfigurable collective over the replica-group dimension.

    The full collective surface of the reference's ProcessGroup
    (torchft/process_group.py:115-251) mapped to host arrays: allreduce,
    allgather, broadcast, reduce_scatter, alltoall, barrier, send/recv.
    """

    @abstractmethod
    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        """(Re)builds the communicator; aborts any previous one.  store_addr
        is "host:port/prefix" — a unique prefix per quorum round prevents
        rendezvous collisions with stale rounds (torchft/manager.py:503)."""

    @abstractmethod
    def allreduce(
        self,
        arrays: Sequence[np.ndarray],
        op: str = "sum",
        allow_wire_compression: bool = True,
    ) -> Work:
        """Elementwise reduction across ranks; results replace `arrays`
        contents in the returned Work's result list.

        allow_wire_compression=False opts this call out of lossy wire
        encodings (wire_dtype="bf16"): gradient-like payloads tolerate
        per-hop bf16 rounding, but direct PARAMETER averaging (LocalSGD)
        must not accumulate quantization across syncs."""

    @abstractmethod
    def allgather(self, array: np.ndarray) -> Work:
        """Gathers each rank's array; result is a list of world_size arrays."""

    @abstractmethod
    def broadcast(self, array: np.ndarray, root: int = 0) -> Work:
        """Broadcasts root's array to all ranks; result is the array."""

    @abstractmethod
    def reduce_scatter(self, arrays: Sequence[np.ndarray], op: str = "sum") -> Work:
        """Reduces world_size equal chunks and scatters: rank i receives the
        reduction of every rank's arrays[i]."""

    @abstractmethod
    def alltoall(self, arrays: Sequence[np.ndarray]) -> Work:
        """Rank i sends arrays[j] to rank j; result is the received list."""

    @abstractmethod
    def send(self, array: np.ndarray, dst: int, tag: int = 0) -> Work:
        ...

    @abstractmethod
    def recv(self, shape: tuple, dtype, src: int, tag: int = 0) -> Work:
        ...

    @abstractmethod
    def barrier(self) -> Work:
        ...

    @abstractmethod
    def size(self) -> int:
        ...

    @abstractmethod
    def rank(self) -> int:
        ...

    def abort(self) -> None:
        """Cancels in-flight work and poisons the communicator until the next
        configure()."""

    def errored(self) -> Optional[Exception]:
        """Returns the latched error, if any."""
        return None

    def shutdown(self) -> None:
        self.abort()


class DummyCollective(Collective):
    """World-size-1 no-op collective: copies inputs to outputs and completes
    immediately.  Used to soak init-time collectives and as post-error
    placeholder (reference: ProcessGroupDummy, torchft/process_group.py:730-864)."""

    wire_codecs = WIRE_CODECS  # accepted (and ignored: world size 1)

    def __init__(self, rank: int = 0, world_size: int = 1) -> None:
        self._rank = rank
        self._world_size = world_size
        self.configure_count = 0

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self._rank = rank
        self._world_size = world_size
        self.configure_count += 1

    def allreduce(
        self,
        arrays: Sequence[np.ndarray],
        op: str = "sum",
        allow_wire_compression: bool = True,
        wire_codec: Optional[str] = None,
    ) -> Work:
        out = [np.array(a, copy=True) for a in arrays]
        if op == "avg":
            out = [a / 1.0 for a in out]
        return Work(completed_future(out))

    def allgather(self, array: np.ndarray) -> Work:
        return Work(completed_future([np.array(array, copy=True)]))

    def broadcast(self, array: np.ndarray, root: int = 0) -> Work:
        return Work(completed_future(np.array(array, copy=True)))

    def reduce_scatter(self, arrays: Sequence[np.ndarray], op: str = "sum") -> Work:
        return Work(completed_future(np.array(arrays[0], copy=True)))

    def alltoall(self, arrays: Sequence[np.ndarray]) -> Work:
        return Work(completed_future([np.array(a, copy=True) for a in arrays]))

    def send(self, array: np.ndarray, dst: int, tag: int = 0) -> Work:
        return Work(completed_future(None))

    def recv(self, shape: tuple, dtype, src: int, tag: int = 0) -> Work:
        return Work(completed_future(np.zeros(shape, dtype)))

    def barrier(self) -> Work:
        return Work(completed_future(None))

    def size(self) -> int:
        return self._world_size

    def rank(self) -> int:
        return self._rank


# ---------------------------------------------------------------------------
# TCP ring collective — the cross-group (DCN) data plane.
# ---------------------------------------------------------------------------

_HDR = struct.Struct("<IQ")  # tag, nbytes

# Per-chunk scale header for the int8 wire codec (see _codec): one f32
# scale prefixes each quantized frame, so every hop can decode without any
# out-of-band scale exchange and the allgather phase's byte-forwarding
# stays self-contained (replica consistency: every rank decodes the same
# scale+payload bytes).
_INT8_SCALE = struct.Struct("<f")


class LinkShaper:
    """DCN-shaped link emulation for transport validation on localhost.

    Applied at the sender: each frame pays half the RTT (propagation) and
    its bytes are paced at the configured bandwidth (serialization), so a
    loopback TCP link behaves like a latency/bandwidth-bound cross-site
    link.  Enabled for all TCPCollective peers via
    ``TPUFT_SHAPED_LINK="<mbps>:<rtt_ms>"``; wire-byte counters let tests
    assert traffic (e.g. the bf16 wire halving) without timing flakiness.

    The serialization budget is a shared VIRTUAL-TIME pacer: concurrent
    senders (the multi-lane ring shares ONE shaper per peer direction)
    queue on the modeled link, so adding lanes cannot multiply the modeled
    bandwidth — lanes may only win by overlapping propagation (the half-RTT
    per frame) and host-side work with serialization, exactly the physics
    of parallel TCP streams on one bottleneck link.
    """

    def __init__(self, mbps: float, rtt_ms: float) -> None:
        self.bytes_per_s = mbps * 1e6 / 8.0
        self.half_rtt_s = rtt_ms / 2000.0
        self._bytes_sent = 0
        self._frames_sent = 0
        # Time actually slept waiting out the modeled serialization +
        # propagation — the "shaping" bucket of obs.report's
        # link_attribution split.
        self._wait_s = 0.0
        # When the native ring engine owns this direction's sends, its
        # pacer does the counting; the hook keeps the byte-accounting
        # surface (tests, benches) engine-agnostic.
        self._native_read: Optional[Callable[[], tuple]] = None
        self._native_wait: Optional[Callable[[], float]] = None
        self._lock = threading.Lock()
        # Virtual time (monotonic clock) until which the modeled link is
        # busy serializing already-admitted frames.
        self._busy_until = 0.0

    @property
    def bytes_sent(self) -> int:
        if self._native_read is not None:
            return self._native_read()[0]
        return self._bytes_sent

    @property
    def frames_sent(self) -> int:
        if self._native_read is not None:
            return self._native_read()[1]
        return self._frames_sent

    @property
    def wait_s(self) -> float:
        """Seconds senders actually slept in this pacer (shaping time)."""
        if self._native_wait is not None:
            return self._native_wait()
        return self._wait_s

    def set_rate(self, mbps: float, rtt_ms: float) -> None:
        """Mid-run re-shaping (the slow-link bench degrades ONE peer
        direction without a reconfigure).  ``mbps <= 0`` disables the
        pacing — matching the native engine's SetRate contract, and
        avoiding a divide-by-zero in on_send."""
        with self._lock:
            if mbps > 0:
                self.bytes_per_s = mbps * 1e6 / 8.0
                self.half_rtt_s = rtt_ms / 2000.0
            else:
                self.bytes_per_s = float("inf")
                self.half_rtt_s = 0.0

    @classmethod
    def from_env(cls) -> Optional["LinkShaper"]:
        spec = os.environ.get("TPUFT_SHAPED_LINK")
        if not spec:
            return None
        mbps, _, rtt = spec.partition(":")
        return cls(float(mbps), float(rtt or "0"))

    def delay_s(self, nbytes: int) -> float:
        return self.half_rtt_s + nbytes / self.bytes_per_s

    def on_send(self, nbytes: int) -> None:
        with self._lock:
            self._bytes_sent += nbytes
            self._frames_sent += 1
            now = time.monotonic()
            start = max(now, self._busy_until)
            self._busy_until = start + nbytes / self.bytes_per_s
            # Frame is delivered once its bytes clear the shared link plus
            # one-way propagation; a lone sender sees exactly the legacy
            # delay (serialization + half RTT per frame back-to-back).
            wake = self._busy_until + self.half_rtt_s
        remaining = wake - time.monotonic()
        if remaining > 0:
            time.sleep(remaining)
            with self._lock:
                self._wait_s += remaining


# -- data-plane flight recorder (docs/architecture.md "Data-plane
# observability") ----------------------------------------------------------
# Per-hop telemetry from the ring hot loop, recorded IDENTICALLY by both
# engines: the Python loops below feed a HopRecorder, the native engine
# records inside RingPass (native/src/ring.cc RingHopRecord) — same field
# set, same semantics, schema-pinned against each other by
# tests/test_link.py.  ``TPUFT_HOP_SAMPLE`` records every Nth hop into the
# bounded timeline ring (0 keeps only the cheap per-tier aggregates);
# ``TPUFT_HOP_RING`` bounds the retained timeline.
TPUFT_HOP_SAMPLE_ENV = "TPUFT_HOP_SAMPLE"
TPUFT_HOP_RING_ENV = "TPUFT_HOP_RING"
_HOP_RING_DEFAULT = 2048

# The cross-engine hop-record schema: ts = wall-clock seconds at hop
# start; tier 0 flat / 1 row / 2 col; send_s = blocked joining the lane
# sender (includes link pacing); recv_s = blocked on the matching inbound
# frame; comb_s = decode + combine of the received chunk (reduce-scatter
# hops; 0 on allgather forwards); nbytes = frame payload bytes sent.
HOP_RECORD_FIELDS = (
    "ts", "tier", "lane", "tag", "send_s", "recv_s", "comb_s", "nbytes",
)


def _hop_sample_from_env() -> int:
    try:
        return max(0, int(os.environ.get(TPUFT_HOP_SAMPLE_ENV, "1")))
    except ValueError:
        return 1


def _hop_ring_from_env() -> int:
    try:
        return max(16, int(os.environ.get(TPUFT_HOP_RING_ENV, str(_HOP_RING_DEFAULT))))
    except ValueError:
        return _HOP_RING_DEFAULT


class HopRecorder:
    """Bounded, lock-light per-hop recorder — the Python engine's half of
    the data-plane flight recorder.

    Two tiers of cost: per-tier AGGREGATE stall counters (a few float adds
    per hop, always on — ``lane_stats()``'s "hops" feed and the
    link_attribution split's source) and a SAMPLED bounded timeline ring
    (every ``sample``-th hop; 0 disables the timeline) that
    ``obs/trace.py`` renders as the per-lane data-plane Perfetto track.
    Hops are millisecond-scale network operations; the recorder's budget
    is pinned by the bench's healthy control cell (<2% throughput impact).
    """

    def __init__(self, sample: Optional[int] = None, cap: Optional[int] = None) -> None:
        self.sample = sample if sample is not None else _hop_sample_from_env()
        self.cap = cap if cap is not None else _hop_ring_from_env()
        self._lock = threading.Lock()
        self._ring: "collections.deque[dict]" = collections.deque(maxlen=self.cap)
        self._count = 0
        # tier -> [hops, send_s, recv_s, comb_s]
        self._agg: Dict[int, List[float]] = {}

    def record(
        self,
        tier: int,
        lane: int,
        tag: int,
        send_s: float,
        recv_s: float,
        comb_s: float,
        nbytes: int,
        ts: float,
    ) -> None:
        with self._lock:
            agg = self._agg.get(tier)
            if agg is None:
                agg = self._agg[tier] = [0, 0.0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += send_s
            agg[2] += recv_s
            agg[3] += comb_s
            if self.sample <= 0:
                return
            n = self._count
            self._count = n + 1
            if n % self.sample:
                return
            self._ring.append(
                {
                    "ts": ts,
                    "tier": tier,
                    "lane": lane,
                    "tag": tag,
                    "send_s": send_s,
                    "recv_s": recv_s,
                    "comb_s": comb_s,
                    "nbytes": nbytes,
                }
            )

    def stats(self, tier: int) -> dict:
        """Aggregate stall counters for one tier (same keys as the native
        engine's ``hop_stats``)."""
        with self._lock:
            agg = self._agg.get(tier, [0, 0.0, 0.0, 0.0])
            return {
                "hops": int(agg[0]),
                "send_block_s": agg[1],
                "recv_wait_s": agg[2],
                "combine_s": agg[3],
            }

    def records(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def keep(self, rec: dict) -> None:
        """Appends an already-recorded hop (e.g. the native engine's
        timeline, banked before the engine is torn down) WITHOUT touching
        the aggregates — it was aggregated where it was recorded."""
        with self._lock:
            self._ring.append(rec)

    def reset_aggregates(self) -> None:
        """Zeroes the aggregate counters, KEEPING the timeline ring: the
        aggregates are banked into lane_totals at abort (re-reading them
        would double-count), but the timeline is the data-plane black box
        — wiping it at abort would empty the hop dump on exactly the
        fault paths it exists to explain."""
        with self._lock:
            self._agg = {}

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._agg = {}
            self._count = 0


class _ShmRing:
    """One attached end of a same-host SPSC byte ring — the Python
    engine's half of the shm lane transport (the native half is
    ShmWriteAll/ShmReadExact in native/src/ring.cc over the SAME segment
    layout, so a Python producer feeds a native consumer and vice versa).

    Exactly one producer and one consumer per segment (ring lane links
    are unidirectional: the dialer only sends, the acceptor only
    receives), so the only synchronization is the pair of monotonic
    byte cursors — head (producer) and tail (consumer) — in the segment
    header.  Python's side relies on the GIL's sequencing plus x86/ARM
    acquire-release-on-aligned-load semantics for the cursor reads, the
    same assumption mmap-based SPSC rings make everywhere.

    Stalls poll the link's kept-open TCP socket for liveness: a dead
    peer's socket reads EOF long before the op timeout, so shm lanes
    fail exactly as fast as tcp lanes do (the crash-cleanup test pins
    this)."""

    _SPINS = 512

    def __init__(self, path: str, token: int, sock: socket.socket) -> None:
        fd = os.open(path, os.O_RDWR)
        try:
            size = os.fstat(fd).st_size
            if size <= _SHM_HDR:
                raise ConnectionError(f"shm segment too small: {size} bytes")
            self._mm = mmap.mmap(fd, size)
        finally:
            os.close(fd)
        magic, tok = struct.unpack_from("<QQ", self._mm, 0)
        if magic != _SHM_MAGIC or tok != token:
            self._mm.close()
            raise ConnectionError(
                "stale shm segment (generation mismatch) — refusing to attach"
            )
        self._cap = size - _SHM_HDR
        self._sock = sock
        self.path = path
        self._closed = False

    def _u64(self, off: int) -> int:
        return struct.unpack_from("<Q", self._mm, off)[0]

    def poison(self) -> None:
        """Marks the segment dead for the peer (cross-process fail-fast,
        the shm analogue of a socket shutdown)."""
        if not self._closed:
            struct.pack_into("<I", self._mm, _SHM_POISON_OFF, 1)

    def _wait_tick(self, spins: List[int], deadline: float,
                   consumer: bool = False) -> None:
        """One no-progress step: spin briefly, then check the deadline,
        the peer's poison flag, and the TCP socket's liveness.  For the
        CONSUMER, peer-death signals (poison, socket EOF) only fail once
        the ring is drained: the producer's final frames land in the ring
        before its close() sets the flag, exactly like bytes sitting in a
        closed TCP socket's buffer."""
        def dead(msg: str) -> None:
            if consumer and self._u64(_SHM_HEAD_OFF) - self._u64(_SHM_TAIL_OFF):
                return  # final frames still in the ring — drain first
            raise ConnectionError(msg)

        if struct.unpack_from("<I", self._mm, _SHM_POISON_OFF)[0]:
            dead("peer connection closed (shm ring poisoned)")
            return
        if spins[0] < self._SPINS:
            spins[0] += 1
            return
        spins[0] = 0
        if time.monotonic() > deadline:
            raise TimeoutError("shm ring timed out")
        try:
            readable, _, _ = select.select([self._sock], [], [], 0)
            eof = bool(readable) and self._sock.recv(1, socket.MSG_PEEK) == b""
        except (OSError, ValueError):
            readable, eof = False, True
        if eof:
            dead("peer connection closed")
            return
        if readable:
            raise ConnectionError("unexpected socket data on shm lane")
        time.sleep(20e-6)

    def write(self, data, timeout: float) -> None:
        """Producer: appends ``data``'s bytes, blocking (with liveness
        polling) while the ring is full.  Frames larger than the capacity
        flow through in pieces."""
        mv = memoryview(data)
        if mv.ndim != 1 or mv.itemsize != 1:
            mv = mv.cast("B")
        deadline = time.monotonic() + timeout
        spins = [0]
        pos, n, cap = 0, len(mv), self._cap
        while pos < n:
            if self._closed:
                raise ConnectionError("shm ring closed")
            h = self._u64(_SHM_HEAD_OFF)
            t = self._u64(_SHM_TAIL_OFF)
            free = cap - (h - t)
            if free == 0:
                self._wait_tick(spins, deadline)
                continue
            take = min(n - pos, free)
            off = h % cap
            first = min(take, cap - off)
            self._mm[_SHM_HDR + off : _SHM_HDR + off + first] = mv[pos : pos + first]
            if take > first:
                self._mm[_SHM_HDR : _SHM_HDR + take - first] = (
                    mv[pos + first : pos + take]
                )
            struct.pack_into("<Q", self._mm, _SHM_HEAD_OFF, h + take)
            pos += take
            deadline = time.monotonic() + timeout
            spins[0] = 0

    def read_into(self, view: memoryview, timeout: float) -> None:
        """Consumer: fills ``view`` from the ring, blocking (with liveness
        polling) while it is empty."""
        deadline = time.monotonic() + timeout
        spins = [0]
        pos, n, cap = 0, len(view), self._cap
        while pos < n:
            if self._closed:
                raise ConnectionError("shm ring closed")
            t = self._u64(_SHM_TAIL_OFF)
            h = self._u64(_SHM_HEAD_OFF)
            avail = h - t
            if avail == 0:
                self._wait_tick(spins, deadline, consumer=True)
                continue
            take = min(n - pos, avail)
            off = t % cap
            first = min(take, cap - off)
            view[pos : pos + first] = self._mm[_SHM_HDR + off : _SHM_HDR + off + first]
            if take > first:
                view[pos + first : pos + take] = (
                    self._mm[_SHM_HDR : _SHM_HDR + take - first]
                )
            struct.pack_into("<Q", self._mm, _SHM_TAIL_OFF, t + take)
            pos += take
            deadline = time.monotonic() + timeout
            spins[0] = 0

    def close(self) -> None:
        if not self._closed:
            try:
                self.poison()
            except ValueError:
                pass
            self._closed = True
            try:
                self._mm.close()
            except Exception:  # noqa: BLE001
                pass


class _Peer:
    """A framed duplex TCP link to one peer rank.

    Frames arriving out of order (concurrent senders on a thread pool) are
    demultiplexed by tag: a frame for a tag nobody asked for yet is stashed
    until the matching recv_msg arrives.

    The demux is leader/follower: exactly one caller (the leader) reads the
    socket at a time, but it publishes every non-matching frame to the
    stash UNDER THE CONDITION and notifies, so a concurrent caller whose
    frame already landed takes it immediately instead of queuing behind the
    leader's blocking read.  The previous design held one mutex across the
    socket read; with three or more ops interleaved on a shared lane the
    two ring directions could form a hold-and-wait cycle — rank A's lock
    holder blocked on a frame rank B can only send after B's lock holder
    receives a frame stashed (unreachable) behind A's holder — a mutual
    stall the striped bf16 e2e bench hit roughly once per dozen steps."""

    def __init__(self, sock: socket.socket, shaper: Optional[LinkShaper] = None) -> None:
        self.sock = sock
        self.send_lock = threading.Lock()
        self.recv_cond = threading.Condition()
        self._reading = False
        self.shaper = shaper if shaper is not None else LinkShaper.from_env()
        self._stash: dict[int, "collections.deque[bytearray]"] = {}
        # Wire-byte counters (headers included), always on — the per-lane
        # throughput accounting the GB/s telemetry reads; ints under the
        # send lock / recv condition, so the cost is a couple of adds per
        # frame.  When the native ring engine owns this link's I/O the
        # hook reads its counter instead, so lane_stats and the tests
        # that sweep peer byte counters stay engine-agnostic.
        self._bytes_out = 0
        self._bytes_in = 0
        self._native_bytes: Optional[Callable[[], int]] = None
        # Same-host shm lane transport (ring channels only).  _shm_pending
        # holds the negotiated (path, token, role) from rendezvous until
        # the engine decision arms it: the native engine maps the segment
        # itself (set_shm); the Python engine arms _shm_tx (dialer,
        # producer) or _shm_rx (acceptor, consumer) below, after which
        # send_msg/_recv_exact move payload bytes through the ring while
        # the socket stays open as the liveness/abort channel.
        self._shm_pending: Optional[tuple] = None
        self._shm_tx: Optional[_ShmRing] = None
        self._shm_rx: Optional[_ShmRing] = None

    @property
    def bytes_out(self) -> int:
        if self._native_bytes is not None:
            return self._native_bytes()
        return self._bytes_out

    @property
    def bytes_in(self) -> int:
        if self._native_bytes is not None:
            return self._native_bytes()
        return self._bytes_in

    def send_msg(self, tag: int, payload) -> None:
        """payload: one buffer, or a list of buffers sent as a single frame
        (scatter-gather — lets callers frame header+raw-array without
        concatenating into yet another copy)."""
        parts = payload if isinstance(payload, (list, tuple)) else [payload]
        total = sum(len(p) for p in parts)
        with self.send_lock:
            if self.shaper is not None:
                self.shaper.on_send(total + _HDR.size)
            if self._shm_tx is not None:
                budget = self.sock.gettimeout() or 60.0
                self._shm_tx.write(_HDR.pack(tag, total), budget)
                for p in parts:
                    self._shm_tx.write(p, budget)
            else:
                self.sock.sendall(_HDR.pack(tag, total))
                for p in parts:
                    self.sock.sendall(p)
            self._bytes_out += total + _HDR.size

    def recv_msg(self, expect_tag: int) -> bytearray:
        with self.recv_cond:
            while True:
                q = self._stash.get(expect_tag)
                if q:
                    payload = q.popleft()
                    if not q:
                        del self._stash[expect_tag]
                    return payload
                if not self._reading:
                    self._reading = True
                    break
                # A leader is on the socket; it will either hand us our
                # frame via the stash (notify below) or step down (finally
                # block), at which point we take over.  The leader's socket
                # timeout bounds this wait — a dead peer surfaces as its
                # error, then ours.
                self.recv_cond.wait()
        try:
            while True:
                hdr = self._recv_exact(_HDR.size)
                tag, nbytes = _HDR.unpack(hdr)
                payload = self._recv_exact(nbytes)
                if tag == expect_tag:
                    return payload
                with self.recv_cond:
                    self._stash.setdefault(tag, collections.deque()).append(payload)
                    self.recv_cond.notify_all()
        finally:
            with self.recv_cond:
                self._reading = False
                self.recv_cond.notify_all()

    def _recv_exact(self, n: int) -> bytearray:
        # Returned as the bytearray itself (writable, no bytes() copy):
        # np.frombuffer over it yields mutable arrays and every ring exchange
        # saves a full payload memcpy.
        buf = bytearray(n)
        view = memoryview(buf)
        if self._shm_rx is not None:
            self._shm_rx.read_into(view, self.sock.gettimeout() or 60.0)
            self._bytes_in += n
            return buf
        got = 0
        while got < n:
            r = self.sock.recv_into(view[got:], n - got)
            if r == 0:
                raise ConnectionError("peer connection closed")
            got += r
        self._bytes_in += n
        return buf

    def close(self) -> None:
        for ring in (self._shm_tx, self._shm_rx):
            if ring is not None:
                ring.close()
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()


class _FifoQueue:
    """Submission-order turnstile for one (direction, peer, tag) stream.

    A stream is all-or-nothing: once any op on it fails (timeout or socket
    error) the stream is poisoned and every later op fails immediately.
    Skipping a failed slot instead would let the remote side's matching op
    pair with the *next* op's frame — a silent payload swap that consumers
    outside the commit gate (checkpoint transports) could act on before any
    reconfigure clears the error."""

    def __init__(self) -> None:
        self.cond = threading.Condition()
        self.next_submit = 0
        self.next_serve = 0
        self.poison: Optional[Exception] = None

    def take_ticket(self) -> int:
        with self.cond:
            seq = self.next_submit
            self.next_submit += 1
            return seq

    def wait_turn(self, seq: int, timeout: float) -> None:
        with self.cond:
            ok = self.cond.wait_for(
                lambda: self.poison is not None or self.next_serve >= seq,
                timeout=timeout,
            )
            if self.poison is not None:
                raise RuntimeError(f"channel poisoned by earlier failure: {self.poison}")
            if not ok:
                raise TimeoutError("timed out waiting for earlier op on this channel")

    def done(self) -> None:
        with self.cond:
            self.next_serve += 1
            self.cond.notify_all()

    def poison_with(self, exc: Exception) -> None:
        with self.cond:
            if self.poison is None:
                self.poison = exc
            self.cond.notify_all()


# Parallel ring connections ("lanes") per neighbor.  Lanes stripe ring
# chunks across independent sockets and a per-lane worker pool, so one
# bucket's reduce-scatter *sum* overlaps another bucket's send/recv, and
# per-frame propagation (RTT) overlaps across lanes — the two effects that
# keep a shaped/high-RTT link busy.  Shaped benches stay honest: all lanes
# to one neighbor share a single LinkShaper serialization budget.
TPUFT_RING_LANES_ENV = "TPUFT_RING_LANES"
_MAX_LANES = 8
# Stripes per ring chunk are capped so tag space and frame overhead stay
# bounded; tags are carved as seq * _TAGS_PER_OP + stripe * _TAGS_PER_STRIPE
# + subtag.  The per-stripe block is PARTITIONED BY TIER: the flat ring (and
# the 2D topology's row tier, which reuses its subtags on its own sockets)
# takes the low half, the 2D topology's nested column tier the high half —
# so a hierarchical op's two nested rings can never collide on a tag even
# if a future topology multiplexes tiers onto shared sockets.  The static
# audit in tests/test_collectives.py pins every subtag below
# _TAGS_PER_STRIPE and every stripe block inside its op's _TAGS_PER_OP.
_MAX_STRIPES = 64
_TAGS_PER_STRIPE = 8
_TAGS_PER_OP = _TAGS_PER_STRIPE * (_MAX_STRIPES + 1)
# Subtags within one stripe's block.
_SUB_RS = 1  # reduce-scatter hops (flat ring / row tier)
_SUB_AG = 2  # allgather hops (flat ring / row tier)
_SUB_GATHER = 3  # whole-object circulation (allgather/broadcast/alltoall)
_SUB_COL_RS = 4  # nested column-tier reduce-scatter (ring2d)
_SUB_COL_AG = 5  # nested column-tier allgather (ring2d)

# Cross-group allreduce topology (docs/architecture.md "Topology-aware
# allreduce").  "ring" is the flat single ring over all N groups (latency
# grows as 2(N-1) hops); "ring2d" arranges the N groups on an R x C grid
# (R = largest divisor <= sqrt(N)) and runs reduce-scatter along the row
# ring, a full allreduce along the column ring, and allgather back along
# the row — 2(C-1) + 2(R-1) hops, the latency win that keeps step time flat
# at O(100) groups.  "auto" picks ring2d once the group count reaches
# TPUFT_RING2D_MIN_GROUPS (and the count factors into a real grid).
TPUFT_RING_TOPOLOGY_ENV = "TPUFT_RING_TOPOLOGY"
TPUFT_RING2D_MIN_ENV = "TPUFT_RING2D_MIN_GROUPS"
_RING2D_DEFAULT_MIN = 8
_TOPOLOGIES = ("auto", "ring", "ring2d")


# Ring engine selection (docs/architecture.md "Native data plane").  The
# hot loop — per-hop socket I/O, tag demux, link pacing, wire codecs, the
# f32 combine — can run either in Python threads ("py") or in the native
# GIL-free engine (native/src/ring.cc, "native").  Both produce IDENTICAL
# wire bytes and results (bitwise — pinned by the engine-parity tests), so
# mixed-engine rings interoperate and "auto" (the default) simply picks
# native whenever libtpuft.so exports it, falling back to Python otherwise
# (one warning when native was requested explicitly but the .so is stale).
# Payloads outside the native fast path (non-f32 accumulation: int/f64
# payloads, pickled control traffic) run the Python orchestration over the
# engine's socket layer, so ALL reads of a lane socket share one demux.
TPUFT_RING_ENGINE_ENV = "TPUFT_RING_ENGINE"
_RING_ENGINES = ("auto", "py", "native")

# Native engine op/wire codes (mirrors native/src/ring.h enums).
_NATIVE_OP = {"sum": 0, "avg": 0, "max": 1, "min": 2}
_NATIVE_WIRE_RAW = 0
_NATIVE_WIRE_BF16 = 1
_NATIVE_WIRE_INT8 = 2
_NATIVE_WIRE_INT4 = 3
_NATIVE_PASS_FULL = 0
_NATIVE_PASS_RS = 1
_NATIVE_PASS_AG = 2

# Ring lane transport (docs/architecture.md "Same-host data plane").
# "tcp" (default): every lane frame crosses the kernel socket.  "shm":
# lanes whose two ranks prove same-host at rendezvous (matching
# /proc/sys/kernel/random/boot_id, exchanged right after the connection
# preamble) move their frames through a lock-free SPSC byte ring in a
# /dev/shm segment instead — the TCP socket stays open as the
# liveness/abort channel, and tag demux / abort / reconfigure semantics
# are unchanged (the segment layout is pinned between _ShmRing here and
# native/src/ring.cc, so mixed-engine rings still interoperate).  "auto"
# negotiates shm where it can and silently keeps tcp elsewhere; "shm"
# makes a failed same-host negotiation a hard configure() error.  The
# knob must match on every rank of one collective (like lanes/topology):
# a tcp rank cannot parse the shm handshake bytes.
TPUFT_RING_TRANSPORT_ENV = "TPUFT_RING_TRANSPORT"
_TRANSPORTS = ("tcp", "shm", "auto")

# Incremental reconfiguration (docs/architecture.md "Elastic scale").  A
# membership delta that preserves this rank's flat-ring position reuses
# the surviving lane sockets and shm segments instead of the full
# teardown-and-rendezvous — the dominant per-transition dead-time cost
# under churn.  Default on; "0" forces the full path on every quorum
# transition (the parity baseline the elastic soak compares against).
TPUFT_INCREMENTAL_RECONF_ENV = "TPUFT_INCREMENTAL_RECONF"


def _incremental_from_env() -> bool:
    v = os.environ.get(TPUFT_INCREMENTAL_RECONF_ENV, "1").strip().lower()
    return v not in ("0", "false", "off", "no")

# Per-link SPSC ring capacity (data bytes past the 64-byte header).
# Frames larger than the capacity flow through in pieces, so this bounds
# memory, not payload size.
_SHM_RING_BYTES_DEFAULT = 1 << 20

# Segment header layout — MUST mirror native/src/ring.cc (kShmMagic,
# kShmHdr, kShm*Off): magic u64 @0, generation token u64 @8, head
# (producer cursor) u64 @16, tail (consumer cursor) u64 @24, poisoned
# u32 @32, consumer-parked u32 @40, producer-parked u32 @44, data @64.
# Cursors are monotonic byte counts.  The parked flags belong to the
# native engine's futex wait path; this Python engine polls and never
# sets them (a native waiter paired with a Python peer is bounded by
# its 2 ms park timeout), but the offsets are reserved here so the two
# layouts cannot drift.
_SHM_MAGIC = 0x746675745F736D68
_SHM_HDR = 64
_SHM_TOKEN_OFF = 8
_SHM_HEAD_OFF = 16
_SHM_TAIL_OFF = 24
_SHM_POISON_OFF = 32

# Rendezvous extension blocks (sent on ring channels only, and only when
# the transport knob is not "tcp"): dialer -> 64-byte padded boot-id;
# acceptor -> (flag, token, segment name); dialer -> 1 ack byte.
_SHM_REQ = struct.Struct("<64s")
_SHM_REP = struct.Struct("<BQ64s")


def _transport_from_env() -> str:
    t = os.environ.get(TPUFT_RING_TRANSPORT_ENV, "tcp")
    return t if t in _TRANSPORTS else "tcp"


def _boot_id() -> bytes:
    """This host's boot UUID — the same-host proof two ranks compare at
    rendezvous (equal boot-ids => same kernel instance => /dev/shm is
    genuinely shared).  Empty when unreadable, which disables shm."""
    try:
        with open("/proc/sys/kernel/random/boot_id", "rb") as f:
            return f.read().strip()[:64]
    except OSError:
        return b""

_native_fallback_warned = False


def _warn_native_fallback(reason: str) -> None:
    """One clear line per process when TPUFT_RING_ENGINE=native was
    requested but the engine could not be constructed — a silent Python
    fallback here would report CPU-bound numbers as if they were the native
    data plane's."""
    global _native_fallback_warned
    if _native_fallback_warned:
        return
    _native_fallback_warned = True
    import logging

    logging.getLogger("torchft_tpu.collectives").warning(
        "TPUFT_RING_ENGINE=native requested but the native ring engine is "
        "unavailable; running the PYTHON ring engine instead: %s",
        reason,
    )


def _ring_engine_from_env() -> str:
    engine = os.environ.get(TPUFT_RING_ENGINE_ENV, "auto")
    return engine if engine in _RING_ENGINES else "auto"


def _ring_lanes_from_env() -> int:
    try:
        lanes = int(os.environ.get(TPUFT_RING_LANES_ENV, "2"))
    except ValueError:
        return 2
    return max(1, min(_MAX_LANES, lanes))


def _topology_from_env() -> str:
    topo = os.environ.get(TPUFT_RING_TOPOLOGY_ENV, "auto")
    return topo if topo in _TOPOLOGIES else "auto"


def _ring2d_min_from_env() -> int:
    try:
        return max(2, int(os.environ.get(TPUFT_RING2D_MIN_ENV, str(_RING2D_DEFAULT_MIN))))
    except ValueError:
        return _RING2D_DEFAULT_MIN


def _grid_shape(n: int) -> tuple:
    """``(rows, cols)`` with ``rows * cols == n`` and ``rows`` the largest
    divisor <= sqrt(n) — the squarest exact factoring, which minimizes the
    2D hop count 2(C-1) + 2(R-1).  Every rank derives the identical grid
    from the world size alone (no negotiation), and non-square N lands on
    its divisor grid (6 -> 2x3, 8 -> 2x4).  Primes return (1, n): no 2D
    factoring exists, and the caller degrades to the flat ring."""
    rows = int(math.isqrt(n))
    while rows > 1 and n % rows:
        rows -= 1
    rows = max(1, rows)
    return rows, n // rows


class _TierLinks:
    """Connections and metadata for one nested ring tier of the 2D topology.

    A tier is a smaller ring over a subset of the world (a grid row or
    column): ``size`` members, this rank at position ``ring_rank``, one
    socket per lane per direction, and its own per-lane sender pools so a
    shaped row send never heads-of-line-blocks a column send on a different
    physical link."""

    def __init__(self, size: int, ring_rank: int, next_rank: int, prev_rank: int) -> None:
        self.size = size
        self.ring_rank = ring_rank
        self.next_rank = next_rank  # world rank of the tier's next neighbor
        self.prev_rank = prev_rank  # world rank of the tier's prev neighbor
        self.next_lanes: List[_Peer] = []
        self.prev_lanes: List[_Peer] = []
        self.send_pools: List[object] = []

    def peers(self) -> List[_Peer]:
        return list(self.next_lanes) + list(self.prev_lanes)


class TCPCollective(Collective):
    """Striped multi-lane ring collective over TCP sockets between replica
    groups.

    This is the tpu-ft data plane for the *replica* (DCN) dimension: gradients
    have already been reduced over ICI inside the pjit step; what crosses
    groups is one host buffer per ring chunk.  Ring allreduce moves
    2*(n-1)/n of the data per rank — bandwidth optimal, and each group talks
    only to its ring neighbors, matching how DCN links are provisioned.

    Lanes: ``TPUFT_RING_LANES`` (default 2, max 8) parallel connections per
    ring neighbor.  With lanes > 1 each allreduce is split into round-robin
    chunk stripes, every stripe running its own ring on lane ``stripe %
    lanes`` with a unique per-op tag, executed by a per-lane worker pool —
    so stripe k's local *sum* overlaps stripe k+1's bytes on the wire, and
    back-to-back allreduce calls (the GradientAverager's buckets) overlap
    each other instead of serializing on one socket pair.  Submission order
    of ring ops must still be identical on every rank (program order), but
    alignment within that order is carried by tags, not timing.

    Topology: ``topology="auto"`` (``TPUFT_RING_TOPOLOGY``) selects between
    the flat ring and a 2D ring-of-rings per configure().  The flat ring's
    latency term is 2(N-1) sequential hops; at O(dozens) of groups on a
    real (high-RTT) DCN link that term IS the step-time floor.  "ring2d"
    arranges the groups on an R x C grid and runs: reduce-scatter along the
    ROW ring (C-1 hops), a full allreduce of the owned row chunk along the
    COLUMN ring (2(R-1) hops), allgather back along the row (C-1 hops) —
    ~4*sqrt(N) hops total.  Fold order is deterministic per topology (row
    partials then column fold, each in fixed ring-step order), so results
    remain BITWISE-identical across every rank — the replica-consistency
    property the commit protocol depends on — though hierarchical f32/bf16
    results differ from the flat ring's within reassociation/requantization
    rounding.  "auto" keeps the flat ring below TPUFT_RING2D_MIN_GROUPS
    (default 8) and whenever N has no non-trivial divisor (primes).
    allgather/broadcast/alltoall/barrier always use the flat ring (control
    traffic, not the gradient hot path); both tiers' sockets are torn down
    together by abort()/configure().

    Reconfiguration: rendezvous through the group store under a caller-chosen
    prefix; every rank publishes "host:port", rank i dials rank (i+1)%n once
    per lane.  abort() closes the sockets, causing in-flight ops to fail
    fast and latch an error until the next configure() (the NCCL-abort
    analogue, torchft/process_group.py:584-647).
    """

    RENDEZVOUS_TIMEOUT_MS = 60000

    def __init__(
        self,
        timeout: float = 60.0,
        chunk_bytes: int = 4 << 20,
        wire_dtype: str = "auto",
        lanes: Optional[int] = None,
        topology: Optional[str] = None,
        engine: Optional[str] = None,
        transport: Optional[str] = None,
    ) -> None:
        """``wire_dtype="bf16"`` halves allreduce bytes on the wire (DCN is
        the cross-slice bottleneck): ring payloads are cast to bfloat16 per
        hop while local accumulation stays in the input dtype (f32 for
        grads).  Every rank quantizes the reduced chunk identically before
        the allgather phase, so all replicas still receive BITWISE-equal
        results — the property the commit protocol depends on.

        ``"auto"`` (default) picks bf16 when the link is declared
        bandwidth-bound — ``TPUFT_LINK_PROFILE=dcn`` in the environment,
        or a shaped-link emulation is active (``TPUFT_SHAPED_LINK``) —
        and f32 otherwise.  Why not bf16 always: (1) each hop quantizes,
        so error grows with ring size — at the replica dimension's small
        world sizes (2-8 groups) the rounding is well inside gradient
        noise; (2) it trades host CPU (the casts) for wire bytes, so it
        wins only when the network is the bottleneck — a bandwidth-bound
        link moves half the bytes, while on localhost loopback the casts
        cost more than the bytes save (CPU-host observations of earlier
        rounds; no cell of the benchmark runs the bf16 wire)."""
        if wire_dtype == "auto":
            wire_dtype = (
                "bf16"
                if os.environ.get("TPUFT_LINK_PROFILE") == "dcn"
                or os.environ.get("TPUFT_SHAPED_LINK")
                else "f32"
            )
        if wire_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"unsupported wire_dtype {wire_dtype!r}; expected 'f32' or 'auto' or 'bf16'"
            )
        topology = topology if topology is not None else _topology_from_env()
        if topology not in _TOPOLOGIES:
            raise ValueError(
                f"unsupported topology {topology!r}; expected one of {_TOPOLOGIES}"
            )
        engine = engine if engine is not None else _ring_engine_from_env()
        if engine not in _RING_ENGINES:
            raise ValueError(
                f"unsupported engine {engine!r}; expected one of {_RING_ENGINES}"
            )
        transport = transport if transport is not None else _transport_from_env()
        if transport not in _TRANSPORTS:
            raise ValueError(
                f"unsupported transport {transport!r}; expected one of {_TRANSPORTS}"
            )
        self._timeout = timeout
        self._chunk_bytes = chunk_bytes
        self._wire_dtype = wire_dtype
        self._lanes = lanes if lanes is not None else _ring_lanes_from_env()
        self._lanes = max(1, min(_MAX_LANES, self._lanes))
        self._topology = topology  # requested; resolved per configure()
        self._ring2d_min = _ring2d_min_from_env()
        self._active_topology = "ring"
        # Native GIL-free ring engine handle (None = Python engine); built
        # per configure() over the freshly rendezvoused lane sockets.
        self._engine_mode = engine
        self._engine = None
        # Lane transport: requested mode, per-configure count of armed shm
        # links, and every segment path this rank negotiated (BOTH sides
        # track, so whichever rank survives a peer crash unlinks).
        self._transport = transport
        self._shm_links = 0
        self._shm_lock = threading.Lock()
        self._shm_paths: set = set()
        self._row_tier: Optional[_TierLinks] = None
        self._col_tier: Optional[_TierLinks] = None
        self._lock = threading.Lock()
        self._executor: Optional[object] = None
        self._ring_executor: Optional[object] = None
        self._lane_executor: Optional[object] = None
        # One single-worker sender pool per lane (see _exchange).
        self._send_pools: List[object] = []
        self._rank = 0
        self._world_size = 1
        self._next_lanes: List[_Peer] = []  # links to (rank+1) % n, one per lane
        self._prev_lanes: List[_Peer] = []  # links to (rank-1) % n, one per lane
        # Ring-op sequence counter: allocated at CALL time on the caller's
        # thread, so identical program order on every rank yields identical
        # tags (the cross-rank alignment contract now that ops overlap).
        self._op_seq = 0
        self._op_seq_lock = threading.Lock()
        # In-flight striped-op result futures, failed fast on abort().
        self._inflight: set = set()
        # Data-plane flight recorder (shared by both engines' Python-
        # orchestrated hops; native ring passes record inside ring.cc and
        # are merged in lane_stats/hop_records).  Reset per configure(),
        # like the lane byte counters.
        self._hops = HopRecorder()
        # Lifetime (cross-configure) counter bank: lane/hop counters zero
        # on every configure(), so any cumulative exposition (the worker
        # /metrics endpoint) would go BACKWARDS across a reconfigure.
        # abort() banks the closing generation's totals here;
        # lane_totals() = banked + live, monotonic by construction (the
        # same reset-aware epoch logic obs.report.data_plane applies to
        # step_summary snapshots, applied at the source).
        self._lifetime: Dict[str, object] = {}
        self._peers: dict[int, _Peer] = {}
        self._accept_cond = threading.Condition()
        self._accept_thread: Optional[threading.Thread] = None
        self._accepted_ring: dict[int, _Peer] = {}
        self._dialing: set[int] = set()
        self._listener: Optional[socket.socket] = None
        self._error: Optional[Exception] = None
        self._op_error: Optional[Exception] = None
        self._generation = 0
        self._store: Optional[StoreClient] = None
        # FIFO tickets so same-(peer, tag) send/recv pairs execute in
        # submission order despite the multi-worker p2p executor; without
        # this, two same-tag ops could be silently swapped by the tag demux.
        self._fifo_lock = threading.Lock()
        self._fifo: dict[tuple, "_FifoQueue"] = {}
        self._p2p_submit_lock = threading.Lock()
        # Incremental (elastic) reconfiguration state.  Each flat-ring
        # neighbor's identity is its published listener address plus an
        # incarnation token minted with the listener — equal identity
        # across a quorum transition proves the SAME process still holds
        # the other end of our lane sockets, so the edge can be reused.
        # The prev-direction shapers live on the instance (not the accept
        # loop's closure) so an accept loop started by one generation can
        # arm peers for a later incremental generation.
        self._incremental = _incremental_from_env()
        self._self_addr: Optional[str] = None
        self._listener_token = ""
        self._neighbor_ids: Dict[str, tuple] = {}
        self._ring_prev_shaper: Optional[LinkShaper] = None
        self._tier_prev_shapers: Dict[int, Optional[LinkShaper]] = {}
        # What the LAST configure() did — the Manager's membership_change
        # event and the elastic bench read this to attribute transition
        # cost to the full vs incremental path.
        self.last_configure: Dict[str, object] = {
            "mode": "none",
            "reused_lanes": 0,
            "opened_lanes": 0,
            "configure_s": 0.0,
        }

    # -- lifecycle ----------------------------------------------------------

    @property
    def _next(self) -> Optional[_Peer]:
        """Lane-0 link to (rank+1) % n — kept as the stable single-lane
        handle (tests and diagnostics); all lanes of one direction share one
        LinkShaper, so its byte counters cover the whole direction."""
        return self._next_lanes[0] if self._next_lanes else None

    @property
    def _prev(self) -> Optional[_Peer]:
        return self._prev_lanes[0] if self._prev_lanes else None

    def _resolve_topology(self, world_size: int) -> str:
        """The topology this configuration actually runs.  ring2d needs a
        non-trivial grid (primes cannot factor: the "remainder" worlds);
        auto additionally keeps the flat ring below the crossover group
        count, where 2(N-1) hops still beats paying two tiers' framing."""
        if self._topology == "ring" or world_size < 4:
            return "ring"
        rows, _cols = _grid_shape(world_size)
        if rows < 2:
            return "ring"
        if self._topology == "ring2d":
            return "ring2d"
        return "ring2d" if world_size >= self._ring2d_min else "ring"

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        t0 = time.monotonic()
        if self._configure_incremental(store_addr, rank, world_size, t0):
            return
        self.abort()
        with self._lock:
            self._error = None
            self._op_error = None
            self._rank = rank
            self._world_size = world_size
            self._generation += 1
            self._active_topology = self._resolve_topology(world_size)
            with self._op_seq_lock:
                self._op_seq = 0
            # Abort may have cancelled queued p2p ops that will never call
            # done(); fresh turnstiles avoid cross-generation waits.
            with self._fifo_lock:
                self._fifo = {}
            # Hop AGGREGATES are per-configure like the lane byte counters
            # (abort() just banked the closing generation's totals and
            # reset them; the timeline ring persists across generations —
            # it is the bounded black box, not a counter).
            if world_size == 1:
                self.last_configure = {
                    "mode": "full",
                    "reused_lanes": 0,
                    "opened_lanes": 0,
                    "configure_s": time.monotonic() - t0,
                }
                return
            self._store = StoreClient(store_addr)
            self._rendezvous()
            self._engine = self._create_engine()
            self._arm_shm_links()
            from concurrent.futures import ThreadPoolExecutor

            # Single-lane ring ops share the lane-0 sockets and execute one
            # at a time in submission order on this executor — program
            # order is identical on every rank, which keeps the rings
            # aligned.  Striped ops instead fan out to the per-lane pool
            # below, aligned by per-op tags.  P2P send/recv use per-pair
            # sockets with tag demux and may overlap freely.
            self._ring_executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpuft_ring"
            )
            self._send_pools = [
                ThreadPoolExecutor(max_workers=1, thread_name_prefix=f"tpuft_send{ln}")
                for ln in range(self._lanes)
            ]
            for name, tier in (("row", self._row_tier), ("col", self._col_tier)):
                if tier is not None:
                    # Each tier direction gets its own single-worker-per-lane
                    # sender pool: a shaped row frame must not head-of-line
                    # block a column frame headed down a different link.
                    tier.send_pools = [
                        ThreadPoolExecutor(
                            max_workers=1, thread_name_prefix=f"tpuft_{name}{ln}"
                        )
                        for ln in range(self._lanes)
                    ]
            if self._lanes > 1:
                # Depth-2 per lane: a stripe's worker stays occupied through
                # its link-serialization wait (real or shaped), so with only
                # one worker per lane the next bucket's stripes could never
                # enter the wire until the current bucket's cleared it —
                # exactly the bubble lanes exist to remove.  2x lets stripe
                # k+1 overlap stripe k's in-flight time; the shared per-peer
                # shaper still bounds aggregate bandwidth.
                self._lane_executor = ThreadPoolExecutor(
                    max_workers=self._lanes * 2, thread_name_prefix="tpuft_lane"
                )
            self._executor = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="tpuft_p2p"
            )
            opened = len(self._next_lanes) + len(self._prev_lanes)
            for tier in (self._row_tier, self._col_tier):
                if tier is not None:
                    opened += len(tier.next_lanes) + len(tier.prev_lanes)
            self.last_configure = {
                "mode": "full",
                "reused_lanes": 0,
                "opened_lanes": opened,
                "configure_s": time.monotonic() - t0,
            }

    def _configure_incremental(
        self, store_addr: str, rank: int, world_size: int, t0: float
    ) -> bool:
        """Quorum-transition fast path: when this rank's flat-ring position
        survives the membership delta, reuse the surviving lane sockets and
        shm segments and open only the edges that changed, instead of the
        full teardown-and-rendezvous (the dominant per-transition dead-time
        cost under churn).  Returns False — the caller then runs the full
        path — whenever a precondition fails or any step slips; the
        subsequent abort() reclaims everything a partial attempt registered
        on self.

        Protocol: every configuring rank publishes ``rank_{r}`` (listener
        address — stable here, the listener is kept) and ``cfg_{r}``
        ("inc:<token>" on this path, "full:<token>" on the full path) into
        the NEW quorum's store namespace.  An edge is reused iff the
        neighbor's published (addr, token) identity equals the identity
        recorded at the previous configure AND its mode is "inc" (a "full"
        neighbor's old sockets were closed by its abort()).  Both ends of
        a surviving edge evaluate the same two records, so the decision is
        symmetric.  Once this rank has PUBLISHED it commits to the
        incremental path even when no edge survives (both neighbors
        replaced — it then rebuilds every edge over the kept listener):
        the published address is live the moment the key lands, so a
        fresh neighbor may already hold a connection to it.  The rare
        asymmetric slip (a rank aborts to the full path AFTER publishing
        "inc", e.g. a peer crash mid-configure) leaves the reusing side
        holding a dead socket, which surfaces as an op error and recovers
        on the next quorum — the same contract as the crash itself.
        Late-arriving spares take the full path (nothing of theirs
        survives) and hot-admit by dialing the survivors' kept listeners.
        """
        if not self._incremental:
            return False
        with self._lock:
            try:
                return self._configure_incremental_locked(
                    store_addr, rank, world_size, t0
                )
            except Exception:  # noqa: BLE001 — any slip falls back to full
                return False

    def _configure_incremental_locked(
        self, store_addr: str, rank: int, world_size: int, t0: float
    ) -> bool:
        # Preconditions: a live single-tier ring on BOTH sides of the
        # transition (ring2d crossovers always rebuild — tier membership
        # changes shape, not just neighbors), a kept listener, no latched
        # error, and nothing in flight (the Manager reconfigures at a step
        # boundary; in-flight work means something already failed).
        if (
            self._listener is None
            or self._self_addr is None
            or not self._neighbor_ids
            or self._world_size <= 1
            or world_size <= 1
            or self._error is not None
            or self._op_error is not None
            or self._inflight
            or self._active_topology != "ring"
            or self._resolve_topology(world_size) != "ring"
            or not self._next_lanes
            or not self._prev_lanes
            or self._ring_executor is None
        ):
            return False
        old_next_id = self._neighbor_ids.get("next")
        old_prev_id = self._neighbor_ids.get("prev")
        if old_next_id is None or old_prev_id is None:
            return False
        store = StoreClient(store_addr)
        old_store, self._store = self._store, store
        if old_store is not None:
            try:
                old_store.close()
            except Exception:  # noqa: BLE001
                pass
        # Purge point-to-point links and stale accepted conns BEFORE
        # publishing our address: ranks renumber (p2p can never survive),
        # and a fast new neighbor may dial the moment it reads the key —
        # its lanes must land in _accepted_ring AFTER this sweep, not be
        # closed by it.  Generation bump invalidates in-flight dials,
        # exactly as abort() does.
        with self._accept_cond:
            stale = list(self._peers.values()) + list(self._accepted_ring.values())
            self._peers = {}
            self._accepted_ring = {}
            self._generation += 1
            self._dialing = set()
            self._accept_cond.notify_all()
            # The segments of the CLOSING generation, noted at the same
            # point and for the same reason: a fast new neighbor's lanes —
            # and the segments their handshakes register — land after this.
            with self._shm_lock:
                old_shm_paths = set(self._shm_paths)
        for p in stale:
            p.close()
        # Fresh prev-direction shaper installed before the publish for the
        # same reason; if the prev edge ends up reused, the accepted-lane
        # path never reads it and the reused peers keep their own shaper.
        self._ring_prev_shaper = LinkShaper.from_env()
        store.set(f"rank_{rank}", self._self_addr.encode())
        store.set(f"cfg_{rank}", f"inc:{self._listener_token}".encode())
        next_rank = (rank + 1) % world_size
        prev_rank = (rank - 1) % world_size
        # Full rendezvous budget, not the surviving-neighbor short wait: a
        # REPLACED neighbor is a fresh process that may publish late
        # (restart + runtime init), and the full path would wait just as
        # long for its dial.
        ident_ms = self.RENDEZVOUS_TIMEOUT_MS
        next_id = self._peer_identity(next_rank, timeout_ms=ident_ms)
        prev_id = self._peer_identity(prev_rank, timeout_ms=ident_ms)
        if next_id is None or prev_id is None:
            return False
        reuse_next = next_id[2] == "inc" and next_id[:2] == old_next_id
        reuse_prev = prev_id[2] == "inc" and prev_id[:2] == old_prev_id
        # When NOTHING survives (e.g. world 2 and the only neighbor was
        # replaced by a fresh incarnation publishing "full") we still stay
        # on this path and rebuild both edges over the KEPT listener.
        # Falling back to full here would be unsound, not just slow: our
        # address + "inc" marker are already published, and a fresh
        # neighbor may have dialed that listener the moment the key
        # appeared — the fallback's abort() would close it under them,
        # they'd finish their rendezvous holding dead sockets, and our
        # full-path replacement listener would wait out the whole
        # rendezvous timeout for a dial that never comes (a survivor +
        # restarted-peer pair stalled 60 s per transition this way).
        # Bank the closing generation's counters while the native engine
        # (if any) is still readable, then DETACH it: plain close() of its
        # dup'd fds — unlike Close()'s shutdown(), the reused sockets'
        # underlying connections stay alive.  A detach refusal (ops in
        # flight) raises and falls back to the full path.
        self._bank_locked()
        engine, self._engine = self._engine, None
        if engine is not None:
            engine.detach()
        # Close the edges that did not survive; zero the surviving ones'
        # per-generation counters (their totals were just banked) and drop
        # their native hooks until _create_engine rewires them.
        keep_paths: set = set()
        for reused, lanes_list in (
            (reuse_next, self._next_lanes),
            (reuse_prev, self._prev_lanes),
        ):
            sh = lanes_list[0].shaper if lanes_list else None
            if reused and sh is not None:
                sh._native_read = None
                sh._native_wait = None
                with sh._lock:
                    sh._bytes_sent = 0
                    sh._frames_sent = 0
                    sh._wait_s = 0.0
                    sh._busy_until = 0.0
            for p in lanes_list:
                if reused:
                    p._bytes_out = 0
                    p._bytes_in = 0
                    p._native_bytes = None
                    if p._shm_pending is not None:
                        keep_paths.add(p._shm_pending[0])
                else:
                    p.close()
        # Reclaim only the segments whose edges died; surviving segments
        # keep their names (the re-built engine re-attaches them by the
        # unchanged header token).  Only the closing generation's: a
        # segment a new neighbor's handshake registered since the publish
        # is this generation's, and unlinking it here left that neighbor
        # holding a name that opens nothing ("shm open: No such file").
        with self._shm_lock:
            drop = [sp for sp in old_shm_paths if sp not in keep_paths]
            self._shm_paths = (self._shm_paths - set(drop)) | keep_paths
        for sp in drop:
            try:
                os.unlink(sp)
            except OSError:
                pass
        self._error = None
        self._op_error = None
        self._rank = rank
        self._world_size = world_size
        self._active_topology = "ring"
        with self._op_seq_lock:
            self._op_seq = 0
        with self._fifo_lock:
            self._fifo = {}
        # Open only the changed edges.  Executors and the accept loop are
        # generation-agnostic and stay up — that, plus the kept sockets,
        # is the entire dead-time win.
        lanes = self._lanes
        opened = 0
        if not reuse_next:
            next_shaper = LinkShaper.from_env()
            self._next_lanes = []
            for lane in range(lanes):
                self._next_lanes.append(
                    self._dial_rank(
                        next_rank, self._CH_RING, lane=lane, shaper=next_shaper
                    )
                )
            opened += lanes
        if not reuse_prev:
            self._prev_lanes = []
            expected = [(prev_rank, self._CH_RING, lane) for lane in range(lanes)]
            deadline = self.RENDEZVOUS_TIMEOUT_MS / 1000
            with self._accept_cond:
                ok = self._accept_cond.wait_for(
                    lambda: all(key in self._accepted_ring for key in expected),
                    timeout=deadline,
                )
                if not ok:
                    missing = [k for k in expected if k not in self._accepted_ring]
                    raise TimeoutError(
                        f"incremental reconfigure: ring peers never connected: "
                        f"{missing}"
                    )
                self._prev_lanes = [
                    self._accepted_ring.pop((prev_rank, self._CH_RING, lane))
                    for lane in range(lanes)
                ]
            opened += lanes
        self._engine = self._create_engine()
        self._arm_shm_links()
        self._neighbor_ids = {"next": next_id[:2], "prev": prev_id[:2]}
        self.last_configure = {
            "mode": "incremental",
            "reused_lanes": (lanes if reuse_next else 0)
            + (lanes if reuse_prev else 0),
            "opened_lanes": opened,
            "configure_s": time.monotonic() - t0,
        }
        return True

    @property
    def ring_engine(self) -> str:
        """The engine the CURRENT configuration runs the ring hot loop on:
        "native" (GIL-free, native/src/ring.cc) or "py".  "auto" and
        explicit requests resolve here — what the bench's engine A/B
        records and the parity tests pin."""
        return "native" if self._engine is not None else "py"

    @property
    def ring_transport(self) -> str:
        """The transport the CURRENT configuration's ring lanes move
        payload bytes on: "shm" when at least one same-host segment was
        negotiated and armed (all-loopback rings arm every lane), "tcp"
        otherwise — what the bench's transport A/B records and
        test_transport_quick_smoke pins."""
        return "shm" if self._shm_links > 0 else "tcp"

    def _create_engine(self) -> Optional[object]:
        """Builds the native ring engine over this generation's lane fds
        (all tiers), or returns None for the Python engine.  Called under
        _lock right after _rendezvous; any failure degrades to Python."""
        if self._engine_mode == "py":
            return None
        from torchft_tpu import _native

        mbps = rtt_ms = 0.0
        spec = os.environ.get("TPUFT_SHAPED_LINK")
        if spec:
            try:
                head, _, tail = spec.partition(":")
                mbps, rtt_ms = float(head), float(tail or "0")
            except ValueError:
                mbps = rtt_ms = 0.0
        tiers = [(_native.RingEngine.TIER_FLAT, self._next_lanes, self._prev_lanes)]
        for tid, tier in ((_native.RingEngine.TIER_ROW, self._row_tier),
                          (_native.RingEngine.TIER_COL, self._col_tier)):
            if tier is not None:
                tiers.append((tid, tier.next_lanes, tier.prev_lanes))
        try:
            eng = _native.RingEngine(self._lanes, mbps, rtt_ms)
            for tid, nexts, prevs in tiers:
                eng.set_tier(
                    tid,
                    [p.sock.fileno() for p in nexts],
                    [p.sock.fileno() for p in prevs],
                )
        except Exception as e:  # noqa: BLE001 — engine is an optimization
            if self._engine_mode == "native":
                _warn_native_fallback(f"engine construction failed: {e}")
            return None
        # The engine's hop recorder follows this collective's sampling /
        # ring-capacity config so both engines' timelines are comparable.
        try:
            eng.set_hop(self._hops.sample, self._hops.cap)
        except Exception:  # noqa: BLE001 — telemetry only
            pass
        # Re-point the byte-accounting surface at the native counters so
        # lane_stats, the shaped-link byte assertions, and the Manager's
        # GB/s telemetry are engine-agnostic.
        for tid, nexts, prevs in tiers:
            for lane, peer in enumerate(nexts):
                peer._native_bytes = (
                    lambda eng=eng, tid=tid, lane=lane: eng.link_bytes(tid, 0, lane)
                )
            for lane, peer in enumerate(prevs):
                peer._native_bytes = (
                    lambda eng=eng, tid=tid, lane=lane: eng.link_bytes(tid, 1, lane)
                )
            for direction, peers in ((0, nexts), (1, prevs)):
                shaper = peers[0].shaper if peers else None
                if shaper is not None:
                    self._wire_native_shaper_hooks(eng, shaper, tid, direction)
        return eng

    @staticmethod
    def _wire_native_shaper_hooks(eng, shaper: LinkShaper, tid: int, direction: int) -> None:
        """Points one LinkShaper's byte/wait reads at the native engine's
        pacer counters — the ONE wiring used at engine creation and by
        set_link_shaping's lazy attach, so the hook shape cannot drift
        between the two paths."""
        shaper._native_read = (
            lambda eng=eng, tid=tid, d=direction: eng.shaper_counters(tid, d)
        )
        shaper._native_wait = (
            lambda eng=eng, tid=tid, d=direction: eng.shaper_wait_s(tid, d)
        )

    # Channel ids in the 12-byte connection preamble (rank, channel, lane).
    # _CH_ROW/_CH_COL are the 2D topology's tier rings — distinct channels
    # (not just distinct tags) so the accept side can route each socket to
    # its tier's lane table and shaper.
    _CH_RING = 0
    _CH_P2P = 1
    _CH_ROW = 2
    _CH_COL = 3
    _PREAMBLE = struct.Struct("<III")

    def _rendezvous(self) -> None:
        listener = socket.create_server(("", 0), family=socket.AF_INET6, dualstack_ipv6=True)
        listener.listen(16 + 6 * self._lanes)
        self._listener = listener
        # Incarnation token: minted with the listener, republished by every
        # incremental configure.  (addr, token) equality across a quorum
        # transition is the proof the SAME process incarnation still holds
        # the far end of our lane sockets — an address alone could be a
        # respawn that recycled the ephemeral port.
        self._listener_token = os.urandom(8).hex()
        port = listener.getsockname()[1]
        host = socket.gethostname()
        self._self_addr = f"{host}:{port}"
        self._store.set(f"rank_{self._rank}", self._self_addr.encode())
        # Mode token: "full" tells neighbors our previous sockets are GONE
        # (abort() closed them) so they must not try to reuse the edge.
        self._store.set(
            f"cfg_{self._rank}", f"full:{self._listener_token}".encode()
        )

        n = self._world_size
        rank = self._rank
        lanes = self._lanes
        next_rank = (rank + 1) % n
        prev_rank = (rank - 1) % n
        # One serialization budget per peer DIRECTION, shared by every lane
        # of that direction: shaped benches cannot widen the modeled link by
        # adding lanes, and the direction's byte counters stay whole.  Each
        # 2D tier direction is a DIFFERENT physical peer link, so it gets
        # its own budget (matching per-neighbor DCN provisioning).
        next_shaper = LinkShaper.from_env()
        prev_shaper = LinkShaper.from_env()

        # 2D grid tiers: rank (r, c) on an R x C grid rendezvouses a row
        # ring (same r, all c) and a column ring (same c, all r) alongside
        # the flat ring.  Grid geometry derives from (world_size, rank)
        # alone, identically on every rank.
        self._row_tier = None
        self._col_tier = None
        tier_specs: List[tuple] = []  # (channel, tier, prev_shaper)
        if self._active_topology == "ring2d":
            rows, cols = _grid_shape(n)
            r, c = divmod(rank, cols)
            self._row_tier = _TierLinks(
                size=cols,
                ring_rank=c,
                next_rank=r * cols + (c + 1) % cols,
                prev_rank=r * cols + (c - 1) % cols,
            )
            self._col_tier = _TierLinks(
                size=rows,
                ring_rank=r,
                next_rank=((r + 1) % rows) * cols + c,
                prev_rank=((r - 1) % rows) * cols + c,
            )
            tier_specs = [
                (self._CH_ROW, self._row_tier, LinkShaper.from_env()),
                (self._CH_COL, self._col_tier, LinkShaper.from_env()),
            ]
        self._ring_prev_shaper = prev_shaper
        self._tier_prev_shapers = {ch: sh for ch, _t, sh in tier_specs}

        # Persistent accept loop: registers the per-lane ring links from
        # prev (flat and tier rings, keyed by channel) and any lazily-dialed
        # point-to-point links (used by checkpoint transports to move
        # weights between arbitrary replica pairs, the reference's
        # pg.send/recv path, torchft/checkpointing/pg_transport.py:197-301).
        # Keyed by LISTENER identity, not generation: an incremental
        # reconfigure bumps the generation but keeps this listener (and
        # this loop) alive across quorum transitions; prev-direction
        # shapers are read off the instance for the same reason.
        def accept_loop() -> None:
            while True:
                try:
                    conn, _ = listener.accept()
                except OSError:
                    return  # listener closed by abort()
                try:
                    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    # Accepted sockets must carry the op timeout too: a recv
                    # from a stalled-but-open peer has to surface as an error,
                    # not block an executor thread forever.
                    conn.settimeout(self._timeout)
                    peer = _Peer(conn)
                    their_rank, channel, lane = self._PREAMBLE.unpack(
                        peer._recv_exact(self._PREAMBLE.size)
                    )
                    if channel != self._CH_P2P and self._transport != "tcp":
                        self._shm_accept_handshake(peer, their_rank, channel, lane)
                    with self._accept_cond:
                        if self._listener is not listener:
                            conn.close()
                            return
                        if channel == self._CH_P2P:
                            self._peers[their_rank] = peer
                        else:
                            if channel == self._CH_RING:
                                peer.shaper = self._ring_prev_shaper
                            else:
                                peer.shaper = self._tier_prev_shapers.get(channel)
                            self._accepted_ring[(their_rank, channel, lane)] = peer
                        self._accept_cond.notify_all()
                except Exception:  # noqa: BLE001
                    conn.close()

        self._accepted_ring: dict[tuple, _Peer] = {}
        self._accept_thread = threading.Thread(target=accept_loop, daemon=True)
        self._accept_thread.start()

        # Dial our next neighbors, one connection per lane per ring.
        self._next_lanes = [
            self._dial_rank(next_rank, self._CH_RING, lane=lane, shaper=next_shaper)
            for lane in range(lanes)
        ]
        for channel, tier, _sh in tier_specs:
            tier_next_shaper = LinkShaper.from_env()
            tier.next_lanes = [
                self._dial_rank(tier.next_rank, channel, lane=lane, shaper=tier_next_shaper)
                for lane in range(lanes)
            ]

        # Wait for every prev-direction lane: the flat ring's, plus each
        # active tier's.
        expected = [(prev_rank, self._CH_RING, lane) for lane in range(lanes)]
        for channel, tier, _sh in tier_specs:
            expected += [(tier.prev_rank, channel, lane) for lane in range(lanes)]
        deadline = self.RENDEZVOUS_TIMEOUT_MS / 1000
        with self._accept_cond:
            ok = self._accept_cond.wait_for(
                lambda: all(key in self._accepted_ring for key in expected),
                timeout=deadline,
            )
            if not ok:
                missing = [key for key in expected if key not in self._accepted_ring]
                raise TimeoutError(
                    f"rendezvous: ring peers never connected: {missing}"
                )
            self._prev_lanes = [
                self._accepted_ring.pop((prev_rank, self._CH_RING, lane))
                for lane in range(lanes)
            ]
            for channel, tier, _sh in tier_specs:
                tier.prev_lanes = [
                    self._accepted_ring.pop((tier.prev_rank, channel, lane))
                    for lane in range(lanes)
                ]
        # Record each flat-ring neighbor's (addr, token) identity: the
        # evidence the NEXT configure compares to decide whether this
        # edge's sockets survived the membership delta.  Flat ring only —
        # ring2d transitions always take the full path.  Best-effort: a
        # missing identity just forces the full path next time.
        self._neighbor_ids = {}
        if self._active_topology == "ring":
            try:
                nxt = self._peer_identity(next_rank)
                prv = self._peer_identity(prev_rank)
                if nxt is not None and prv is not None:
                    self._neighbor_ids = {"next": nxt[:2], "prev": prv[:2]}
            except Exception:  # noqa: BLE001 — reuse hint only
                pass

    def _peer_identity(
        self, peer_rank: int, timeout_ms: int = 10_000
    ) -> Optional[tuple]:
        """``(addr, token, mode)`` published by ``peer_rank`` in the
        current store namespace — both keys are published before that
        rank's lanes could have connected, so the default short wait
        suffices for surviving neighbors; callers expecting a freshly
        restarted peer pass a rendezvous-scale budget."""
        addr = self._store.get(f"rank_{peer_rank}", wait=True, timeout_ms=timeout_ms)
        cfg = self._store.get(f"cfg_{peer_rank}", wait=True, timeout_ms=timeout_ms)
        if addr is None or cfg is None:
            return None
        mode, _, token = cfg.decode().partition(":")
        if not token:
            return None
        return (addr.decode(), token, mode)

    def _dial_rank(
        self,
        peer_rank: int,
        channel: int,
        timeout: Optional[float] = None,
        lane: int = 0,
        shaper: Optional[LinkShaper] = None,
    ) -> _Peer:
        timeout = timeout if timeout is not None else self.RENDEZVOUS_TIMEOUT_MS / 1000
        addr = self._store.get(
            f"rank_{peer_rank}", wait=True, timeout_ms=int(timeout * 1000)
        )
        if addr is None:
            raise TimeoutError(f"rendezvous: rank {peer_rank} never published its address")
        phost, pport = addr.decode().rsplit(":", 1)
        sock = socket.create_connection(
            (phost, int(pport)), timeout=min(self._timeout, timeout)
        )
        # create_connection's timeout would otherwise persist as the socket's
        # recv/send deadline; ops get the full op timeout.
        sock.settimeout(self._timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        peer = _Peer(sock, shaper=shaper)
        peer.sock.sendall(self._PREAMBLE.pack(self._rank, channel, lane))
        if channel != self._CH_P2P and self._transport != "tcp":
            self._shm_dial_handshake(peer, peer_rank)
        return peer

    # -- same-host shm lane negotiation -------------------------------------

    def _create_shm_segment(self, their_rank: int, channel: int, lane: int) -> tuple:
        """Creates one fresh /dev/shm segment for a same-host lane link:
        O_EXCL create (any stale leftover under the same name is unlinked
        first), sized header + ring capacity, initialized with the magic
        and a FRESH random generation token.  The token is what makes a
        dead peer's stale segment unattachable: the dialer verifies it
        against the value negotiated on THIS connection, so a leftover
        file from a crashed process can never be re-attached."""
        name = (
            f"tpuft-{os.getpid()}-g{self._generation}-r{their_rank}"
            f"to{self._rank}-c{channel}-l{lane}-{os.urandom(4).hex()}"
        )
        path = "/dev/shm/" + name
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
        cap = _SHM_RING_BYTES_DEFAULT
        fd = os.open(path, os.O_RDWR | os.O_CREAT | os.O_EXCL, 0o600)
        try:
            os.ftruncate(fd, _SHM_HDR + cap)
            token = int.from_bytes(os.urandom(8), "little") | 1
            os.pwrite(fd, struct.pack("<QQQQI", _SHM_MAGIC, token, 0, 0, 0), 0)
        except OSError:
            os.close(fd)
            try:
                os.unlink(path)
            except OSError:
                pass
            raise
        os.close(fd)
        return path, token

    def _shm_accept_handshake(
        self, peer: _Peer, their_rank: int, channel: int, lane: int
    ) -> None:
        """Acceptor side of the shm negotiation (runs in the accept loop,
        right after the preamble): read the dialer's boot-id; when it
        matches ours, create a fresh segment and offer (token, name); a
        positive ack arms this link's consumer role at engine-arm time."""
        (req,) = _SHM_REQ.unpack(bytes(peer._recv_exact(_SHM_REQ.size)))
        their_boot = req.rstrip(b"\x00")
        mine = _boot_id()
        flag, token, name, path = 0, 0, b"", None
        if mine and their_boot == mine:
            try:
                path, token = self._create_shm_segment(their_rank, channel, lane)
                name = os.path.basename(path).encode()
                flag = 1
            except OSError:
                flag, token, name, path = 0, 0, b"", None
        peer.sock.sendall(_SHM_REP.pack(flag, token, name))
        if not flag:
            return
        if bytes(peer._recv_exact(1)) != b"\x01":
            # Dialer could not attach (or refused): stay on tcp, reclaim
            # the segment now.
            try:
                os.unlink(path)
            except OSError:
                pass
            return
        peer._shm_pending = (path, token, "rx")
        with self._shm_lock:
            self._shm_paths.add(path)

    def _shm_dial_handshake(self, peer: _Peer, peer_rank: int) -> None:
        """Dialer side: send our boot-id; on a same-host offer, verify the
        segment's magic + generation token BEFORE acking (a stale segment
        from a dead peer is refused here) and record the producer role."""
        peer.sock.sendall(_SHM_REQ.pack(_boot_id()))
        flag, token, name = _SHM_REP.unpack(bytes(peer._recv_exact(_SHM_REP.size)))
        if not flag:
            if self._transport == "shm":
                raise ConnectionError(
                    f"TPUFT_RING_TRANSPORT=shm but rank {peer_rank} offered no "
                    "same-host segment (different host, unreadable boot-id, or "
                    "segment creation failed); use transport='auto' for mixed "
                    "placements"
                )
            return
        path = "/dev/shm/" + name.rstrip(b"\x00").decode()
        try:
            fd = os.open(path, os.O_RDWR)
            try:
                magic, tok = struct.unpack("<QQ", os.pread(fd, 16, 0))
            finally:
                os.close(fd)
            if magic != _SHM_MAGIC or tok != token:
                raise ConnectionError(
                    "stale shm segment (generation mismatch) — refusing to attach"
                )
        except Exception:
            peer.sock.sendall(b"\x00")
            if self._transport == "shm":
                raise
            return
        peer.sock.sendall(b"\x01")
        peer._shm_pending = (path, token, "tx")
        with self._shm_lock:
            self._shm_paths.add(path)

    def _arm_shm_links(self) -> None:
        """Applies every rendezvous-negotiated segment to whichever engine
        this configuration runs: the native engine maps segments itself
        (set_shm — its WriteAll/ReadExact then route through the ring),
        the Python engine arms the peers' _ShmRing producer/consumer
        halves.  Called under _lock right after _create_engine."""
        specs = [(0, 0, self._next_lanes), (0, 1, self._prev_lanes)]
        for tid, tier in ((1, self._row_tier), (2, self._col_tier)):
            if tier is not None:
                specs += [(tid, 0, tier.next_lanes), (tid, 1, tier.prev_lanes)]
        self._shm_links = 0
        for tid, direction, peers in specs:
            for lane, peer in enumerate(peers):
                if peer._shm_pending is None:
                    continue
                # Reused (incremental-reconfigure) peers on the Python
                # engine are already armed — their _ShmRing halves map the
                # kept segment and stay valid across generations.
                if self._engine is None and (
                    peer._shm_tx is not None or peer._shm_rx is not None
                ):
                    self._shm_links += 1
                    continue
                path, token, role = peer._shm_pending
                try:
                    if self._engine is not None:
                        self._engine.set_shm(tid, direction, lane, path, token)
                    elif role == "tx":
                        peer._shm_tx = _ShmRing(path, token, peer.sock)
                    else:
                        peer._shm_rx = _ShmRing(path, token, peer.sock)
                except Exception:
                    if self._transport == "shm":
                        raise
                    continue
                self._shm_links += 1

    def _dial(self, peer_rank: int) -> _Peer:
        """Point-to-point link for send/recv to an arbitrary rank.  Exactly
        one side dials (the lower rank); concurrent callers on the dialing
        side coalesce onto one socket per pair.  If the elected dialer fails,
        a waiter takes over; a reconfigure mid-dial invalidates the attempt
        (generation guard) so stale sockets never cross quorum boundaries."""
        deadline = time.monotonic() + self._timeout
        while True:
            with self._accept_cond:
                gen = self._generation
                peer = self._peers.get(peer_rank)
                if peer is not None:
                    return peer
                if self._rank < peer_rank and peer_rank not in self._dialing:
                    self._dialing.add(peer_rank)
                    break  # we are the dialer
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"no point-to-point link to rank {peer_rank} within timeout"
                    )
                if self._rank < peer_rank:
                    # Wake when the link lands, the dialer gives up, or a
                    # reconfigure invalidates this generation.
                    pred = lambda: (
                        peer_rank in self._peers
                        or peer_rank not in self._dialing
                        or self._generation != gen
                    )
                else:
                    pred = lambda: (
                        peer_rank in self._peers or self._generation != gen
                    )
                self._accept_cond.wait_for(pred, timeout=remaining)
                if self._generation != gen:
                    raise RuntimeError("collective reconfigured during dial")
        try:
            # Honor the remaining op budget, not the full rendezvous window:
            # a caller's timeout covers election + dial together.
            peer = self._dial_rank(
                peer_rank,
                self._CH_P2P,
                timeout=max(0.1, deadline - time.monotonic()),
            )
        except Exception:
            with self._accept_cond:
                self._dialing.discard(peer_rank)
                self._accept_cond.notify_all()
            raise
        with self._accept_cond:
            if self._generation != gen:
                self._dialing.discard(peer_rank)
                self._accept_cond.notify_all()
                peer.close()
                raise RuntimeError("collective reconfigured during dial")
            self._peers[peer_rank] = peer
            self._dialing.discard(peer_rank)
            self._accept_cond.notify_all()
        return peer

    def abort(self) -> None:
        with self._lock:
            if self._error is None:
                self._error = RuntimeError("collective aborted")
            # Bank the closing generation's wire/hop counters BEFORE the
            # lanes are torn down: lane_stats zeroes on every configure(),
            # and the cumulative exposition (lane_totals / the worker
            # /metrics endpoint) must never go backwards.  The native
            # engine is still alive here, so its counters are readable.
            self._bank_locked()
            with self._accept_cond:
                peers = list(self._peers.values()) + list(self._accepted_ring.values())
                self._peers = {}
                self._accepted_ring = {}
                # Invalidate in-flight dials: a dial completing after this
                # point must not register its socket into the next
                # generation's peer table.
                self._generation += 1
                self._dialing = set()
                self._accept_cond.notify_all()
            tiers = [t for t in (self._row_tier, self._col_tier) if t is not None]
            tier_peers = [p for t in tiers for p in t.peers()]
            for peer in self._next_lanes + self._prev_lanes + tier_peers + peers:
                if peer is not None:
                    peer.close()
            if self._listener is not None:
                self._listener.close()
                self._listener = None
            # The listener (and its incarnation token) is dead: no edge of
            # ours can be reused by the next transition.
            self._neighbor_ids = {}
            self._self_addr = None
            self._next_lanes = []
            self._prev_lanes = []
            # Unlink every negotiated shm segment (both ends track every
            # path, so the survivor of a peer crash reclaims it; a second
            # unlink is a harmless ENOENT).  The native engine's mappings
            # survive until its close() below — unlink only removes the
            # name.
            with self._shm_lock:
                shm_paths, self._shm_paths = list(self._shm_paths), set()
            self._shm_links = 0
            for sp in shm_paths:
                try:
                    os.unlink(sp)
                except OSError:
                    pass
            if self._executor is not None:
                self._executor.shutdown(wait=False, cancel_futures=True)
                self._executor = None
            if self._ring_executor is not None:
                self._ring_executor.shutdown(wait=False, cancel_futures=True)
                self._ring_executor = None
            if self._lane_executor is not None:
                self._lane_executor.shutdown(wait=False, cancel_futures=True)
                self._lane_executor = None
            for pool in self._send_pools:
                pool.shutdown(wait=False, cancel_futures=True)
            self._send_pools = []
            for tier in tiers:
                for pool in tier.send_pools:
                    pool.shutdown(wait=False, cancel_futures=True)
                tier.send_pools = []
                tier.next_lanes = []
                tier.prev_lanes = []
            self._row_tier = None
            self._col_tier = None
            if self._store is not None:
                self._store.close()
                self._store = None
            engine, self._engine = self._engine, None
            inflight, self._inflight = list(self._inflight), set()
        # Outside the lock: the engine close briefly drains in-flight native
        # ops (they wake instantly — every socket was just shut down), and
        # failing a future runs its done-callbacks inline.
        if engine is not None:
            engine.close()
        err = RuntimeError("collective aborted")
        for fut in inflight:
            if not fut.done():
                try:
                    fut.set_exception(err)
                except Exception:  # noqa: BLE001 — racing completion
                    pass

    def errored(self) -> Optional[Exception]:
        """Reports latched operation failures; cleared by configure()."""
        with self._lock:
            return self._op_error

    def _latch(self, exc: Exception) -> None:
        with self._lock:
            if self._op_error is None:
                self._op_error = exc

    def size(self) -> int:
        return self._world_size

    def rank(self) -> int:
        return self._rank

    # -- ops ----------------------------------------------------------------

    def _submit(self, fn: Callable[[], object], ring: bool = True) -> Work:
        if self._world_size == 1:
            try:
                return Work(completed_future(fn()))
            except Exception as e:  # noqa: BLE001
                self._latch(e)
                return Work(failed_future(e))
        with self._lock:
            executor = self._ring_executor if ring else self._executor
        if executor is None:
            err = self._op_error or RuntimeError("collective not configured")
            return Work(failed_future(err))

        times = [0, 0]

        def run() -> object:
            times[0] = time.monotonic_ns()
            try:
                return fn()
            except Exception as e:  # noqa: BLE001
                self._latch(e)
                raise
            finally:
                times[1] = time.monotonic_ns()

        return Work(executor.submit(run), times)

    def _next_seq(self) -> int:
        """Ring-op sequence number, allocated at call time so identical
        program order on every rank yields identical tag blocks."""
        with self._op_seq_lock:
            seq = self._op_seq
            self._op_seq += 1
        return seq

    def _tag_base(self, seq: int, stripe: int = 0) -> int:
        return (seq * _TAGS_PER_OP + stripe * _TAGS_PER_STRIPE) & 0x7FFFFFFF

    @property
    def topology(self) -> str:
        """The topology the CURRENT configuration resolved to ("ring" or
        "ring2d") — "auto" and degenerate worlds (primes, N < crossover)
        report what actually runs."""
        return self._active_topology

    def _tier_id(self, tier: Optional[_TierLinks]) -> int:
        """The native-engine tier id (0 flat / 1 row / 2 col) for a ring
        loop's ``tier`` argument — the tier key hop records carry."""
        if tier is None:
            return 0
        return 1 if tier is self._row_tier else 2

    def _record_hop(self, tier: Optional[_TierLinks], lane: int, tag: int,
                    hop: dict, comb_s: float = 0.0) -> None:
        """Commits one Python-orchestrated hop (the dict ``_exchange``
        filled) into the recorder."""
        self._hops.record(
            self._tier_id(tier),
            lane,
            tag,
            hop.get("send_s", 0.0),
            hop.get("recv_s", 0.0),
            comb_s,
            hop.get("nbytes", 0),
            hop.get("ts", 0.0),
        )

    def _hop_stats_tier(self, tier_id: int) -> dict:
        """Merged per-tier hop aggregates: Python-orchestrated hops from
        the local recorder plus (under the native engine) the ring passes
        recorded inside ring.cc — ONE engine-agnostic surface."""
        s = self._hops.stats(tier_id)
        eng = self._engine
        if eng is not None:
            try:
                ns = eng.hop_stats(tier_id)
                s = {k: s[k] + ns[k] for k in s}
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        return s

    def _tier_shape_s(self, tier: Optional[_TierLinks]) -> float:
        """Shaping sleep charged to one tier's next direction (sends pace
        outbound only)."""
        peers = tier.next_lanes if tier is not None else self._next_lanes
        shaper = peers[0].shaper if peers else None
        return float(shaper.wait_s) if shaper is not None else 0.0

    def hop_records(self) -> List[dict]:
        """The retained data-plane hop timeline (both engines' records
        merged, oldest first) — dicts with exactly HOP_RECORD_FIELDS.
        Bounded by TPUFT_HOP_RING per engine; sampled per
        TPUFT_HOP_SAMPLE.  ``obs/trace.py`` renders this as the per-lane
        data-plane Perfetto track."""
        recs = self._hops.records()
        eng = self._engine
        if eng is not None:
            try:
                recs = recs + eng.hop_records(self._hops.cap)
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        recs.sort(key=lambda r: r.get("ts", 0.0))
        return recs

    def _live_counters(self) -> dict:
        """Current-generation cumulative counters in lane_totals' shape."""
        tiers: Dict[str, dict] = {}
        hops: Dict[str, dict] = {}
        specs = [("flat", None, self._next_lanes, self._prev_lanes)]
        for name, tier in (("row", self._row_tier), ("col", self._col_tier)):
            if tier is not None:
                specs.append((name, tier, tier.next_lanes, tier.prev_lanes))
        for name, tier, nexts, prevs in specs:
            tiers[name] = {
                "sent_bytes": sum(p.bytes_out for p in list(nexts)),
                "recv_bytes": sum(p.bytes_in for p in list(prevs)),
            }
            tid = self._tier_id(tier)
            hops[name] = dict(self._hop_stats_tier(tid))
            hops[name]["shape_s"] = self._tier_shape_s(tier)
        return {
            "sent_bytes": sum(t["sent_bytes"] for t in tiers.values()),
            "recv_bytes": sum(t["recv_bytes"] for t in tiers.values()),
            "tiers": tiers,
            "hops": hops,
        }

    def _bank_locked(self) -> None:
        """Folds the current generation's counters into the lifetime bank
        (caller holds _lock; called by abort() before lane teardown)."""
        if not self._next_lanes:
            return  # nothing configured this generation
        try:
            live = self._live_counters()
        except Exception:  # noqa: BLE001 — telemetry must not fail abort
            return
        bank = self._lifetime
        bank["reconfigures"] = int(bank.get("reconfigures", 0)) + 1
        bank["sent_bytes"] = int(bank.get("sent_bytes", 0)) + live["sent_bytes"]
        bank["recv_bytes"] = int(bank.get("recv_bytes", 0)) + live["recv_bytes"]
        tiers = bank.setdefault("tiers", {})
        for name, t in live["tiers"].items():
            slot = tiers.setdefault(name, {"sent_bytes": 0, "recv_bytes": 0})
            slot["sent_bytes"] += t["sent_bytes"]
            slot["recv_bytes"] += t["recv_bytes"]
        hops = bank.setdefault("hops", {})
        for name, h in live["hops"].items():
            slot = hops.setdefault(
                name,
                {"hops": 0, "send_block_s": 0.0, "recv_wait_s": 0.0,
                 "combine_s": 0.0, "shape_s": 0.0},
            )
            for k in slot:
                slot[k] += h.get(k, 0)
        # The native engine (and its hop timeline) dies with this
        # generation — fold its retained records into the Python ring so
        # a post-abort dump (Manager shutdown after a fault) still holds
        # the hops leading up to the failure.
        eng = self._engine
        if eng is not None:
            try:
                for rec in eng.hop_records(self._hops.cap):
                    self._hops.keep(rec)
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        # The recorder's AGGREGATES are now IN the bank; without this
        # reset a lane_totals() read in the abort->configure window (or
        # after shutdown, forever) would add them a second time — the
        # banked hops would read ~2x and then DROP later, the exact
        # backwards-counter regression the bank exists to prevent.  The
        # TIMELINE stays: it is never summed into the bank, and it is the
        # black box the fault-path hop dump reads.  (The byte counters
        # need no equivalent: the peers carrying them are cleared by
        # abort() itself.)
        self._hops.reset_aggregates()

    def lane_totals(self) -> dict:
        """MONOTONIC cumulative wire/hop counters across reconfigures:
        the lifetime bank (every closed generation, banked at abort())
        plus the live generation.  This is what any scrape-visible
        exposition of lane counters must read — ``lane_stats()`` resets on
        every configure(), so exporting it directly would show Prometheus
        counters going backwards across quorum reconfigurations.

        Never blocks a scrape on the collective's big lock: configure()
        holds it across the full network rendezvous (up to the connect
        timeout when a peer is dead — exactly the fault windows telemetry
        exists to explain), so a contended read degrades to the BANK-ONLY
        snapshot (last closed generations; monotonic, slightly stale)
        instead of hanging the /metrics endpoint."""
        acquired = self._lock.acquire(timeout=0.5)
        try:
            bank = self._lifetime
            if not acquired:
                live = {"sent_bytes": 0, "recv_bytes": 0, "tiers": {}, "hops": {}}
            else:
                try:
                    live = self._live_counters()
                except Exception:  # noqa: BLE001
                    live = {"sent_bytes": 0, "recv_bytes": 0, "tiers": {},
                            "hops": {}}
            out = {
                "reconfigures": int(bank.get("reconfigures", 0)),
                "sent_bytes": int(bank.get("sent_bytes", 0)) + live["sent_bytes"],
                "recv_bytes": int(bank.get("recv_bytes", 0)) + live["recv_bytes"],
                "tiers": {},
                "hops": {},
            }
            names = set(live["tiers"]) | set(bank.get("tiers", {}))
            for name in names:
                b = (bank.get("tiers") or {}).get(name, {})
                l = live["tiers"].get(name, {})
                out["tiers"][name] = {
                    "sent_bytes": int(b.get("sent_bytes", 0)) + int(l.get("sent_bytes", 0)),
                    "recv_bytes": int(b.get("recv_bytes", 0)) + int(l.get("recv_bytes", 0)),
                }
            names = set(live["hops"]) | set(bank.get("hops", {}))
            for name in names:
                b = (bank.get("hops") or {}).get(name, {})
                l = live["hops"].get(name, {})
                out["hops"][name] = {
                    k: (b.get(k, 0) or 0) + (l.get(k, 0) or 0)
                    for k in ("hops", "send_block_s", "recv_wait_s",
                              "combine_s", "shape_s")
                }
            return out
        finally:
            if acquired:
                self._lock.release()

    def set_link_shaping(self, mbps: float, rtt_ms: float,
                         direction: str = "next", tier: str = "flat") -> None:
        """Re-shapes ONE peer direction's modeled link mid-run, in
        whichever engine owns the pacing — the slow-link bench's
        fault injector (a real deployment's analogue is the physical link
        degrading; no reconfigure happens either way)."""
        tid = {"flat": 0, "row": 1, "col": 2}[tier]
        t = {"flat": None, "row": self._row_tier, "col": self._col_tier}[tier]
        if t is None:
            peers = self._next_lanes if direction == "next" else self._prev_lanes
        else:
            peers = t.next_lanes if direction == "next" else t.prev_lanes
        shared: Optional[LinkShaper] = None
        for p in peers:
            if p.shaper is None:
                # mbps <= 0 means "disable pacing"; with no shaper attached
                # there is nothing to disable — and constructing one with a
                # zero rate would divide the next send by zero.
                if mbps <= 0:
                    continue
                if shared is None:
                    shared = LinkShaper(mbps, rtt_ms)
                p.shaper = shared
            else:
                p.shaper.set_rate(mbps, rtt_ms)
        eng = self._engine
        if eng is not None:
            d = 0 if direction == "next" else 1
            try:
                eng.set_shaper(tid, d, mbps, rtt_ms)
                # A collective configured UNSHAPED never wired the
                # native-counter hooks (_create_engine only hooks shapers
                # that existed at configure) — without them the freshly
                # attached Python shaper would read its own zeros while
                # the native pacer does the sleeping, and the shaping
                # bucket of link_attribution would silently read 0.
                sh = peers[0].shaper if peers else None
                if sh is not None and sh._native_wait is None:
                    self._wire_native_shaper_hooks(eng, sh, tid, d)
            except Exception:  # noqa: BLE001
                pass

    def lane_stats(self) -> dict:
        """Per-lane wire-byte counters for the current configuration:
        ``{"lanes": L, "topology": ..., "sent": [bytes per next-lane],
        "recv": [bytes per prev-lane]}``, plus a ``"tiers"`` map with the
        same sent/recv counters per 2D tier ("row"/"col", with each tier's
        ring size) when the hierarchical topology is active — the per-tier
        attribution that keeps step_summary's byte accounting comparable
        across topologies.  Cumulative since the last configure(); feeds
        the Manager's allreduce GB/s telemetry and the bench artifacts."""
        nexts, prevs = list(self._next_lanes), list(self._prev_lanes)
        out = {
            "lanes": self._lanes,
            "topology": self._active_topology,
            "engine": self.ring_engine,
            "sent": [p.bytes_out for p in nexts],
            "recv": [p.bytes_in for p in prevs],
        }
        tiers = {}
        for name, tier in (("row", self._row_tier), ("col", self._col_tier)):
            if tier is not None:
                tiers[name] = {
                    "size": tier.size,
                    "sent": [p.bytes_out for p in list(tier.next_lanes)],
                    "recv": [p.bytes_in for p in list(tier.prev_lanes)],
                }
        if tiers:
            out["tiers"] = tiers
        # Data-plane hop telemetry: per-tier stall aggregates (both
        # engines merged) + shaping sleep — rides step_summary's
        # allreduce_lanes into obs.report's link_attribution split and the
        # Manager's per-neighbor link health estimate.
        hops = {"flat": dict(self._hop_stats_tier(0))}
        hops["flat"]["shape_s"] = self._tier_shape_s(None)
        for name, tier in (("row", self._row_tier), ("col", self._col_tier)):
            if tier is not None:
                hops[name] = dict(self._hop_stats_tier(self._tier_id(tier)))
                hops[name]["shape_s"] = self._tier_shape_s(tier)
        out["hops"] = hops
        return out

    # Wire codecs this collective's allreduce accepts (see WIRE_CODECS).
    wire_codecs = WIRE_CODECS

    def allreduce(
        self,
        arrays: Sequence[np.ndarray],
        op: str = "sum",
        allow_wire_compression: bool = True,
        wire_codec: Optional[str] = None,
        donate: bool = False,
    ) -> Work:
        """``donate=True`` hands the input buffers to the op: the caller
        promises not to read them again, so the native engine may reduce IN
        PLACE over them (zero-copy — no defensive working-buffer memcpy)
        and the results may alias the inputs.  Safe for temporaries and for
        staging buffers overwritten before the next round (the DDP wire
        stage); the Python engine ignores the hint (it never mutates
        inputs), so results are bitwise-identical either way."""
        # Validate BEFORE the world-size-1 fast path: a typo'd op must fail
        # on a single-replica config too, not only after scaling up.
        if op not in _REDUCE_COMBINE:
            return Work(failed_future(_bad_reduce_op(op)))
        if wire_codec is not None:
            if wire_codec not in WIRE_CODECS:
                return Work(
                    failed_future(
                        ValueError(
                            f"unsupported wire_codec {wire_codec!r}; expected "
                            f"one of {WIRE_CODECS}"
                        )
                    )
                )
            # int8 quantization of integer payloads would corrupt them the
            # same way the bf16 gate guards against — codecs are float-only.
            # (_is_bf16: bfloat16 is floating but not an np.floating
            # subtype — see the helper's docstring.)
            if not all(
                np.issubdtype(np.asarray(a).dtype, np.floating)
                or _is_bf16(np.asarray(a).dtype)
                for a in arrays
            ):
                return Work(
                    failed_future(
                        ValueError(
                            f"wire_codec={wire_codec!r} requires floating "
                            "inputs"
                        )
                    )
                )
        arrays = [np.ascontiguousarray(a) for a in arrays]
        if self._world_size == 1:
            return Work(completed_future(list(arrays)))
        seq = self._next_seq()
        if self._active_topology == "ring2d":
            if self._lanes > 1:
                return self._striped_hier_allreduce(
                    arrays, op, allow_wire_compression, seq, codec=wire_codec,
                    donate=donate,
                )
            return self._submit(
                lambda: self._hier_allreduce(
                    arrays, op, allow_wire_compression, seq, codec=wire_codec,
                    donate=donate,
                )
            )
        if self._lanes > 1:
            return self._striped_allreduce(
                arrays, op, allow_wire_compression, seq, codec=wire_codec,
                donate=donate,
            )
        return self._submit(
            lambda: self._ring_allreduce(
                arrays, op, allow_wire_compression, seq, codec=wire_codec,
                donate=donate,
            )
        )

    def _exchange(self, tag: int, payload, lane: int = 0,
                  tier: Optional[_TierLinks] = None,
                  hop: Optional[dict] = None) -> bytes:
        """Sends to the next neighbor while receiving from the previous one,
        on the given lane's socket pair (of the flat ring, or of ``tier``
        when a 2D tier ring is passed).  Full-duplex is required: with
        payloads larger than the kernel socket buffers, blocking
        send-then-recv deadlocks the ring.  The send runs on the lane's
        persistent sender worker — a striped allreduce makes hundreds of
        hops per op, and a fresh thread per hop is pure scheduler churn.
        One worker per lane serializes sends exactly like the peer's
        send_lock already does, so ordering is unchanged.

        ``hop`` (optional, a mutable dict) is filled with the hop's
        timing split — ``ts`` (wall clock at start), ``recv_s`` (blocked
        on the inbound frame), ``send_s`` (additional wait joining the
        send after the recv returned), ``nbytes`` (payload bytes sent) —
        the data-plane flight recorder's feed.  Over the native socket
        layer the engine's exchange blocks for recv AND send together, so
        the whole wait lands in ``recv_s`` (documented coarse split for
        Python-orchestrated control ops; the ring hot loop's native hops
        are split natively inside ring.cc)."""
        if hop is not None:
            hop["ts"] = time.time()
        engine = self._engine
        if engine is not None:
            # Native path: the engine's per-link sender thread + demux do
            # the full-duplex work GIL-free; all ring-lane socket reads go
            # through its one stash, so native ring passes and Python-
            # orchestrated ops (this path) can interleave on one lane.
            tier_id = 0 if tier is None else (1 if tier is self._row_tier else 2)
            if isinstance(payload, (list, tuple)):
                payload = b"".join(bytes(p) for p in payload)
            elif not isinstance(payload, bytes):
                payload = bytes(payload)
            t0 = time.monotonic()
            out = engine.exchange(tier_id, lane, tag, payload, self._timeout)
            if hop is not None:
                hop["recv_s"] = time.monotonic() - t0
                hop["send_s"] = 0.0
                hop["nbytes"] = len(payload)
            return out
        if tier is not None:
            nxt = tier.next_lanes[lane]
            prv = tier.prev_lanes[lane]
            pools = tier.send_pools
        else:
            nxt = self._next_lanes[lane]
            prv = self._prev_lanes[lane]
            pools = self._send_pools
        if not pools:
            raise RuntimeError("collective aborted")
        if isinstance(payload, (bytes, bytearray)):
            payload = memoryview(payload)
        nbytes = (
            sum(len(p) for p in payload)
            if isinstance(payload, (list, tuple))
            else len(payload)
        )
        sent = pools[lane].submit(nxt.send_msg, tag, payload)
        # A recv error propagates as-is (matching the old join-then-drop
        # behavior); the in-flight send fails on its own when _fail_ring /
        # abort closes the lane sockets.
        t0 = time.monotonic()
        received = prv.recv_msg(tag)
        t1 = time.monotonic()
        sent.result(timeout=self._timeout)
        if hop is not None:
            hop["recv_s"] = t1 - t0
            hop["send_s"] = time.monotonic() - t1
            hop["nbytes"] = nbytes
        return received

    @property
    def wire_dtype(self) -> str:
        """The resolved wire encoding ("f32" or "bf16").  Public so the
        data-plane layers above (GradientAverager's device wire prep) can
        cast payloads to the wire dtype ON DEVICE and fetch half the bytes
        — planning that cast requires knowing what this collective would
        put on the wire anyway."""
        return self._wire_dtype

    def wire_nbytes(
        self,
        array,
        allow_wire_compression: bool = True,
        wire_codec: Optional[str] = None,
    ) -> int:
        """Bytes ``array`` would occupy PER HOP on the ring wire — the
        single source of truth for wire-byte telemetry (the Manager's
        allreduce_gb_per_s gauge), so a change to ``_wire_for``'s gating
        cannot silently diverge from what the accounting counts.  With
        ``wire_codec="int8"`` floating payloads count 1 byte per element
        plus the per-frame scale header (~0.25x the f32 wire); with
        ``"int4"`` they count the PACKED nibble bytes — ceil(n/2) plus
        the scale header (~0.125x) — never the int8 frame width."""
        array = np.asarray(array)
        is_float = (
            np.issubdtype(array.dtype, np.floating) or _is_bf16(array.dtype)
        )
        if wire_codec == "int8" and is_float:
            return int(array.size) + _INT8_SCALE.size
        if wire_codec == "int4" and is_float:
            return (int(array.size) + 1) // 2 + _INT8_SCALE.size
        wire, _ = self._wire_for([array], array.dtype, allow_wire_compression)
        if wire is not None:
            return int(array.size) * wire.itemsize
        return int(array.nbytes)

    def _wire_for(
        self, arrays: Sequence[np.ndarray], flat_dtype, allow_wire_compression: bool
    ):
        """``(wire, acc_dtype)`` for one allreduce.

        ``wire`` is bfloat16 when compression is allowed, configured, and
        EVERY input array is floating (not just the promoted buffer dtype)
        — a mixed [f32, int64] call promotes flat to float64, and
        quantizing the integer values would corrupt them.  ``acc_dtype`` is
        the local accumulation dtype (the input dtype normally).

        Inputs that arrive ALREADY in the wire dtype (a device-wire-prepped
        bucket fetched as bf16) keep bf16 on the wire but accumulate in
        float32: per-hop bytes are identical to the host-cast path, and the
        reduction runs at the same precision — only the quantization point
        moved from host CPU to the device epilogue.  Without the explicit
        ``_is_bf16`` branch these payloads would fall through the
        ``np.issubdtype(..., np.floating)`` gate (bf16 is not a numpy
        floating subtype) into raw-bytes framing with bf16 accumulation."""
        if allow_wire_compression and self._wire_dtype == "bf16":
            if np.issubdtype(flat_dtype, np.floating) and all(
                np.issubdtype(a.dtype, np.floating) for a in arrays
            ):
                import ml_dtypes

                return np.dtype(ml_dtypes.bfloat16), np.dtype(flat_dtype)
            if _is_bf16(flat_dtype) and all(_is_bf16(a.dtype) for a in arrays):
                import ml_dtypes

                return np.dtype(ml_dtypes.bfloat16), np.dtype(np.float32)
        return None, np.dtype(flat_dtype)

    def _codec(self, wire, acc_dtype, codec: Optional[str] = None):
        """(encode, decode) for one ring pass: encode casts to the wire
        dtype and frames raw bytes (as_u8, not memoryview.cast, so
        ml_dtypes payloads like bfloat16 frame correctly); decode upcasts
        back to the accumulation dtype.

        ``codec="int8"`` supersedes ``wire``: each frame is a 4-byte f32
        scale followed by int8 values (scale = chunk amax / 127, symmetric
        round-to-nearest).  Accumulation stays in ``acc_dtype`` — each
        reduce-scatter hop decodes, sums full-width, and requantizes with
        its own scale, exactly the bf16 wire's per-hop quantization shape;
        the allgather phase quantizes each owned chunk once and forwards
        the scale+payload bytes verbatim, so every rank decodes
        bitwise-identical results (the commit protocol's premise)."""
        from torchft_tpu.checkpointing.serialization import as_u8

        if codec == "int8":
            def encode(chunk: np.ndarray):
                scale, q = quantize_int8(chunk)
                return [_INT8_SCALE.pack(scale), memoryview(as_u8(q))]

            def decode(raw, n: Optional[int] = None) -> np.ndarray:
                (scale,) = _INT8_SCALE.unpack_from(raw, 0)
                q = np.frombuffer(raw, dtype=np.int8, offset=_INT8_SCALE.size)
                return (q.astype(np.float32) * np.float32(scale)).astype(
                    acc_dtype, copy=False
                )

            return encode, decode

        if codec == "int4":
            # Same frame shape as int8 (4-byte f32 scale + payload) with
            # the payload packed two signed nibbles per byte — 0.125x the
            # f32 wire, bitwise-identical to native/src/ring.cc's
            # Int4Encode frames.  A packed frame of k bytes holds 2k-1 or
            # 2k elements, so decode takes the expected element count from
            # the caller (the ring always knows its chunk geometry).
            def encode(chunk: np.ndarray):
                scale, q = quantize_int4(chunk)
                return [_INT8_SCALE.pack(scale), memoryview(pack_int4(q))]

            def decode(raw, n: Optional[int] = None) -> np.ndarray:
                nbytes = len(raw) - _INT8_SCALE.size
                if n is None:
                    n = nbytes * 2
                (scale,) = _INT8_SCALE.unpack_from(raw, 0)
                q = unpack_int4(memoryview(raw)[_INT8_SCALE.size:], n)
                return (q.astype(np.float32) * np.float32(scale)).astype(
                    acc_dtype, copy=False
                )

            return encode, decode

        def encode(chunk: np.ndarray) -> memoryview:
            if wire is not None:
                chunk = chunk.astype(wire)
            return memoryview(as_u8(chunk))

        def decode(raw, n: Optional[int] = None) -> np.ndarray:
            if wire is not None:
                return np.frombuffer(raw, dtype=wire).astype(acc_dtype)
            return np.frombuffer(raw, dtype=acc_dtype)

        return encode, decode

    # -- native engine dispatch --------------------------------------------

    def _native_wire_mode(
        self, flat_dtype, wire, acc_dtype, codec: Optional[str]
    ) -> Optional[int]:
        """The native engine's wire mode for one allreduce, or None when
        this payload stays on the Python orchestration (no engine, or a
        payload outside the native fast path: integer/f64 accumulation,
        bf16 raw framing, codecs over non-f32 buffers).  The fallback is
        per-op and silent — it still rides the engine's socket layer via
        _exchange, so the demux stays unified."""
        if self._engine is None:
            return None
        if codec is not None:
            if codec not in ("int8", "int4"):
                return None
            return (
                (_NATIVE_WIRE_INT8 if codec == "int8" else _NATIVE_WIRE_INT4)
                if np.dtype(flat_dtype) == np.float32
                and np.dtype(acc_dtype) == np.float32
                else None
            )
        if wire is not None:
            # bf16 wire: f32 accumulation covers both f32 inputs and
            # device-prepped bf16 inputs (upcast is lossless).
            return _NATIVE_WIRE_BF16 if np.dtype(acc_dtype) == np.float32 else None
        return _NATIVE_WIRE_RAW if np.dtype(flat_dtype) == np.float32 else None

    def _native_buffer(self, flat: np.ndarray, fresh: bool = False) -> np.ndarray:
        """The f32 working buffer a native pass mutates IN PLACE — never a
        caller input (the ring never mutates its inputs); bf16 payloads
        upcast losslessly and _unflatten's astype casts back.  ``fresh``
        marks a flat buffer _flatten just ALLOCATED (the multi-array
        concatenate path), which the pass may therefore mutate directly —
        skipping the defensive copy saves a full memcpy per bucket on the
        hot path."""
        if flat.dtype == np.float32:
            return flat if fresh else flat.copy()
        return flat.astype(np.float32)

    def _native_pass_views(
        self,
        views: List[np.ndarray],
        tier_id: int,
        lane: int,
        n: int,
        rank: int,
        tag_base: int,
        rs_sub: int,
        ag_sub: int,
        pass_mode: int,
        op: str,
        wire_mode: int,
    ) -> None:
        """One GIL-free ring pass over contiguous f32 views of the working
        buffer.  The views' addresses go straight to the engine (zero-copy
        scatter-gather I/O over them); the GIL is released for the whole
        pass — this call IS the native hot loop."""
        engine = self._engine
        if engine is None:
            raise RuntimeError("collective aborted")
        engine.ring_pass(
            tier_id,
            lane,
            n,
            rank,
            tag_base,
            rs_sub,
            ag_sub,
            pass_mode,
            _NATIVE_OP[op],
            wire_mode,
            [int(v.ctypes.data) for v in views],
            [int(v.size) for v in views],
            self._timeout,
        )

    def _native_flat_pass(
        self, buf: np.ndarray, lane: int, tag_base: int, op: str, wire_mode: int
    ) -> None:
        """Full flat-ring pass (reduce-scatter + allgather) over ``buf`` in
        place — the native counterpart of one _ring_rs_ag over
        np.array_split(buf, world)."""
        self._native_pass_views(
            list(np.array_split(buf, self._world_size)),
            0,
            lane,
            self._world_size,
            self._rank,
            tag_base,
            _SUB_RS,
            _SUB_AG,
            _NATIVE_PASS_FULL,
            op,
            wire_mode,
        )

    def _native_hier_pass(
        self, buf: np.ndarray, lane: int, tag_base: int, op: str, wire_mode: int
    ) -> None:
        """Hierarchical (ring2d) pass over ``buf`` in place: row
        reduce-scatter, column full pass over the owned row chunk, row
        allgather — the same three phases (and the same tag subspaces) as
        _hier_rs_ag_flat, each phase one GIL-free native call."""
        row = cast(_TierLinks, self._row_tier)
        col = cast(_TierLinks, self._col_tier)
        C, crank = row.size, row.ring_rank
        chunks = list(np.array_split(buf, C))
        self._native_pass_views(
            chunks, 1, lane, C, crank, tag_base, _SUB_RS, _SUB_AG,
            _NATIVE_PASS_RS, op, wire_mode,
        )
        own = (crank + 1) % C
        if col.size > 1:
            self._native_pass_views(
                list(np.array_split(chunks[own], col.size)),
                2, lane, col.size, col.ring_rank, tag_base,
                _SUB_COL_RS, _SUB_COL_AG, _NATIVE_PASS_FULL, op, wire_mode,
            )
        self._native_pass_views(
            chunks, 1, lane, C, crank, tag_base, _SUB_RS, _SUB_AG,
            _NATIVE_PASS_AG, op, wire_mode,
        )

    def _ring_rs_ag(
        self,
        chunks: List[np.ndarray],
        combine,
        wire,
        acc_dtype,
        lane: int,
        tag_base: int,
        tier: Optional[_TierLinks] = None,
        rs_sub: int = _SUB_RS,
        ag_sub: int = _SUB_AG,
        codec: Optional[str] = None,
    ) -> List[np.ndarray]:
        """One complete ring pass (reduce-scatter then allgather) over
        ``chunks`` — one 1-D array per rank slot — on the given lane, over
        the flat ring or a 2D ``tier`` ring.  Returns the fully reduced
        chunk list.  ``tag_base + rs_sub`` / ``+ ag_sub`` pick this pass's
        tags inside the stripe's block so concurrent stripes, back-to-back
        ops, AND nested tier rings demux cleanly (the column tier passes
        its own subtags from the high half of the block).

        Wire compression: floating payloads travel as bfloat16 per hop with
        accumulation in ``acc_dtype`` (or as scale+int8 frames when
        ``codec="int8"``); in the allgather phase each rank quantizes its
        OWNED chunk exactly once and every other rank forwards the received
        WIRE BYTES untouched — no per-hop decode/re-encode, so all ranks
        decode bitwise-identical values (replica consistency — the commit
        protocol's premise).  For the bf16 wire, quantization and
        accumulation are elementwise in fixed ring-step order, so striping
        a chunk across lanes reproduces the single-lane result BIT FOR
        BIT.  The int8 codec's scale is per-FRAME (amax over the encoded
        chunk), so different lane/stripe configs produce slightly
        different values — every rank must run the same config (already
        the collective-wide contract), and a striped run is NOT
        bit-comparable to a single-lane golden run under int8.
        """
        n = tier.size if tier is not None else self._world_size
        rank = tier.ring_rank if tier is not None else self._rank
        chunks = list(chunks)
        encode, decode = self._codec(wire, acc_dtype, codec)

        # Reduce-scatter phase: after n-1 steps, chunk (rank+1)%n holds the
        # full reduction on this rank.
        for step in range(n - 1):
            send_idx = (rank - step) % n
            recv_idx = (rank - step - 1) % n
            hop: dict = {}
            raw = self._exchange(
                tag_base + rs_sub, encode(chunks[send_idx]), lane, tier, hop=hop
            )
            t_comb = time.monotonic()
            incoming = decode(raw, chunks[recv_idx].size)
            chunks[recv_idx] = combine(chunks[recv_idx], incoming)
            self._record_hop(
                tier, lane, tag_base + rs_sub, hop,
                comb_s=time.monotonic() - t_comb,
            )

        return self._ring_ag_phase(
            chunks, wire, acc_dtype, lane, tag_base + ag_sub, tier, codec=codec
        )

    def _ring_ag_phase(
        self,
        chunks: List[np.ndarray],
        wire,
        acc_dtype,
        lane: int,
        tag: int,
        tier: Optional[_TierLinks] = None,
        codec: Optional[str] = None,
    ) -> List[np.ndarray]:
        """Allgather circulation over a ring (flat or a 2D tier): each rank
        owns chunk (rank+1)%n and the owned chunks circulate until every
        rank holds all n.  The ONE implementation of this phase — shared by
        _ring_rs_ag and the hierarchical pass's row allgather, so the wire
        framing and replica-consistency mechanics cannot diverge between
        topologies.  With wire compression (bf16 wire or an int8 codec)
        each owner quantizes its chunk exactly once and every other rank
        forwards the received WIRE BYTES untouched, so all ranks decode
        bitwise-identical values."""
        n = tier.size if tier is not None else self._world_size
        rank = tier.ring_rank if tier is not None else self._rank
        chunks = list(chunks)
        encode, decode = self._codec(wire, acc_dtype, codec)
        if wire is not None or codec is not None:
            own = (rank + 1) % n
            raw_chunks: List[Optional[bytes]] = [None] * n
            enc = encode(chunks[own])
            raw_chunks[own] = (
                b"".join(bytes(p) for p in enc)
                if isinstance(enc, (list, tuple))
                else bytes(enc)
            )
            for step in range(n - 1):
                send_idx = (rank - step + 1) % n
                recv_idx = (rank - step) % n
                hop: dict = {}
                raw_chunks[recv_idx] = self._exchange(
                    tag, memoryview(cast(bytes, raw_chunks[send_idx])), lane, tier,
                    hop=hop,
                )
                self._record_hop(tier, lane, tag, hop)
            return [
                decode(cast(bytes, raw_chunks[i]), chunks[i].size)
                for i in range(n)
            ]
        for step in range(n - 1):
            send_idx = (rank - step + 1) % n
            recv_idx = (rank - step) % n
            hop2: dict = {}
            chunks[recv_idx] = decode(
                self._exchange(tag, encode(chunks[send_idx]), lane, tier, hop=hop2)
            ).copy()
            self._record_hop(tier, lane, tag, hop2)
        return chunks

    def _hier_rs_ag_flat(
        self,
        flat: np.ndarray,
        combine,
        wire,
        acc_dtype,
        lane: int,
        tag_base: int,
        codec: Optional[str] = None,
    ) -> np.ndarray:
        """One hierarchical (2D ring-of-rings) allreduce pass over a flat
        1-D buffer: reduce-scatter along the ROW ring, full allreduce of
        the owned row chunk along the COLUMN ring, allgather back along the
        row.  Returns the fully reduced flat buffer.

        Hops: (C-1) + 2(R-1) + (C-1) versus the flat ring's 2(N-1) — the
        latency term that keeps step time flat as the group count grows.

        Replica consistency: after the column allreduce every member of a
        column holds BITWISE-identical bytes for its owned chunk
        (_ring_rs_ag's allgather forwards the owner's wire bytes), and the
        row allgather forwards those bytes verbatim (each owner re-encodes
        a value that is already exactly representable on the wire), so ALL
        N ranks decode identical results.  Fold order — row partials summed
        in row-ring-step order, then folded across rows in column-ring-step
        order — is fixed by (world_size, rank) alone, hence deterministic
        per topology."""
        row = cast(_TierLinks, self._row_tier)
        col = cast(_TierLinks, self._col_tier)
        C, crank = row.size, row.ring_rank
        chunks = list(np.array_split(flat, C))
        encode, decode = self._codec(wire, acc_dtype, codec)

        # Phase 1: row reduce-scatter — after C-1 hops this rank's owned
        # chunk holds the full reduction over its row.
        for step in range(C - 1):
            send_idx = (crank - step) % C
            recv_idx = (crank - step - 1) % C
            hop: dict = {}
            raw = self._exchange(
                tag_base + _SUB_RS, encode(chunks[send_idx]), lane, row, hop=hop
            )
            t_comb = time.monotonic()
            incoming = decode(raw, chunks[recv_idx].size)
            chunks[recv_idx] = combine(chunks[recv_idx], incoming)
            self._record_hop(
                row, lane, tag_base + _SUB_RS, hop,
                comb_s=time.monotonic() - t_comb,
            )
        own = (crank + 1) % C

        # Phase 2: column allreduce of the owned row chunk, on the column
        # tier's sockets with the tier partition's subtags.  Every member
        # of this column ends with bitwise-identical bytes.
        if col.size > 1:
            sub = self._ring_rs_ag(
                list(np.array_split(chunks[own], col.size)),
                combine, wire, acc_dtype, lane, tag_base,
                tier=col, rs_sub=_SUB_COL_RS, ag_sub=_SUB_COL_AG, codec=codec,
            )
            chunks[own] = np.concatenate(sub) if len(sub) > 1 else sub[0]

        # Phase 3: row allgather of the owned chunks — the SAME shared
        # circulation as the flat ring's allgather phase (with wire
        # compression each owner quantizes once; after phase 2 already
        # decoded wire values that re-encode is an identity, so forwarded
        # bytes stay bitwise-identical everywhere).
        chunks = self._ring_ag_phase(
            chunks, wire, acc_dtype, lane, tag_base + _SUB_AG, tier=row,
            codec=codec,
        )
        return np.concatenate(chunks) if C > 1 else chunks[0]

    def _flatten(self, arrays: Sequence[np.ndarray]) -> np.ndarray:
        """One contiguous working buffer of the common dtype.  A single
        input is viewed, not copied — the ring never mutates its inputs
        (every combine allocates), so the zero-copy view is safe and saves
        a full memcpy per gradient bucket."""
        if len(arrays) > 1:
            return np.concatenate([a.reshape(-1) for a in arrays])
        return arrays[0].reshape(-1)

    def _unflatten(
        self, out_flat: np.ndarray, arrays: Sequence[np.ndarray], op: str
    ) -> List[np.ndarray]:
        if op == "avg":
            out_flat = out_flat / self._world_size
        out: List[np.ndarray] = []
        pos = 0
        for a in arrays:
            out.append(
                out_flat[pos : pos + a.size].reshape(a.shape).astype(a.dtype, copy=False)
            )
            pos += a.size
        return out

    def _ring_allreduce(
        self,
        arrays: List[np.ndarray],
        op: str,
        allow_wire_compression: bool = True,
        seq: Optional[int] = None,
        codec: Optional[str] = None,
        donate: bool = False,
    ) -> List[np.ndarray]:
        """Single-lane whole-chunk ring allreduce (the lanes=1 path, and the
        building block reduce_scatter/barrier reuse)."""
        if seq is None:
            seq = self._next_seq()
        n = self._world_size
        combine = _REDUCE_COMBINE[op]
        flat = self._flatten(arrays)
        wire, acc_dtype = self._wire_for(
            arrays, flat.dtype, allow_wire_compression and codec is None
        )
        wire_mode = self._native_wire_mode(flat.dtype, wire, acc_dtype, codec)
        if wire_mode is not None:
            buf = self._native_buffer(flat, fresh=donate or len(arrays) > 1)
            self._native_flat_pass(buf, 0, self._tag_base(seq), op, wire_mode)
            return self._unflatten(buf, arrays, op)
        chunks = np.array_split(flat, n)
        chunks = self._ring_rs_ag(
            chunks, combine, wire, acc_dtype, lane=0,
            tag_base=self._tag_base(seq), codec=codec,
        )
        return self._unflatten(np.concatenate(chunks), arrays, op)

    def _hier_allreduce(
        self,
        arrays: List[np.ndarray],
        op: str,
        allow_wire_compression: bool = True,
        seq: Optional[int] = None,
        codec: Optional[str] = None,
        donate: bool = False,
    ) -> List[np.ndarray]:
        """Single-lane hierarchical (ring2d) allreduce — the lanes=1
        counterpart of _ring_allreduce, running one 2D pass over the whole
        flattened payload."""
        if seq is None:
            seq = self._next_seq()
        combine = _REDUCE_COMBINE[op]
        flat = self._flatten(arrays)
        wire, acc_dtype = self._wire_for(
            arrays, flat.dtype, allow_wire_compression and codec is None
        )
        wire_mode = self._native_wire_mode(flat.dtype, wire, acc_dtype, codec)
        if wire_mode is not None:
            buf = self._native_buffer(flat, fresh=donate or len(arrays) > 1)
            self._native_hier_pass(buf, 0, self._tag_base(seq), op, wire_mode)
            return self._unflatten(buf, arrays, op)
        out = self._hier_rs_ag_flat(
            flat, combine, wire, acc_dtype, lane=0,
            tag_base=self._tag_base(seq), codec=codec,
        )
        return self._unflatten(out, arrays, op)

    def _stripe_count(self, max_chunk_nbytes: int) -> int:
        """Stripes per ring chunk: enough to keep every lane busy, sized at
        ~chunk_bytes so stripe k's combine overlaps stripe k+1's wire time,
        rounded to a lane multiple for balance, capped for tag/frame
        overhead."""
        per = max(1, self._chunk_bytes)
        s = max(self._lanes, -(-max_chunk_nbytes // per))
        s = -(-s // self._lanes) * self._lanes
        # The cap must stay a lane multiple AND come after the rounding: a
        # post-cap round-up (e.g. 64 -> 66 at 6 lanes) would spill stripe
        # tags past this seq's _TAGS_PER_OP block into the next op's.
        return min(s, _MAX_STRIPES - _MAX_STRIPES % self._lanes)

    def _run_striped(self, nstripes: int, stripe_body, assemble) -> Work:
        """Shared striped-op scaffolding (flat and hierarchical topologies):
        runs ``stripe_body(s)`` for every stripe on the per-lane worker
        pool, fails the whole op fast on the first stripe error — latch +
        _fail_ring, which closes the flat lanes AND both 2D tiers' lanes of
        this generation so sibling stripes blocked on any tier fail
        immediately — and resolves the returned Work with
        ``assemble(results)`` when the last stripe lands."""
        with self._lock:
            lane_exec = self._lane_executor
            gen = self._generation
        if lane_exec is None:
            err = self._op_error or RuntimeError("collective not configured")
            return Work(failed_future(err))

        results: List[Optional[object]] = [None] * nstripes
        out: Future = Future()
        times = [0, 0]  # Work.times: first stripe taken up, op settled
        state_lock = threading.Lock()
        state = {"pending": nstripes, "failed": False}
        with self._lock:
            self._inflight.add(out)

        def settle_err(e: Exception) -> None:
            self._latch(e)
            # Close the ring lanes of THIS generation so sibling stripes
            # blocked in send/recv fail fast instead of burning the full op
            # timeout; the op is already doomed and errors latch until the
            # next configure() rebuilds every lane.
            self._fail_ring(gen)
            with self._lock:
                self._inflight.discard(out)
            times[1] = time.monotonic_ns()
            if not out.done():
                try:
                    out.set_exception(e)
                except Exception:  # noqa: BLE001 — racing abort()
                    pass

        def finish() -> None:
            try:
                outs = assemble(results)
            except Exception as e:  # noqa: BLE001
                settle_err(e)
                return
            with self._lock:
                self._inflight.discard(out)
            times[1] = time.monotonic_ns()
            if not out.done():
                try:
                    out.set_result(outs)
                except Exception:  # noqa: BLE001 — racing abort()
                    pass

        def make_stripe(s: int):
            def run() -> None:
                with state_lock:
                    if not times[0]:
                        times[0] = time.monotonic_ns()
                try:
                    res = stripe_body(s)
                except Exception as e:  # noqa: BLE001
                    with state_lock:
                        first = not state["failed"]
                        state["failed"] = True
                    if first:
                        settle_err(e)
                    return
                results[s] = res
                with state_lock:
                    state["pending"] -= 1
                    done = state["pending"] == 0 and not state["failed"]
                if done:
                    finish()

            return run

        try:
            for s in range(nstripes):
                lane_exec.submit(make_stripe(s))
        except RuntimeError as e:  # executor shut down by a concurrent abort
            settle_err(e)
        return Work(out, times)

    def _striped_allreduce(
        self,
        arrays: List[np.ndarray],
        op: str,
        allow_wire_compression: bool,
        seq: int,
        codec: Optional[str] = None,
        donate: bool = False,
    ) -> Work:
        """Lanes > 1: stripe the ring chunks round-robin across lanes and run
        each stripe as an independent tagged ring on the per-lane worker
        pool.  Stripes of one op overlap each other (sum vs wire), and
        back-to-back ops (gradient buckets) overlap too — the Work future
        resolves when every stripe lands."""
        n = self._world_size
        combine = _REDUCE_COMBINE[op]
        try:
            flat = self._flatten(arrays)
            chunks = np.array_split(flat, n)
            wire, acc_dtype = self._wire_for(
                arrays, flat.dtype, allow_wire_compression and codec is None
            )
            # Stripe sizing from the ORIGINAL flat chunks (not the native
            # f32 working copy) so both engines carve identical stripe
            # boundaries and tag blocks — the cross-engine interop contract.
            nstripes = self._stripe_count(max(c.nbytes for c in chunks))
            wire_mode = self._native_wire_mode(flat.dtype, wire, acc_dtype, codec)
            if wire_mode is not None:
                buf = self._native_buffer(flat, fresh=donate or len(arrays) > 1)
                # sub[i][s]: stripe s of rank-chunk i, a view into buf the
                # engine reduces in place — assembly is just _unflatten.
                sub = [
                    np.array_split(c, nstripes)
                    for c in np.array_split(buf, n)
                ]
            else:
                sub = [np.array_split(c, nstripes) for c in chunks]
        except Exception as e:  # noqa: BLE001
            self._latch(e)
            return Work(failed_future(e))

        if wire_mode is not None:
            engine = self._engine

            def stripe_body(_s: int) -> None:
                # ONE capi crossing for the whole stripe set: per-stripe
                # fan-out runs on the engine's internal worker pool
                # (ring.cc RingPassMulti), with identical stripe/lane/tag
                # geometry to the per-stripe path — so this rank
                # interoperates with peers still making one ring_pass per
                # stripe, and with the Python engine.
                if engine is None:
                    raise RuntimeError("collective aborted")
                engine.ring_pass_multi(
                    0,
                    nstripes,
                    n,
                    self._rank,
                    [s % self._lanes for s in range(nstripes)],
                    [self._tag_base(seq, s) for s in range(nstripes)],
                    _SUB_RS,
                    _SUB_AG,
                    _NATIVE_PASS_FULL,
                    _NATIVE_OP[op],
                    wire_mode,
                    [
                        int(sub[i][s].ctypes.data)
                        for s in range(nstripes)
                        for i in range(n)
                    ],
                    [
                        int(sub[i][s].size)
                        for s in range(nstripes)
                        for i in range(n)
                    ],
                    self._timeout,
                )

            def assemble(results: List[Optional[object]]) -> List[np.ndarray]:
                return self._unflatten(buf, arrays, op)

            # One "stripe" from _run_striped's perspective — the whole
            # batched pass; back-to-back ops still overlap on the lane
            # executor's other workers.
            return self._run_striped(1, stripe_body, assemble)

        def stripe_body(s: int) -> List[np.ndarray]:
            return self._ring_rs_ag(
                [sub[i][s] for i in range(n)],
                combine,
                wire,
                acc_dtype,
                lane=s % self._lanes,
                tag_base=self._tag_base(seq, s),
                codec=codec,
            )

        def assemble(results: List[Optional[object]]) -> List[np.ndarray]:
            # One concatenate in (chunk, stripe) order — a per-chunk
            # concat followed by a cross-chunk concat would memcpy the
            # whole reduced payload twice on the hot path.
            segs = [
                cast(list, results[s])[i]
                for i in range(n)
                for s in range(nstripes)
            ]
            return self._unflatten(np.concatenate(segs), arrays, op)

        return self._run_striped(nstripes, stripe_body, assemble)

    def _striped_hier_allreduce(
        self,
        arrays: List[np.ndarray],
        op: str,
        allow_wire_compression: bool,
        seq: int,
        codec: Optional[str] = None,
        donate: bool = False,
    ) -> Work:
        """Lanes > 1 under the 2D topology: split the flat payload into
        stripes directly (stripe-major — each stripe runs the COMPLETE
        hierarchical pass, cutting its own row/column chunks), so stripes
        overlap on the wire exactly like the flat striped path while tag
        blocks and lane assignment stay per-stripe.  Stripe boundaries
        derive from the identical flat length on every rank."""
        combine = _REDUCE_COMBINE[op]
        try:
            flat = self._flatten(arrays)
            wire, acc_dtype = self._wire_for(
                arrays, flat.dtype, allow_wire_compression and codec is None
            )
            row_cols = cast(_TierLinks, self._row_tier).size
            # Size stripes so each stripe's ROW chunk (its per-hop exchange
            # unit) lands near chunk_bytes, mirroring the flat path's
            # per-rank-chunk sizing.  Sized from the ORIGINAL flat payload
            # so both engines carve identical stripes (interop contract).
            nstripes = self._stripe_count(-(-flat.nbytes // max(1, row_cols)))
            wire_mode = self._native_wire_mode(flat.dtype, wire, acc_dtype, codec)
            if wire_mode is not None:
                buf = self._native_buffer(flat, fresh=donate or len(arrays) > 1)
                stripes = np.array_split(buf, nstripes)
            else:
                stripes = np.array_split(flat, nstripes)
        except Exception as e:  # noqa: BLE001
            self._latch(e)
            return Work(failed_future(e))

        if wire_mode is not None:

            def stripe_body(s: int) -> None:
                self._native_hier_pass(
                    stripes[s], s % self._lanes, self._tag_base(seq, s), op,
                    wire_mode,
                )

            def assemble(results: List[Optional[object]]) -> List[np.ndarray]:
                return self._unflatten(buf, arrays, op)

            return self._run_striped(nstripes, stripe_body, assemble)

        def stripe_body(s: int) -> np.ndarray:
            return self._hier_rs_ag_flat(
                stripes[s],
                combine,
                wire,
                acc_dtype,
                lane=s % self._lanes,
                tag_base=self._tag_base(seq, s),
                codec=codec,
            )

        def assemble(results: List[Optional[object]]) -> List[np.ndarray]:
            parts = [cast(np.ndarray, r) for r in results]
            return self._unflatten(
                np.concatenate(parts) if len(parts) > 1 else parts[0], arrays, op
            )

        return self._run_striped(nstripes, stripe_body, assemble)

    def _fail_ring(self, gen: int) -> None:
        """Closes this generation's ring lane sockets — flat AND both 2D
        tiers — so every stripe/op blocked on any of them fails fast: a
        hierarchical stripe can be mid-hop in either tier when a sibling
        fails, and a survivor blocked in the column ring must not ride out
        the full op timeout because only the row sockets died.  The
        generation guard keeps a stale failure from touching the next
        quorum's fresh lanes."""
        with self._lock:
            if self._generation != gen:
                return
            peers = list(self._next_lanes) + list(self._prev_lanes)
            for tier in (self._row_tier, self._col_tier):
                if tier is not None:
                    peers += tier.peers()
            engine = self._engine
        for p in peers:
            p.close()
        # The native engine's dup'd lane fds die with the generation too
        # (the fd-sweep contract); counters stay readable, ops fail fast.
        if engine is not None:
            engine.close()

    def allgather(self, array: np.ndarray) -> Work:
        array = np.ascontiguousarray(array)
        if self._world_size == 1:
            return Work(completed_future([array.copy()]))
        seq = self._next_seq()
        return self._submit(lambda: self._ring_allgather(array, self._tag_base(seq) + _SUB_GATHER))

    def _ring_allgather(self, array: np.ndarray, tag: int) -> List[np.ndarray]:
        import pickle

        n = self._world_size
        rank = self._rank
        slots: List[Optional[bytes]] = [None] * n
        slots[rank] = pickle.dumps(array)
        for step in range(n - 1):
            send_idx = (rank - step) % n
            recv_idx = (rank - step - 1) % n
            slots[recv_idx] = self._exchange(tag, slots[send_idx])
        return [pickle.loads(s) for s in slots]

    def broadcast(self, array: np.ndarray, root: int = 0) -> Work:
        array = np.ascontiguousarray(array)
        if self._world_size == 1:
            return Work(completed_future(array.copy()))
        seq = self._next_seq()

        def run() -> np.ndarray:
            out = self._ring_allgather(array, self._tag_base(seq) + _SUB_GATHER)[root]
            return out

        return self._submit(run)

    def reduce_scatter(self, arrays: Sequence[np.ndarray], op: str = "sum") -> Work:
        if op not in _REDUCE_COMBINE:
            return Work(failed_future(_bad_reduce_op(op)))
        arrays = [np.ascontiguousarray(a) for a in arrays]
        if self._world_size == 1:
            return Work(completed_future(arrays[0].copy()))
        if len(arrays) != self._world_size:
            return Work(
                failed_future(
                    ValueError(
                        f"reduce_scatter needs world_size={self._world_size} inputs, "
                        f"got {len(arrays)}"
                    )
                )
            )
        seq = self._next_seq()

        def run() -> np.ndarray:
            # Implemented over ring allreduce of the stacked buffer; rank i
            # keeps slice i.  Adequate for the replica dim's small world sizes.
            stacked = np.stack(arrays)
            reduced = self._ring_allreduce([stacked], op, seq=seq)[0]
            return reduced[self._rank]

        return self._submit(run)

    def alltoall(self, arrays: Sequence[np.ndarray]) -> Work:
        arrays = [np.ascontiguousarray(a) for a in arrays]
        if self._world_size == 1:
            return Work(completed_future([arrays[0].copy()]))
        seq = self._next_seq()

        def run() -> List[np.ndarray]:
            import pickle

            n = self._world_size
            rank = self._rank
            # Route through the ring: circulate everyone's full payload list.
            slots: List[Optional[bytes]] = [None] * n
            slots[rank] = pickle.dumps(list(arrays))
            tag = self._tag_base(seq) + _SUB_GATHER
            for step in range(n - 1):
                send_idx = (rank - step) % n
                recv_idx = (rank - step - 1) % n
                slots[recv_idx] = self._exchange(tag, slots[send_idx])
            lists = [pickle.loads(s) for s in slots]
            return [lists[src][rank] for src in range(n)]

        return self._submit(run)

    def _fifo_queue(self, key: tuple) -> _FifoQueue:
        with self._fifo_lock:
            q = self._fifo.get(key)
            if q is None:
                q = self._fifo[key] = _FifoQueue()
            return q

    def _sever_peer(self, peer_rank: int, gen: int, used: Optional[_Peer]) -> None:
        """Closes the p2p socket a failed op was using so its in-flight or
        matching remote ops fail fast instead of pairing with a later op's
        frame.  Guards: the generation check keeps a failure that straddles a
        reconfigure from touching the NEW quorum's socket, and the identity
        check keeps a stale failure (op blocked on an already-severed socket)
        from closing a freshly re-dialed healthy replacement."""
        if used is None:
            return
        with self._accept_cond:
            if self._generation != gen or self._peers.get(peer_rank) is not used:
                used = None  # registered peer is not the one that failed
            else:
                del self._peers[peer_rank]
        if used is not None:
            used.close()

    def _p2p_op(
        self, q: _FifoQueue, peer_rank: int, body: Callable[[List[_Peer]], object]
    ) -> Work:
        # Ticket + submit must be atomic: with 4 p2p workers, an inverted
        # executor order could park every worker in wait_turn on later
        # tickets while the earliest is still queued behind them, stalling
        # the stream for the whole timeout window.  (Dedicated lock:
        # _fifo_lock nests inside _lock in configure(), and _submit takes
        # _lock, so reusing _fifo_lock here would invert that order.)
        with self._p2p_submit_lock:
            seq = q.take_ticket()
            gen = self._generation

            def run() -> object:
                # Never advance the turnstile past a never-executed slot:
                # poison the stream so the remote side's matching op errors
                # instead of silently pairing with the next frame.
                try:
                    q.wait_turn(seq, self._timeout)
                except Exception as e:  # noqa: BLE001
                    # Queue stall: poison only.  Severing here would kill a
                    # healthy transfer still progressing on the shared socket
                    # (its per-syscall timeouts never fired); the remote's
                    # matching op simply times out on its own socket.
                    q.poison_with(e)
                    raise
                used: List[_Peer] = []
                try:
                    out = body(used)
                except Exception as e:  # noqa: BLE001
                    # Body failure may have left a partial frame on the wire:
                    # sever the exact link this op used so both sides fail fast.
                    q.poison_with(e)
                    self._sever_peer(peer_rank, gen, used[0] if used else None)
                    raise
                q.done()
                return out

            return self._submit(run, ring=False)

    # p2p frame: u32 meta_len | pickled (np.dtype, shape) | raw array bytes.
    # The array body crosses the wire without pickling — on the GB-scale
    # healing path a pickle.dumps is a full extra memcpy of the state dict.
    # The dtype OBJECT is pickled (not .str): custom dtypes like bfloat16
    # stringify as '<V2' and would round-trip as void16.
    _P2P_META = struct.Struct("<I")

    def send(self, array: np.ndarray, dst: int, tag: int = 0) -> Work:
        array = np.ascontiguousarray(array)
        q = self._fifo_queue(("send", dst, tag))

        def body(used: List[_Peer]) -> None:
            import pickle

            from torchft_tpu.checkpointing.serialization import as_u8

            peer = self._dial(dst)
            used.append(peer)
            meta = pickle.dumps((array.dtype, array.shape))
            # as_u8 handles ml_dtypes (bfloat16) that memoryview cannot cast.
            peer.send_msg(
                100 + tag,
                [self._P2P_META.pack(len(meta)), meta, memoryview(as_u8(array))],
            )

        return self._p2p_op(q, dst, body)

    def recv(self, shape: tuple, dtype, src: int, tag: int = 0) -> Work:
        q = self._fifo_queue(("recv", src, tag))

        def body(used: List[_Peer]) -> np.ndarray:
            import pickle

            peer = self._dial(src)
            used.append(peer)
            raw = peer.recv_msg(100 + tag)
            (mlen,) = self._P2P_META.unpack_from(raw, 0)
            rdtype, rshape = pickle.loads(
                bytes(raw[self._P2P_META.size : self._P2P_META.size + mlen])
            )
            body_off = self._P2P_META.size + mlen
            # raw is a writable bytearray: the returned array is mutable and
            # copy-free, matching the old pickle path's contract.
            return (
                np.frombuffer(raw, dtype=np.uint8, offset=body_off)
                .view(rdtype)
                .reshape(rshape)
            )

        return self._p2p_op(q, src, body)

    def barrier(self) -> Work:
        if self._world_size == 1:
            return Work(completed_future(None))
        token = np.zeros(1, dtype=np.int32)
        seq = self._next_seq()
        return self._submit(
            lambda: (self._ring_allreduce([token], "sum", seq=seq), None)[1]
        )


class ErrorSwallowingCollective(Collective):
    """Latches the first error and turns subsequent operations into immediate
    no-ops until the next configure() (reference:
    ErrorSwallowingProcessGroupWrapper, torchft/process_group.py:906-960)."""

    def __init__(self, inner: Collective) -> None:
        self._inner = inner
        self._error: Optional[Exception] = None

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self._error = None
        self._inner.configure(store_addr, rank, world_size)

    # Wire-policy probes proxy to the wrapped collective: layers above
    # (GradientAverager's device wire prep, the semisync engine's codec
    # gate, the Manager's wire-byte telemetry) discover capabilities via
    # getattr — a wrapper that hides them would silently degrade the wire
    # and fork the byte accounting.

    @property
    def wire_codecs(self):
        return getattr(self._inner, "wire_codecs", ())

    @property
    def wire_dtype(self):
        return getattr(self._inner, "wire_dtype", None)

    def wire_nbytes(
        self,
        array,
        allow_wire_compression: bool = True,
        wire_codec: Optional[str] = None,
    ) -> int:
        probe = getattr(self._inner, "wire_nbytes", None)
        if callable(probe):
            # Forward the codec arg only when set, like every other call
            # site — an inner collective with the pre-codec 2-arg probe
            # signature must keep working for plain calls.
            if wire_codec is not None:
                return probe(array, allow_wire_compression, wire_codec)
            return probe(array, allow_wire_compression)
        return int(np.asarray(array).nbytes)

    def errored(self) -> Optional[Exception]:
        return self._error or self._inner.errored()

    def report_error(self, exc: Exception) -> None:
        if self._error is None:
            self._error = exc

    def _guard(self, fn: Callable[[], Work], fallback) -> Work:
        if self.errored() is not None:
            return Work(completed_future(fallback))
        work = fn()

        def on_done(f: Future) -> None:
            exc = f.exception()
            if exc is not None:
                self.report_error(exc)

        work.add_done_callback(on_done)
        # Swallow: map failure to the fallback value.
        out: Future = Future()

        def settle(f: Future) -> None:
            if f.exception() is not None:
                out.set_result(fallback)
            else:
                out.set_result(f.result())

        work.future().add_done_callback(settle)
        return Work(out)

    def allreduce(
        self,
        arrays: Sequence[np.ndarray],
        op: str = "sum",
        allow_wire_compression: bool = True,
        wire_codec: Optional[str] = None,
        donate: bool = False,
    ) -> Work:
        # Optional kwargs forwarded only when set (mock-compat: an inner
        # collective with the bare 3-arg signature must keep working).
        extra: Dict[str, Any] = {}
        if wire_codec is not None:
            extra["wire_codec"] = wire_codec
        if donate:
            extra["donate"] = True
        return self._guard(
            lambda: self._inner.allreduce(
                arrays, op, allow_wire_compression, **extra
            ),
            list(arrays),
        )

    def allgather(self, array: np.ndarray) -> Work:
        return self._guard(lambda: self._inner.allgather(array), [array])

    def broadcast(self, array: np.ndarray, root: int = 0) -> Work:
        return self._guard(lambda: self._inner.broadcast(array, root), array)

    def reduce_scatter(self, arrays: Sequence[np.ndarray], op: str = "sum") -> Work:
        return self._guard(lambda: self._inner.reduce_scatter(arrays, op), arrays[0])

    def alltoall(self, arrays: Sequence[np.ndarray]) -> Work:
        return self._guard(lambda: self._inner.alltoall(arrays), list(arrays))

    def send(self, array: np.ndarray, dst: int, tag: int = 0) -> Work:
        return self._guard(lambda: self._inner.send(array, dst, tag), None)

    def recv(self, shape: tuple, dtype, src: int, tag: int = 0) -> Work:
        return self._guard(
            lambda: self._inner.recv(shape, dtype, src, tag), np.zeros(shape, dtype)
        )

    def barrier(self) -> Work:
        return self._guard(lambda: self._inner.barrier(), None)

    def size(self) -> int:
        return self._inner.size()

    def rank(self) -> int:
        return self._inner.rank()

    def abort(self) -> None:
        self._inner.abort()


class ManagedCollective(Collective):
    """Collective facade bound to a Manager: operations wait for quorum, report
    errors to the manager, and size() reflects the dynamic participant count.
    This is what makes mesh/array code see the fault-tolerant replica
    dimension (reference: ManagedProcessGroup, torchft/process_group.py:963-1028)."""

    def __init__(self, manager) -> None:  # Manager; untyped to avoid cycle
        self._manager = manager

    def configure(self, store_addr: str, rank: int, world_size: int) -> None:
        self._manager._collective.configure(store_addr, rank, world_size)

    def allreduce(
        self,
        arrays: Sequence[np.ndarray],
        op: str = "sum",
        allow_wire_compression: bool = True,
    ) -> Work:
        # Manager.allreduce implements exactly the fault-tolerant gradient
        # semantic: sum over participants / num_participants (an average).
        # Other reduce ops must not silently return averaged data — use the
        # raw collective (manager.collective()) for those.
        if op not in ("sum", "avg"):
            return Work(
                failed_future(
                    ValueError(
                        f"ManagedCollective.allreduce implements the "
                        f"participant-averaged gradient reduction; op={op!r} "
                        "is not expressible through it"
                    )
                )
            )
        futs = [self._manager.allreduce(a) for a in arrays]
        out: Future = Future()

        def gather(_f: Future) -> None:
            if all(f.done() for f in futs) and not out.done():
                out.set_result([f.result() for f in futs])

        for f in futs:
            f.add_done_callback(gather)
        return Work(out)

    def allgather(self, array: np.ndarray) -> Work:
        self._manager.wait_quorum()
        return self._manager._collective.allgather(array)

    def broadcast(self, array: np.ndarray, root: int = 0) -> Work:
        self._manager.wait_quorum()
        return self._manager._collective.broadcast(array, root)

    def reduce_scatter(self, arrays: Sequence[np.ndarray], op: str = "sum") -> Work:
        self._manager.wait_quorum()
        return self._manager._collective.reduce_scatter(arrays, op)

    def alltoall(self, arrays: Sequence[np.ndarray]) -> Work:
        self._manager.wait_quorum()
        return self._manager._collective.alltoall(arrays)

    def send(self, array: np.ndarray, dst: int, tag: int = 0) -> Work:
        self._manager.wait_quorum()
        return self._manager._collective.send(array, dst, tag)

    def recv(self, shape: tuple, dtype, src: int, tag: int = 0) -> Work:
        self._manager.wait_quorum()
        return self._manager._collective.recv(shape, dtype, src, tag)

    def barrier(self) -> Work:
        self._manager.wait_quorum()
        return self._manager._collective.barrier()

    def size(self) -> int:
        return self._manager.num_participants()

    def rank(self) -> int:
        return self._manager.participating_rank() or 0

    def errored(self) -> Optional[Exception]:
        return self._manager.errored()

    def abort(self) -> None:
        self._manager._collective.abort()
