"""ctypes bindings to the native coordination core (libtpuft.so).

The role of the reference's PyO3 binding layer (reference: src/lib.rs:710-726):
exposes ``LighthouseServer``, ``LighthouseClient``, ``ManagerServer``,
``ManagerClient``, ``QuorumResult`` plus tpu-ft's native ``StoreServer`` /
``StoreClient`` to Python.  Requests and responses cross the C ABI as
serialized protobuf bytes built/parsed with the generated ``tpuft_pb2``
module; ctypes drops the GIL for the duration of every native call, matching
the reference's ``py.allow_threads`` usage (src/lib.rs:186-200).

gRPC-style status codes CANCELLED/DEADLINE_EXCEEDED map to ``TimeoutError``
and everything else to ``RuntimeError`` (reference: StatusError mapping,
src/lib.rs:644-668).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from dataclasses import dataclass, field
from typing import List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_PKG_DIR, "_lib", "libtpuft.so")
_STAMP_PATH = os.path.join(_PKG_DIR, "_lib", "libtpuft.digest")
_PB2_PATH = os.path.join(_PKG_DIR, "proto", "tpuft_pb2.py")

# Wire status codes (native/src/wire.h).
_OK = 0
_CANCELLED = 1
_DEADLINE_EXCEEDED = 4

# Method ids (native/src/wire.h).
LIGHTHOUSE_QUORUM = 1
LIGHTHOUSE_HEARTBEAT = 2
LIGHTHOUSE_STATUS = 3
LIGHTHOUSE_EVICT = 4
LIGHTHOUSE_DRAIN = 5
LIGHTHOUSE_REPLICATE = 6
LIGHTHOUSE_LEADER_INFO = 7
LIGHTHOUSE_REGION_DIGEST = 8
LIGHTHOUSE_REGIONS = 9
MANAGER_QUORUM = 10
MANAGER_CHECKPOINT_METADATA = 11
MANAGER_SHOULD_COMMIT = 12
MANAGER_KILL = 13
STORE_SET = 20
STORE_GET = 21
STORE_ADD = 22
STORE_DELETE = 23


# The plain-g++ source set (native/gen_pb_local.py's docstring recipe);
# tests/test_native_core.py builds its test binary from the same list, so
# the two recipes cannot drift.
NATIVE_SOURCES = (
    "wire.cc",
    "http.cc",
    "flight.cc",
    "lighthouse.cc",
    "manager.cc",
    "store.cc",
    "ring.cc",
    "capi.cc",
)


def _build_native_gxx(native_dir: str) -> None:
    """Toolchain-less fallback: gen_pb_local.py + plain g++ -shared (the
    recipe native/gen_pb_local.py documents).  Used when cmake/ninja are
    absent but g++ exists.  The generated header lands in this checkout's
    own build directory, never in a path other checkouts share."""
    import sys

    gen_dir = os.path.join(native_dir, "build-g++", "gen")
    subprocess.run(
        [sys.executable, os.path.join(native_dir, "gen_pb_local.py"), gen_dir],
        check=True,
        capture_output=True,
        timeout=120,
    )
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    srcs = [os.path.join(native_dir, "src", f) for f in NATIVE_SOURCES]
    subprocess.run(
        # -O3, not -O2: GCC 10 only auto-vectorizes at -O3, and the ring
        # engine's f32 combine + wire-codec loops are the data plane's
        # arithmetic hot path.
        ["g++", "-std=c++17", "-O3", "-fPIC", "-shared",
         "-I", os.path.join(native_dir, "src"), "-I", gen_dir,
         *srcs, "-o", _LIB_PATH, "-lpthread"],
        check=True,
        capture_output=True,
        timeout=600,
    )


def _build_native() -> None:
    """Builds libtpuft.so and the generated protobuf modules via cmake/ninja,
    falling back to the gen_pb_local.py + g++ recipe on toolchain-less
    containers."""
    import shutil

    native_dir = os.path.join(_REPO_ROOT, "native")
    if shutil.which("cmake") is None or shutil.which("ninja") is None:
        _build_native_gxx(native_dir)
        return
    build_dir = os.path.join(native_dir, "build")
    subprocess.run(
        ["cmake", "-B", build_dir, "-G", "Ninja", native_dir],
        check=True,
        capture_output=True,
    )
    # Default target set (not just tpuft+py_proto): ALL includes tpuft_test,
    # so an out-of-the-box `ctest --test-dir native/build` passes with no
    # manual target — round 3 shipped a build dir where it reported Not Run.
    subprocess.run(["ninja", "-C", build_dir], check=True, capture_output=True)


def source_digest() -> str:
    """sha256 over everything the native build reads: ``native/src/*``,
    ``native/tests/*`` (the default target set builds the C++ suite), the
    two build recipes and the proto.  Stamped beside the library; a
    differing stamp means the library was built from other sources."""
    import glob
    import hashlib

    native_dir = os.path.join(_REPO_ROOT, "native")
    files = sorted(
        glob.glob(os.path.join(native_dir, "src", "*"))
        + glob.glob(os.path.join(native_dir, "tests", "*"))
    ) + [
        os.path.join(native_dir, "CMakeLists.txt"),
        os.path.join(native_dir, "gen_pb_local.py"),
        os.path.join(_REPO_ROOT, "proto", "tpuft.proto"),
    ]
    digest = hashlib.sha256()
    for path in files:
        digest.update(os.path.relpath(path, _REPO_ROOT).encode())
        with open(path, "rb") as f:
            digest.update(f.read())
    return digest.hexdigest()


def _built_from(digest: str) -> bool:
    if not (os.path.exists(_LIB_PATH) and os.path.exists(_PB2_PATH)):
        return False
    try:
        with open(_STAMP_PATH, encoding="utf-8") as f:
            return f.read().strip() == digest
    except OSError:
        return False


def _ensure_built() -> None:
    """Builds the library unless the one on disk carries the stamp of the
    sources on disk.  Serialised by a file lock — across processes (launcher
    children importing for the first time would otherwise race the build)
    and across threads alike, each call locking its own open file."""
    import fcntl

    digest = source_digest()
    if _built_from(digest):
        return
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    with open(_LIB_PATH + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _built_from(digest):
            return
        _build_native()
        tmp = f"{_STAMP_PATH}.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(digest + "\n")
        os.replace(tmp, _STAMP_PATH)


_ensure_built()

from torchft_tpu.proto import tpuft_pb2 as pb  # noqa: E402


def _load_lib() -> ctypes.CDLL:
    lib = ctypes.CDLL(_LIB_PATH)
    lib.tf_free.argtypes = [ctypes.c_void_p]
    lib.tf_lighthouse_new.restype = ctypes.c_void_p
    lib.tf_lighthouse_new.argtypes = [
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_lighthouse_address.restype = ctypes.c_void_p
    lib.tf_lighthouse_address.argtypes = [ctypes.c_void_p]
    lib.tf_lighthouse_http_address.restype = ctypes.c_void_p
    lib.tf_lighthouse_http_address.argtypes = [ctypes.c_void_p]
    lib.tf_lighthouse_evict.restype = ctypes.c_int
    lib.tf_lighthouse_evict.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.tf_lighthouse_drain.restype = ctypes.c_int
    lib.tf_lighthouse_drain.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.tf_lighthouse_set_role.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int64,
    ]
    lib.tf_lighthouse_role.restype = ctypes.c_int
    lib.tf_lighthouse_role.argtypes = [ctypes.c_void_p]
    lib.tf_lighthouse_leader_epoch.restype = ctypes.c_int64
    lib.tf_lighthouse_leader_epoch.argtypes = [ctypes.c_void_p]
    lib.tf_lighthouse_snapshot.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.tf_lighthouse_link_state.restype = ctypes.c_int
    lib.tf_lighthouse_link_state.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.tf_lighthouse_flight_json.restype = ctypes.c_void_p
    lib.tf_lighthouse_flight_json.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    # Federation surface (docs/wire.md "Federation").
    lib.tf_lighthouse_set_federation.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_int64,
    ]
    lib.tf_lighthouse_regions_json.restype = ctypes.c_void_p
    lib.tf_lighthouse_regions_json.argtypes = [ctypes.c_void_p]
    lib.tf_lighthouse_shutdown.argtypes = [ctypes.c_void_p]
    lib.tf_lighthouse_free.argtypes = [ctypes.c_void_p]
    lib.tf_manager_new.restype = ctypes.c_void_p
    lib.tf_manager_new.argtypes = [
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_manager_address.restype = ctypes.c_void_p
    lib.tf_manager_address.argtypes = [ctypes.c_void_p]
    lib.tf_manager_set_status.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.c_double,
        ctypes.c_double,
        ctypes.c_double,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_double,
        ctypes.c_double,
        ctypes.c_double,
    ]
    lib.tf_manager_flight_json.restype = ctypes.c_void_p
    lib.tf_manager_flight_json.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    # Goodput-ledger push (heartbeat fields 14-16).
    lib.tf_manager_set_ledger.argtypes = [
        ctypes.c_void_p,
        ctypes.c_double,
        ctypes.c_double,
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_int32,
    ]
    lib.tf_manager_shutdown.argtypes = [ctypes.c_void_p]
    lib.tf_manager_free.argtypes = [ctypes.c_void_p]
    lib.tf_store_new.restype = ctypes.c_void_p
    lib.tf_store_new.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p)]
    lib.tf_store_address.restype = ctypes.c_void_p
    lib.tf_store_address.argtypes = [ctypes.c_void_p]
    lib.tf_store_shutdown.argtypes = [ctypes.c_void_p]
    lib.tf_store_free.argtypes = [ctypes.c_void_p]
    lib.tf_client_new.restype = ctypes.c_void_p
    lib.tf_client_new.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_client_call.restype = ctypes.c_int
    lib.tf_client_call.argtypes = [
        ctypes.c_void_p,
        ctypes.c_uint16,
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_size_t),
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_client_free.argtypes = [ctypes.c_void_p]
    return lib


_lib = _load_lib()


def _bind_ring(lib: ctypes.CDLL) -> None:
    """Declares the tf_ring_* signatures of the GIL-free ring engine
    (native/src/ring.cc).  A library without them was not built from
    these sources; the AttributeError is the build error."""
    lib.tf_ring_new.restype = ctypes.c_void_p
    lib.tf_ring_new.argtypes = [ctypes.c_int32, ctypes.c_double, ctypes.c_double]
    lib.tf_ring_set_tier.restype = ctypes.c_int
    lib.tf_ring_set_tier.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_ring_close.argtypes = [ctypes.c_void_p]
    lib.tf_ring_free.argtypes = [ctypes.c_void_p]
    lib.tf_ring_detach.restype = ctypes.c_int
    lib.tf_ring_detach.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_ring_open_fds.restype = ctypes.c_int
    lib.tf_ring_open_fds.argtypes = [ctypes.c_void_p]
    lib.tf_ring_exchange.restype = ctypes.c_int
    lib.tf_ring_exchange.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_uint32,
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_size_t),
        ctypes.c_double,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_ring_pass.restype = ctypes.c_int
    lib.tf_ring_pass.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_uint32,
        ctypes.c_uint32,
        ctypes.c_uint32,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_double,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_ring_pass_multi.restype = ctypes.c_int
    lib.tf_ring_pass_multi.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_uint32,
        ctypes.c_uint32,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_double,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_ring_set_shm.restype = ctypes.c_int
    lib.tf_ring_set_shm.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_char_p),
    ]
    lib.tf_ring_counters.restype = ctypes.c_int
    lib.tf_ring_counters.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_int32,
    ]
    lib.tf_ring_shaper_counters.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.tf_ring_link_bytes.restype = ctypes.c_uint64
    lib.tf_ring_link_bytes.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_int32,
    ]
    # Data-plane flight recorder (hop telemetry, PR 14).
    lib.tf_ring_set_hop.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
    ]
    lib.tf_ring_hop_stats.restype = ctypes.c_int
    lib.tf_ring_hop_stats.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_double),
    ]
    lib.tf_ring_hop_records.restype = ctypes.c_int
    lib.tf_ring_hop_records.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_int32,
    ]
    lib.tf_ring_shaper_wait_s.restype = ctypes.c_double
    lib.tf_ring_shaper_wait_s.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
    ]
    lib.tf_ring_set_shaper.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int32,
        ctypes.c_double,
        ctypes.c_double,
    ]


_bind_ring(_lib)


def _take_string(ptr: int) -> str:
    if not ptr:
        return ""
    value = ctypes.string_at(ptr).decode()
    _lib.tf_free(ptr)
    return value


def _take_error(err: "ctypes.c_char_p") -> str:
    if not err.value:
        return "unknown native error"
    msg = err.value.decode()
    _lib.tf_free(ctypes.cast(err, ctypes.c_void_p))
    return msg


def _raise_for_status(status: int, msg: str) -> None:
    exc: Exception
    if status in (_CANCELLED, _DEADLINE_EXCEEDED):
        exc = TimeoutError(msg)
    else:
        exc = RuntimeError(msg)
    # The wire status rides on the exception so failover-aware callers can
    # distinguish UNAVAILABLE (retry elsewhere) from application errors
    # like ABORTED "is draining" (final).
    exc.wire_status = status  # type: ignore[attr-defined]
    raise exc


# Wire status UNAVAILABLE (native/src/wire.h): transport failure or an HA
# standby's "not the leader" rejection — the two conditions a multi-address
# client fails over on.
_UNAVAILABLE = 14

# The HA standby-rejection contract (native/src/wire.h kNotLeaderPrefix):
# "not the leader; leader=<rpc_addr> http=<http_addr> epoch=<N>".
NOT_LEADER_PREFIX = "not the leader"


def parse_not_leader(msg: str) -> Optional[str]:
    """Returns the leader RPC address named by a standby rejection, ""
    when the standby knows no leader yet, or None when ``msg`` is not a
    not-leader rejection at all."""
    if not msg.startswith(NOT_LEADER_PREFIX):
        return None
    import re

    m = re.search(r"leader=(\S*)", msg)
    return m.group(1) if m else ""


class _Client:
    """Generic RPC client over the native connection (connect w/ retry+backoff,
    reference: src/net.rs:22-34)."""

    def __init__(self, addr: str, connect_timeout_ms: int = 10000) -> None:
        err = ctypes.c_char_p()
        self._ptr = _lib.tf_client_new(addr.encode(), connect_timeout_ms, ctypes.byref(err))
        if not self._ptr:
            raise TimeoutError(_take_error(err))
        self._addr = addr

    def call(self, method: int, request: bytes, timeout_ms: int) -> bytes:
        resp = ctypes.POINTER(ctypes.c_uint8)()
        resp_len = ctypes.c_size_t()
        err = ctypes.c_char_p()
        status = _lib.tf_client_call(
            self._ptr,
            method,
            request,
            len(request),
            max(0, int(timeout_ms)),
            ctypes.byref(resp),
            ctypes.byref(resp_len),
            ctypes.byref(err),
        )
        if status != _OK:
            _raise_for_status(status, _take_error(err))
        data = ctypes.string_at(resp, resp_len.value)
        _lib.tf_free(ctypes.cast(resp, ctypes.c_void_p))
        return data

    def close(self) -> None:
        if self._ptr:
            _lib.tf_client_free(self._ptr)
            self._ptr = None

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:
            pass


@dataclass
class QuorumResult:
    """Per-rank recovery plan returned by ``ManagerClient._quorum``.
    Reference parity: QuorumResult pyclass, src/lib.rs:275-308."""

    quorum_id: int = 0
    replica_rank: int = 0
    replica_world_size: int = 1
    recover_src_manager_address: str = ""
    recover_src_replica_rank: Optional[int] = None
    # PRIMARY-assignment destinations (what a point-to-point transport must
    # serve — its sends block until matched).
    recover_dst_replica_ranks: List[int] = field(default_factory=list)
    # Full recovering set (what a pull-based transport serves: every donor
    # opens its window for striped fetches).  Falls back to the primary
    # list against pre-multi-donor servers.
    recover_dst_replica_ranks_all: List[int] = field(default_factory=list)
    # Striped multi-donor healing (valid when heal): the full ordered donor
    # rotation — every healthy max-step group, primary first.  Falls back to
    # the singleton [recover_src_*] against pre-multi-donor servers so the
    # healing path can always iterate these.
    recover_src_replica_ranks: List[int] = field(default_factory=list)
    recover_src_manager_addresses: List[str] = field(default_factory=list)
    # Full sorted participant membership (fields 15-16; ALWAYS filled by
    # servers of this generation): shard holders for the erasure-coded
    # recovery fallback are any live participant, not just max-step donors.
    # Empty against pre-EC servers (the EC plane then keeps its last view).
    participant_replica_ranks: List[int] = field(default_factory=list)
    participant_manager_addresses: List[str] = field(default_factory=list)
    store_address: str = ""
    max_step: int = 0
    max_replica_rank: Optional[int] = None
    max_world_size: int = 1
    heal: bool = False


class LighthouseServer:
    """In-process native Lighthouse (reference: LighthouseServer, src/lib.rs:580-642)."""

    def __init__(
        self,
        bind: str = "[::]:0",
        min_replicas: int = 1,
        join_timeout_ms: int = 100,
        quorum_tick_ms: int = 100,
        heartbeat_timeout_ms: int = 5000,
        http_bind: str = "[::]:0",
    ) -> None:
        err = ctypes.c_char_p()
        self._ptr = _lib.tf_lighthouse_new(
            bind.encode(),
            http_bind.encode(),
            min_replicas,
            join_timeout_ms,
            quorum_tick_ms,
            heartbeat_timeout_ms,
            ctypes.byref(err),
        )
        if not self._ptr:
            raise RuntimeError(_take_error(err))

    def address(self) -> str:
        return _take_string(_lib.tf_lighthouse_address(self._ptr))

    def http_address(self) -> str:
        return _take_string(_lib.tf_lighthouse_http_address(self._ptr))

    def evict(self, replica_prefix: str) -> int:
        """Supervisor-assisted failure notification: drop the heartbeat and
        pending join of every replica id matching ``replica_prefix`` (a full
        id or a "<group>" family whose ids are "<group>:<uuid>").  The next
        quorum round then forms without spending join_timeout waiting for a
        process the supervisor already knows is dead.  Returns the number of
        ids dropped."""
        return int(_lib.tf_lighthouse_evict(self._ptr, replica_prefix.encode()))

    def drain(self, replica_prefix: str, deadline_ms: int = 0) -> int:
        """Cooperative drain: mark every replica id matching
        ``replica_prefix`` (full id or "<group>" uuid family) as a PLANNED
        departure — excluded from the next quorum immediately (no
        join/heartbeat-timeout wait) while its in-flight step finishes
        undisturbed, and tombstoned against late re-joins.  The replacement
        incarnation (fresh ":uuid" suffix) is admitted normally.
        ``deadline_ms`` is the advisory preemption deadline.  Returns the
        number of ids marked."""
        return int(
            _lib.tf_lighthouse_drain(self._ptr, replica_prefix.encode(), int(deadline_ms))
        )

    def set_role(
        self,
        leader: bool,
        leader_address: str = "",
        leader_http_address: str = "",
        epoch: int = 0,
        lease_expires_ms: int = 0,
    ) -> None:
        """HA role control (docs/wire.md "HA lighthouse").  A standalone
        lighthouse is a permanent leader; under the lease-based election
        (:mod:`torchft_tpu.ha`) the driver flips the role here on every
        lease transition.  As leader, ``lease_expires_ms`` (epoch ms) is
        the serve-time guard — once it passes without a renewed SetRole,
        Quorum/Heartbeat are refused so an expired-lease leader can never
        split-brain with the lease's next winner.  As follower, the
        leader_* fields are what the redirect rejections and HTTP 307s
        point clients at."""
        if self._ptr:
            _lib.tf_lighthouse_set_role(
                self._ptr,
                1 if leader else 0,
                leader_address.encode(),
                leader_http_address.encode(),
                int(epoch),
                int(lease_expires_ms),
            )

    def role(self) -> int:
        """1 = leader with a live lease, 0 = follower (or lapsed lease)."""
        return int(_lib.tf_lighthouse_role(self._ptr)) if self._ptr else 0

    def leader_epoch(self) -> int:
        return int(_lib.tf_lighthouse_leader_epoch(self._ptr)) if self._ptr else 0

    def set_federation(
        self, region: str, root_addrs: str, push_interval_ms: int = 500
    ) -> None:
        """Join a two-tier federation as the CHILD lighthouse for
        ``region`` (docs/wire.md "Federation").  This instance keeps
        owning heartbeats, sentinels, and the goodput ledger for its
        local replica groups, but stops forming quorums itself: a
        background loop pushes a membership + ledger digest to the ROOT
        at ``root_addrs`` (comma-separated, leader + standbys) every
        ``push_interval_ms`` and installs the global quorum the root
        returns.  Call after the server is up; the root needs no
        configuration — any lighthouse that receives digests serves as
        root.  Flat (non-federated) deployments never call this and
        behave exactly as before."""
        if not self._ptr:
            return
        _lib.tf_lighthouse_set_federation(
            self._ptr, region.encode(), root_addrs.encode(), int(push_interval_ms)
        )

    def regions_json(self) -> str:
        """Federation rollup as a JSON document string — same payload as
        this lighthouse's ``GET /regions.json`` (docs/wire.md
        "Federation"): ``{"role", "region", "regions": [...]}`` where
        role is "root"/"child"/"flat".  A root lists one row per region
        with digest freshness and ledger rollups; a child lists its own
        region; a flat instance lists nothing."""
        if not self._ptr:
            return '{"role":"flat","region":"","regions":[]}'
        return _take_string(_lib.tf_lighthouse_regions_json(self._ptr))

    def regions(self) -> dict:
        """Parsed :meth:`regions_json`."""
        import json

        return json.loads(self.regions_json() or "{}")

    def flight_json(self, limit: int = 0) -> str:
        """Flight-recorder snapshot as a JSON document string (newest-first
        events; ``limit`` 0 = all retained).  Same payload as this
        lighthouse's ``GET /debug/flight.json`` (docs/wire.md "Flight
        recorder")."""
        if not self._ptr:
            return "{}"
        return _take_string(_lib.tf_lighthouse_flight_json(self._ptr, int(limit)))

    def flight(self, limit: int = 0) -> dict:
        """Parsed :meth:`flight_json` — ``{"server", "id", "capacity",
        "recorded", "dropped", "events": [...]}`` with events newest-first.
        Use :mod:`torchft_tpu.obs.flight` to reconstruct quorum-transition
        sequences or merge into a Perfetto trace."""
        import json

        return json.loads(self.flight_json(limit) or "{}")

    def link_state(self, replica_id: str) -> int:
        """Slow-link sentinel state of the replica's OUTBOUND edge (0
        healthy, 1 suspect, 2 degraded) — in-process introspection for
        tests; the wire surfaces are /metrics and /alerts.json."""
        if not self._ptr:
            return 0
        return int(_lib.tf_lighthouse_link_state(self._ptr, replica_id.encode()))

    def snapshot(self) -> bytes:
        """Serialized ``LighthouseReplicateRequest`` of the full replicable
        state (membership, live step/state, straggler-sentinel health,
        link-health, alerts, previous quorum + id) — what the HA election
        driver pushes to each standby over wire method 6."""
        if not self._ptr:
            return b""
        buf = ctypes.POINTER(ctypes.c_uint8)()
        length = ctypes.c_size_t()
        _lib.tf_lighthouse_snapshot(self._ptr, ctypes.byref(buf), ctypes.byref(length))
        data = ctypes.string_at(buf, length.value)
        _lib.tf_free(ctypes.cast(buf, ctypes.c_void_p))
        return data

    def shutdown(self) -> None:
        if self._ptr:
            _lib.tf_lighthouse_shutdown(self._ptr)

    def __del__(self) -> None:
        try:
            if self._ptr:
                _lib.tf_lighthouse_shutdown(self._ptr)
                _lib.tf_lighthouse_free(self._ptr)
                self._ptr = None
        except Exception:
            pass


class LighthouseClient:
    """Direct lighthouse access for tooling and LocalSGD-style algorithms
    (reference: LighthouseClient, src/lib.rs:475-565).

    ``addr`` may be a single ``host:port`` or a comma-separated list (an HA
    lighthouse replica set, docs/wire.md "HA lighthouse"): every call fails
    over across the list with decorrelated-jitter backoff, follows a
    standby's "not the leader; leader=<addr>" redirect straight to the
    leader, and raises a clean, actionable error naming every address when
    none is reachable within the connect timeout."""

    def __init__(self, addr: str, connect_timeout_ms: int = 10000) -> None:
        self._addrs = [a.strip() for a in addr.split(",") if a.strip()]
        if not self._addrs:
            raise ValueError("empty lighthouse address")
        self._connect_timeout_ms = connect_timeout_ms
        self._cur = 0
        self._leader_override: Optional[str] = None
        self._clients: dict = {}

    def _client_for(self, addr: str, budget_ms: int) -> _Client:
        client = self._clients.get(addr)
        if client is None:
            # Short per-attempt connect budget so one dead address cannot
            # eat the whole failover window before its siblings are tried.
            per = min(2000, max(250, budget_ms))
            client = _Client(addr, connect_timeout_ms=per)
            self._clients[addr] = client
        return client

    def _call_failover(self, method: int, payload: bytes, timeout_ms: int) -> bytes:
        """One logical RPC against the replica set: try the current (or
        redirect-learned leader) address; on UNAVAILABLE or a connect
        failure rotate/follow with decorrelated-jitter backoff until
        ``timeout_ms`` elapses.  Application-level errors (ABORTED "is
        draining", NOT_FOUND, server-side DEADLINE_EXCEEDED) are final."""
        import time as _time

        from torchft_tpu.ha.backoff import DecorrelatedBackoff

        deadline = _time.monotonic() + max(0.05, timeout_ms / 1e3)
        # Cap under a lease period (mirrors FailoverRpcClient): mid-election
        # every address rejects, and the sleep — not the rejections — would
        # otherwise become the failover latency floor.
        backoff = DecorrelatedBackoff(base_s=0.05, cap_s=0.5)
        last_exc: Optional[Exception] = None
        first = True
        while first or _time.monotonic() < deadline:
            first = False
            left_ms = max(250, int((deadline - _time.monotonic()) * 1e3))
            addr = self._leader_override or self._addrs[self._cur % len(self._addrs)]
            try:
                client = self._client_for(addr, min(self._connect_timeout_ms, left_ms))
                return client.call(method, payload, min(timeout_ms, left_ms))
            except TimeoutError as e:
                if getattr(e, "wire_status", None) is not None:
                    raise  # DEADLINE_EXCEEDED from a live server: final
                last_exc = e  # connect failure: rotate below
            except RuntimeError as e:
                if getattr(e, "wire_status", None) != _UNAVAILABLE:
                    raise  # application error (e.g. "is draining"): final
                last_exc = e
                leader = parse_not_leader(str(e))
                if leader and leader != addr:
                    # Redirect: jump straight to the named leader; the
                    # rejection proves the service is up, skip the backoff.
                    self._leader_override = leader
                    continue
            # Transport failure or a standby that knows no leader: drop a
            # learned leader (it may have just died) else rotate.
            if self._leader_override is not None:
                self._leader_override = None
            else:
                self._cur = (self._cur + 1) % len(self._addrs)
            sleep_s = backoff.next()
            if _time.monotonic() + sleep_s >= deadline:
                break
            _time.sleep(sleep_s)
        raise TimeoutError(
            "no lighthouse answered at any of ["
            + ", ".join(self._addrs)
            + f"] within {timeout_ms} ms — check TPUFT_LIGHTHOUSE and that "
            f"the lighthouse processes are running (last error: {last_exc})"
        )

    def quorum(
        self,
        replica_id: str,
        timeout_ms: int = 5000,
        address: str = "",
        store_address: str = "",
        step: int = 0,
        world_size: int = 1,
        shrink_only: bool = False,
        data: Optional[dict] = None,
        trace_id: str = "",
    ) -> "pb.Quorum":
        import json

        req = pb.LighthouseQuorumRequest()
        req.trace_id = trace_id
        m = req.requester
        m.replica_id = replica_id
        m.address = address
        m.store_address = store_address
        m.step = step
        m.world_size = world_size
        m.shrink_only = shrink_only
        if data is not None:
            m.data = json.dumps(data)
        resp = pb.LighthouseQuorumResponse()
        resp.ParseFromString(
            self._call_failover(LIGHTHOUSE_QUORUM, req.SerializeToString(), timeout_ms)
        )
        return resp.quorum

    def heartbeat(
        self,
        replica_id: str,
        timeout_ms: int = 5000,
        step: int = 0,
        state: str = "",
        step_time_ms_ewma: float = 0.0,
        step_time_ms_last: float = 0.0,
        trace_id: str = "",
        link_recv_gbps: float = 0.0,
        link_send_gbps: float = 0.0,
        link_hop_rtt_ms: float = 0.0,
    ) -> None:
        """One heartbeat; ``step``/``state`` feed the lighthouse's live
        per-replica observability (GET /metrics step lag, /status.json) and
        the step-time fields feed its straggler sentinel (fields 4-5,
        docs/wire.md).  ``trace_id`` stamps the causal trace of the step in
        flight (field 7).  The link fields (11-13) feed the slow-link
        sentinel; 0 = not reported."""
        req = pb.LighthouseHeartbeatRequest(
            replica_id=replica_id,
            step=int(step),
            state=state,
            step_time_ms_ewma=float(step_time_ms_ewma),
            step_time_ms_last=float(step_time_ms_last),
            trace_id=trace_id,
            link_recv_gbps=float(link_recv_gbps),
            link_send_gbps=float(link_send_gbps),
            link_hop_rtt_ms=float(link_hop_rtt_ms),
        )
        self._call_failover(LIGHTHOUSE_HEARTBEAT, req.SerializeToString(), timeout_ms)

    def evict(self, replica_prefix: str, timeout_ms: int = 5000) -> int:
        """Supervisor-assisted failure notification over the wire (method 4,
        docs/wire.md): drop + tombstone every replica id matching
        ``replica_prefix`` (full id or "<group>" uuid family) so the next
        quorum forms without waiting on a process the supervisor reaped."""
        req = pb.LighthouseEvictRequest(replica_prefix=replica_prefix)
        resp = pb.LighthouseEvictResponse()
        resp.ParseFromString(
            self._call_failover(LIGHTHOUSE_EVICT, req.SerializeToString(), timeout_ms)
        )
        return int(resp.evicted)

    def drain(
        self,
        replica_prefix: str,
        deadline_ms: int = 0,
        timeout_ms: int = 5000,
        trace_id: str = "",
    ) -> int:
        """Cooperative-drain notice over the wire (method 5, docs/wire.md):
        mark the matching replica ids as departing so the next quorum forms
        without them, while their in-flight step finishes undisturbed.
        This is what a departing Manager sends the moment its DrainWatcher
        fires (SIGTERM / GCE preemption notice / explicit trigger)."""
        req = pb.LighthouseDrainRequest(
            replica_prefix=replica_prefix,
            deadline_ms=int(deadline_ms),
            trace_id=trace_id,
        )
        resp = pb.LighthouseDrainResponse()
        resp.ParseFromString(
            self._call_failover(LIGHTHOUSE_DRAIN, req.SerializeToString(), timeout_ms)
        )
        return int(resp.drained)

    def status(self, timeout_ms: int = 5000) -> "pb.LighthouseStatusResponse":
        resp = pb.LighthouseStatusResponse()
        resp.ParseFromString(
            self._call_failover(LIGHTHOUSE_STATUS, b"", timeout_ms)
        )
        return resp

    def leader(self, timeout_ms: int = 5000) -> "pb.LighthouseLeaderInfoResponse":
        """Leader discovery (wire method 7): who the answering replica
        believes the leader is, plus its own role (1 leader, 0 follower).
        Answered by every replica — followers do not redirect this."""
        resp = pb.LighthouseLeaderInfoResponse()
        resp.ParseFromString(
            self._call_failover(LIGHTHOUSE_LEADER_INFO, b"", timeout_ms)
        )
        return resp

    def replicate(self, snapshot: bytes, timeout_ms: int = 5000) -> "pb.LighthouseReplicateResponse":
        """Pushes a ``LighthouseServer.snapshot()`` to the replica this
        client currently targets (wire method 6).  Used by the HA election
        driver; applied=False means the receiver holds a higher epoch and
        the SENDER should demote itself."""
        resp = pb.LighthouseReplicateResponse()
        resp.ParseFromString(
            self._call_failover(LIGHTHOUSE_REPLICATE, snapshot, timeout_ms)
        )
        return resp

    def close(self) -> None:
        for client in self._clients.values():
            try:
                client.close()
            except Exception:  # noqa: BLE001
                pass
        self._clients.clear()


class ManagerServer:
    """In-process native Manager server, run by the group's rank 0
    (reference: ManagerServer, src/lib.rs:73-135)."""

    def __init__(
        self,
        replica_id: str,
        lighthouse_addr: str,
        bind: str = "[::]:0",
        store_addr: str = "",
        world_size: int = 1,
        heartbeat_interval_ms: int = 100,
        connect_timeout_ms: int = 10000,
    ) -> None:
        err = ctypes.c_char_p()
        self._ptr = _lib.tf_manager_new(
            replica_id.encode(),
            lighthouse_addr.encode(),
            bind.encode(),
            store_addr.encode(),
            world_size,
            heartbeat_interval_ms,
            connect_timeout_ms,
            ctypes.byref(err),
        )
        if not self._ptr:
            raise RuntimeError(_take_error(err))

    def address(self) -> str:
        return _take_string(_lib.tf_manager_address(self._ptr))

    def set_status(
        self,
        step: int,
        state: str,
        step_time_ms_ewma: float = 0.0,
        step_time_ms_last: float = 0.0,
        allreduce_gb_per_s: float = -1.0,
        ec_shards_held: int = -1,
        ec_shard_step: int = -1,
        ec_k: int = -1,
        link_recv_gbps: float = -1.0,
        link_send_gbps: float = -1.0,
        link_hop_rtt_ms: float = -1.0,
    ) -> None:
        """Pushes live (step, state) into the heartbeat payload so the
        lighthouse's ``GET /metrics`` and ``/status.json`` show per-replica
        progress in real time (see docs/wire.md, Heartbeat fields).  The
        optional step-time telemetry (rolling busy-time EWMA + last
        observation, milliseconds) feeds the lighthouse's straggler
        sentinel; 0 keeps the previously pushed values.
        ``allreduce_gb_per_s`` (the last committed step's gradient
        data-plane throughput) feeds its ``tpuft_allreduce_gb_per_s``
        gauge — there 0 is an authoritative reading (a committed step that
        moved no gradient bytes) and only a negative value keeps the prior
        one, so status-only pushes must leave the default.
        ``ec_shards_held``/``ec_shard_step`` (heartbeat fields 8-9, the
        erasure-shard inventory feeding ``tpuft_ec_shard_coverage``)
        follow the same convention: 0 is an authoritative empty-store
        report, negative keeps the prior reading.  ``ec_k`` (field 10) is
        the EC geometry's data-shard count — the lighthouse coverage
        sentinel pages when per-step coverage drops below k + 1.
        The link-health EWMAs (heartbeat fields 11-13, the slow-link
        sentinel's feed) share the gauge convention: 0 is an
        authoritative "no observation" report, negative keeps the prior
        reading."""
        if self._ptr:
            _lib.tf_manager_set_status(
                self._ptr,
                int(step),
                state.encode(),
                float(step_time_ms_ewma),
                float(step_time_ms_last),
                float(allreduce_gb_per_s),
                int(ec_shards_held),
                int(ec_shard_step),
                int(ec_k),
                float(link_recv_gbps),
                float(link_send_gbps),
                float(link_hop_rtt_ms),
            )

    def set_ledger(
        self,
        goodput_ratio: float,
        compute_seconds: float,
        lost_seconds: "list[float]",
    ) -> None:
        """Pushes the goodput ledger's cumulative counters onto heartbeat
        fields 14-16 (docs/wire.md "Goodput ledger"): the replica's
        productive fraction, productive seconds, and per-cause lost
        seconds in the PINNED taxonomy order
        (:data:`torchft_tpu.obs.ledger.LOST_CAUSES`).  Called once per
        commit vote; counters are monotonic per incarnation."""
        if not self._ptr:
            return
        arr = (ctypes.c_double * len(lost_seconds))(*lost_seconds)
        _lib.tf_manager_set_ledger(
            self._ptr,
            float(goodput_ratio),
            float(compute_seconds),
            arr,
            len(lost_seconds),
        )

    def flight_json(self, limit: int = 0) -> str:
        """Flight-recorder snapshot (newest-first JSON document; ``limit``
        0 = all retained).  Managers serve no HTTP, so this accessor and
        the ``TPUFT_FLIGHT_DIR`` shutdown dump are the read paths."""
        if not self._ptr:
            return "{}"
        return _take_string(_lib.tf_manager_flight_json(self._ptr, int(limit)))

    def flight(self, limit: int = 0) -> dict:
        """Parsed :meth:`flight_json` (see ``LighthouseServer.flight``)."""
        import json

        return json.loads(self.flight_json(limit) or "{}")

    def shutdown(self) -> None:
        if self._ptr:
            _lib.tf_manager_shutdown(self._ptr)

    def __del__(self) -> None:
        try:
            if self._ptr:
                _lib.tf_manager_shutdown(self._ptr)
                _lib.tf_manager_free(self._ptr)
                self._ptr = None
        except Exception:
            pass


class ManagerClient:
    """Client used by every local rank to talk to its group's Manager
    (reference: ManagerClient, src/lib.rs:144-273)."""

    def __init__(self, addr: str, connect_timeout_ms: int = 10000) -> None:
        self._client = _Client(addr, connect_timeout_ms)

    def _quorum(
        self,
        group_rank: int,
        step: int,
        checkpoint_metadata: str,
        shrink_only: bool,
        timeout_ms: int,
        init_sync: bool = True,
        commit_failures: int = 0,
        trace_id: str = "",
    ) -> QuorumResult:
        req = pb.ManagerQuorumRequest(
            group_rank=group_rank,
            step=step,
            checkpoint_metadata=checkpoint_metadata,
            shrink_only=shrink_only,
            init_sync=init_sync,
            commit_failures=commit_failures,
            trace_id=trace_id,
        )
        resp = pb.ManagerQuorumResponse()
        resp.ParseFromString(
            self._client.call(MANAGER_QUORUM, req.SerializeToString(), timeout_ms)
        )
        donor_ranks = list(resp.recover_src_replica_ranks)
        donor_addrs = list(resp.recover_src_manager_addresses)
        if resp.heal and not donor_addrs and resp.recover_src_manager_address:
            # Pre-multi-donor server: degrade to the single assigned donor.
            donor_ranks = [resp.recover_src_replica_rank]
            donor_addrs = [resp.recover_src_manager_address]
        return QuorumResult(
            quorum_id=resp.quorum_id,
            replica_rank=resp.replica_rank,
            replica_world_size=resp.replica_world_size,
            recover_src_manager_address=resp.recover_src_manager_address,
            recover_src_replica_rank=resp.recover_src_replica_rank if resp.heal else None,
            recover_dst_replica_ranks=list(resp.recover_dst_replica_ranks),
            recover_dst_replica_ranks_all=(
                list(resp.recover_dst_replica_ranks_all)
                or list(resp.recover_dst_replica_ranks)
            ),
            recover_src_replica_ranks=donor_ranks if resp.heal else [],
            recover_src_manager_addresses=donor_addrs if resp.heal else [],
            participant_replica_ranks=list(resp.participant_replica_ranks),
            participant_manager_addresses=list(resp.participant_manager_addresses),
            store_address=resp.store_address,
            max_step=resp.max_step,
            max_replica_rank=resp.max_replica_rank if resp.max_replica_rank >= 0 else None,
            max_world_size=resp.max_world_size,
            heal=resp.heal,
        )

    def _checkpoint_metadata(
        self, rank: int, timeout_ms: int, trace_id: str = ""
    ) -> str:
        req = pb.CheckpointMetadataRequest(group_rank=rank, trace_id=trace_id)
        resp = pb.CheckpointMetadataResponse()
        resp.ParseFromString(
            self._client.call(MANAGER_CHECKPOINT_METADATA, req.SerializeToString(), timeout_ms)
        )
        return resp.checkpoint_metadata

    def should_commit(
        self,
        group_rank: int,
        step: int,
        should_commit: bool,
        timeout_ms: int,
        trace_id: str = "",
    ) -> bool:
        req = pb.ShouldCommitRequest(
            group_rank=group_rank,
            step=step,
            should_commit=should_commit,
            trace_id=trace_id,
        )
        resp = pb.ShouldCommitResponse()
        resp.ParseFromString(
            self._client.call(MANAGER_SHOULD_COMMIT, req.SerializeToString(), timeout_ms)
        )
        return resp.should_commit

    def close(self) -> None:
        self._client.close()


class RingEngine:
    """GIL-free ring data plane (native/src/ring.h).

    Owns dup()'d copies of TCPCollective's established lane sockets and runs
    the entire per-hop hot loop natively: scatter-gather socket I/O over the
    caller's flat f32 buffers, the leader/follower tag demux, the
    per-direction virtual-time link pacing, and the bf16/int8 wire codecs —
    all bit-identical to the Python engine (the two interoperate on one
    ring).  Every method releases the GIL for its full duration (ctypes),
    which is the point: a striped allreduce keeps exactly zero interpreter
    work on the wire path.

    Tiers: 0 = flat ring, 1 = ring2d row, 2 = ring2d column.  Directions:
    0 = next (sends), 1 = prev (receives).
    """

    TIER_FLAT = 0
    TIER_ROW = 1
    TIER_COL = 2
    # Ring-pass modes / ops / wires (native/src/ring.h enums).
    PASS_FULL = 0
    PASS_RS = 1
    PASS_AG = 2
    OP_SUM = 0
    OP_MAX = 1
    OP_MIN = 2
    WIRE_RAW = 0
    WIRE_BF16 = 1
    WIRE_INT8 = 2
    WIRE_INT4 = 3

    def __init__(self, lanes: int, shaper_mbps: float = 0.0, shaper_rtt_ms: float = 0.0) -> None:
        self._ptr = _lib.tf_ring_new(int(lanes), float(shaper_mbps), float(shaper_rtt_ms))
        self._lanes = int(lanes)
        # Python→native boundary crossings on the data path (ring_pass +
        # ring_pass_multi calls).  The multi_stripe bench cell asserts this
        # drops to one per op when the batched entry point is in use.
        self.pass_calls = 0

    def set_tier(self, tier: int, next_fds: List[int], prev_fds: List[int]) -> None:
        """Registers one tier's lane sockets (the engine dup()s them; the
        Python sockets stay owned — and closed — by the collective)."""
        n = len(next_fds)
        assert len(prev_fds) == n
        nxt = (ctypes.c_int32 * n)(*next_fds)
        prv = (ctypes.c_int32 * n)(*prev_fds)
        err = ctypes.c_char_p()
        rc = _lib.tf_ring_set_tier(self._ptr, int(tier), n, nxt, prv, ctypes.byref(err))
        if rc != 0:
            raise RuntimeError(_take_error(err))

    @staticmethod
    def _raise(rc: int, err: "ctypes.c_char_p") -> None:
        msg = _take_error(err)
        if rc == 1:
            raise TimeoutError(msg)
        if rc == 2:
            raise ConnectionError(msg)
        raise RuntimeError(msg)

    def exchange(self, tier: int, lane: int, tag: int, payload: bytes, timeout_s: float) -> bytes:
        """Full-duplex framed exchange on (tier, lane): send ``payload``
        under ``tag`` to the next neighbor while receiving the same tag
        from the previous one.  The whole-frame path the Python-orchestrated
        ops (allgather/broadcast/alltoall/barrier, non-f32 fallbacks) ride
        so every read of a lane socket goes through ONE demux."""
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_size_t()
        err = ctypes.c_char_p()
        rc = _lib.tf_ring_exchange(
            self._ptr, int(tier), int(lane), int(tag) & 0xFFFFFFFF,
            payload, len(payload), ctypes.byref(out), ctypes.byref(out_len),
            float(timeout_s), ctypes.byref(err),
        )
        if rc != 0:
            self._raise(rc, err)
        data = ctypes.string_at(out, out_len.value)
        _lib.tf_free(ctypes.cast(out, ctypes.c_void_p))
        return data

    def ring_pass(
        self,
        tier: int,
        lane: int,
        n: int,
        rank: int,
        tag_base: int,
        rs_sub: int,
        ag_sub: int,
        mode: int,
        op: int,
        wire: int,
        chunk_ptrs: List[int],
        chunk_elems: List[int],
        timeout_s: float,
    ) -> None:
        """One ring pass over ``n`` chunk views (raw addresses + element
        counts into the caller's contiguous f32 buffer), IN PLACE.  The
        caller guarantees the buffer outlives the call (it does: the call
        blocks) and that chunk boundaries were cut identically on every
        rank (np.array_split math, same as the Python engine)."""
        ptrs = (ctypes.c_uint64 * n)(*chunk_ptrs)
        elems = (ctypes.c_uint64 * n)(*chunk_elems)
        err = ctypes.c_char_p()
        self.pass_calls += 1
        rc = _lib.tf_ring_pass(
            self._ptr, int(tier), int(lane), int(n), int(rank),
            int(tag_base) & 0xFFFFFFFF, int(rs_sub), int(ag_sub),
            int(mode), int(op), int(wire), ptrs, elems,
            float(timeout_s), ctypes.byref(err),
        )
        if rc != 0:
            self._raise(rc, err)

    def ring_pass_multi(
        self,
        tier: int,
        nstripes: int,
        n: int,
        rank: int,
        lanes: List[int],
        tag_bases: List[int],
        rs_sub: int,
        ag_sub: int,
        mode: int,
        op: int,
        wire: int,
        chunk_ptrs: List[int],
        chunk_elems: List[int],
        timeout_s: float,
    ) -> None:
        """One batched ring pass over a whole stripe set: ``nstripes``
        independent ring passes, stripe ``s`` on lane ``lanes[s]`` under
        ``tag_bases[s]``, each over ``n`` chunk views laid out row-major in
        ``chunk_ptrs``/``chunk_elems`` (stripe s owns slots [s*n, s*n+n)).
        The per-stripe fan-out runs on the engine's internal worker pool so
        Python crosses the capi boundary ONCE per allreduce; a failure on
        any stripe poisons the tier (all stripes + the peer fail fast) and
        the first error is raised."""
        total = int(nstripes) * int(n)
        assert len(chunk_ptrs) == total and len(chunk_elems) == total
        assert len(lanes) == nstripes and len(tag_bases) == nstripes
        lanes_a = (ctypes.c_int32 * nstripes)(*lanes)
        tags_a = (ctypes.c_uint32 * nstripes)(*(int(t) & 0xFFFFFFFF for t in tag_bases))
        ptrs = (ctypes.c_uint64 * total)(*chunk_ptrs)
        elems = (ctypes.c_uint64 * total)(*chunk_elems)
        err = ctypes.c_char_p()
        self.pass_calls += 1
        rc = _lib.tf_ring_pass_multi(
            self._ptr, int(tier), int(nstripes), int(n), int(rank),
            lanes_a, tags_a, int(rs_sub), int(ag_sub),
            int(mode), int(op), int(wire), ptrs, elems,
            float(timeout_s), ctypes.byref(err),
        )
        if rc != 0:
            self._raise(rc, err)

    def set_shm(self, tier: int, direction: int, lane: int, path: str, token: int) -> None:
        """Attaches one lane link to a shared-memory SPSC ring segment
        (created + negotiated by the Python rendezvous).  The link's frames
        move through the segment from then on; the TCP socket stays open as
        the liveness/abort channel.  Raises if the segment's magic or
        generation token doesn't match (stale segment from a dead peer)."""
        err = ctypes.c_char_p()
        rc = _lib.tf_ring_set_shm(
            self._ptr, int(tier), int(direction), int(lane),
            path.encode(), int(token) & 0xFFFFFFFFFFFFFFFF, ctypes.byref(err),
        )
        if rc != 0:
            raise RuntimeError(_take_error(err))

    def counters(self, tier: int) -> "tuple[List[int], List[int]]":
        """(sent, recv) wire-byte counters per lane of one tier (headers
        included) — lane_stats' feed under the native engine."""
        cap = self._lanes
        sent = (ctypes.c_uint64 * cap)()
        recv = (ctypes.c_uint64 * cap)()
        got = _lib.tf_ring_counters(self._ptr, int(tier), sent, recv, cap)
        return list(sent[:got]), list(recv[:got])

    def shaper_counters(self, tier: int, direction: int) -> "tuple[int, int]":
        """(bytes, frames) admitted through one tier-direction's shared
        virtual-time pacer — LinkShaper.bytes_sent/frames_sent parity."""
        b = ctypes.c_uint64()
        f = ctypes.c_uint64()
        _lib.tf_ring_shaper_counters(self._ptr, int(tier), int(direction),
                                     ctypes.byref(b), ctypes.byref(f))
        return int(b.value), int(f.value)

    def link_bytes(self, tier: int, direction: int, lane: int) -> int:
        return int(_lib.tf_ring_link_bytes(self._ptr, int(tier), int(direction), int(lane)))

    def set_hop(self, sample: int, cap: int = 0) -> None:
        """Configures the data-plane flight recorder: record every
        ``sample``-th hop into the bounded timeline ring (0 disables the
        timeline; the per-tier stall aggregates stay on).  ``cap`` > 0
        resizes (and clears) the ring."""
        _lib.tf_ring_set_hop(self._ptr, int(sample), int(cap))

    def hop_stats(self, tier: int) -> "dict":
        """Per-tier stall aggregates: ``{"hops", "send_block_s",
        "recv_wait_s", "combine_s"}`` — lane_stats' native hop feed."""
        out = (ctypes.c_double * 4)()
        _lib.tf_ring_hop_stats(self._ptr, int(tier), out)
        return {
            "hops": int(out[0]),
            "send_block_s": float(out[1]),
            "recv_wait_s": float(out[2]),
            "combine_s": float(out[3]),
        }

    def hop_records(self, cap: int = 4096) -> "List[dict]":
        """The retained hop timeline, oldest first, as dicts with EXACTLY
        the Python engine's HopRecorder keys (collectives
        HOP_RECORD_FIELDS — the cross-engine schema contract)."""
        buf = (ctypes.c_double * (8 * max(1, cap)))()
        n = _lib.tf_ring_hop_records(self._ptr, buf, int(cap))
        records = []
        for i in range(n):
            o = buf[i * 8 : i * 8 + 8]
            records.append(
                {
                    "ts": float(o[0]),
                    "tier": int(o[1]),
                    "lane": int(o[2]),
                    "tag": int(o[3]),
                    "send_s": float(o[4]),
                    "recv_s": float(o[5]),
                    "comb_s": float(o[6]),
                    "nbytes": int(o[7]),
                }
            )
        return records

    def shaper_wait_s(self, tier: int, direction: int) -> float:
        """Seconds one tier-direction's pacer actually slept — the
        "shaping" bucket of the link_attribution split."""
        return float(_lib.tf_ring_shaper_wait_s(self._ptr, int(tier), int(direction)))

    def set_shaper(self, tier: int, direction: int, mbps: float, rtt_ms: float) -> None:
        """Mid-run re-shaping of one tier-direction's pacer (the slow-link
        bench degrades ONE peer link without a reconfigure)."""
        _lib.tf_ring_set_shaper(self._ptr, int(tier), int(direction), float(mbps), float(rtt_ms))

    def open_fd_count(self) -> int:
        """Dup'd lane fds still open — 0 after close() (the native half of
        the no-leaked-fds sweep)."""
        return int(_lib.tf_ring_open_fds(self._ptr)) if self._ptr else 0

    def close(self) -> None:
        """Shutdown + close every dup'd lane fd and join the sender
        threads; idempotent, safe mid-op (blocked ops fail fast)."""
        if self._ptr:
            _lib.tf_ring_close(self._ptr)

    def detach(self) -> None:
        """Quiescent teardown for incremental reconfiguration: releases
        the dup'd lane fds WITHOUT socket shutdown, so the collective's
        surviving sockets stay connected for the next engine generation
        (shm segment files persist too; only the mappings drop).  Raises
        if ops were in flight — the caller must then treat the lanes as
        dead and take the full-rendezvous path."""
        if self._ptr:
            err = ctypes.c_char_p()
            rc = _lib.tf_ring_detach(self._ptr, ctypes.byref(err))
            if rc != 0:
                raise RuntimeError(_take_error(err))

    def __del__(self) -> None:
        try:
            if self._ptr:
                _lib.tf_ring_close(self._ptr)
                _lib.tf_ring_free(self._ptr)
                self._ptr = None
        except Exception:
            pass


class StoreServer:
    """Native key-value rendezvous store server."""

    def __init__(self, bind: str = "[::]:0") -> None:
        err = ctypes.c_char_p()
        self._ptr = _lib.tf_store_new(bind.encode(), ctypes.byref(err))
        if not self._ptr:
            raise RuntimeError(_take_error(err))

    def address(self) -> str:
        return _take_string(_lib.tf_store_address(self._ptr))

    def shutdown(self) -> None:
        if self._ptr:
            _lib.tf_store_shutdown(self._ptr)

    def __del__(self) -> None:
        try:
            if self._ptr:
                _lib.tf_store_shutdown(self._ptr)
                _lib.tf_store_free(self._ptr)
                self._ptr = None
        except Exception:
            pass


class StoreClient:
    """Client for the rendezvous store, with optional key prefixing
    (the PrefixStore analogue, torchft/process_group.py:96-104)."""

    def __init__(self, addr: str, prefix: str = "", connect_timeout_ms: int = 10000) -> None:
        # "host:port/prefix" is accepted like the reference's
        # create_store_client (torchft/process_group.py:85-104).
        if "/" in addr:
            addr, extra = addr.split("/", 1)
            prefix = extra + "/" + prefix if prefix else extra
        self._client = _Client(addr, connect_timeout_ms)
        self._prefix = prefix
        self._addr = addr

    def sub_store(self, prefix: str) -> "StoreClient":
        child = StoreClient.__new__(StoreClient)
        child._client = self._client
        child._addr = self._addr
        child._prefix = f"{self._prefix}/{prefix}" if self._prefix else prefix
        return child

    def _key(self, key: str) -> str:
        return f"{self._prefix}/{key}" if self._prefix else key

    def set(self, key: str, value: bytes, timeout_ms: int = 10000) -> None:
        req = pb.StoreSetRequest(key=self._key(key), value=value)
        self._client.call(STORE_SET, req.SerializeToString(), timeout_ms)

    def get(self, key: str, wait: bool = True, timeout_ms: int = 10000) -> Optional[bytes]:
        req = pb.StoreGetRequest(key=self._key(key), wait=wait)
        resp = pb.StoreGetResponse()
        resp.ParseFromString(self._client.call(STORE_GET, req.SerializeToString(), timeout_ms))
        return resp.value if resp.found else None

    def add(self, key: str, delta: int, timeout_ms: int = 10000) -> int:
        req = pb.StoreAddRequest(key=self._key(key), delta=delta)
        resp = pb.StoreAddResponse()
        resp.ParseFromString(self._client.call(STORE_ADD, req.SerializeToString(), timeout_ms))
        return resp.value

    def delete(self, key: str, timeout_ms: int = 10000) -> None:
        req = pb.StoreDeleteRequest(key=self._key(key))
        self._client.call(STORE_DELETE, req.SerializeToString(), timeout_ms)

    def close(self) -> None:
        self._client.close()
