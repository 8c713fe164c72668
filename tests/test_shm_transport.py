"""Same-host shm lane transport (TPUFT_RING_TRANSPORT=shm) tests:

- the stale-segment generation guard: a leftover segment from a dead
  peer (wrong token, wrong magic) is REFUSED at attach, never reused;
- segment hygiene across the normal lifecycle: negotiated segments
  exist while the ring is armed and every one is unlinked on shutdown;
- the SIGKILL crash story: a real subprocess peer killed mid-op leaves
  the survivor latched (never raising), abort() reclaims BOTH ends'
  segments (each end tracks every negotiated path for exactly this),
  and a fresh configure() builds a working shm ring again;
- a direct _ShmRing producer/consumer roundtrip across the engine-shared
  segment layout.
"""

import glob
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu._native import StoreServer
from torchft_tpu.collectives import (
    _SHM_HDR,
    _SHM_MAGIC,
    _ShmRing,
    TCPCollective,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def store():
    server = StoreServer(bind="127.0.0.1:0")
    yield server
    server.shutdown()


_PREFIX = [0]
_PREFIX_LOCK = threading.Lock()


def fresh_prefix() -> str:
    with _PREFIX_LOCK:
        _PREFIX[0] += 1
        return f"shm_transport/{_PREFIX[0]}"


def _segments(*child_pids: int) -> set:
    # The segments of this process and of the children it started
    # (`_create_shm_segment` names them tpuft-<pid>-...): other test workers
    # on the machine make their own.
    return {
        path
        for pid in (os.getpid(), *child_pids)
        for path in glob.glob(f"/dev/shm/tpuft-{pid}-*")
    }


def _make_segment(path: str, token: int, cap: int = 4096) -> None:
    with open(path, "wb") as f:
        f.write(struct.pack("<QQQQI", _SHM_MAGIC, token, 0, 0, 0))
        f.write(b"\x00" * (_SHM_HDR + cap - f.tell()))


def test_stale_segment_refused(tmp_path) -> None:
    """The generation token is what makes a crashed peer's leftover
    segment unattachable: attach verifies magic + token against the value
    negotiated on THIS connection and refuses any mismatch."""
    path = str(tmp_path / "seg")
    _make_segment(path, token=1234)
    a, b = socket.socketpair()
    try:
        with pytest.raises(ConnectionError, match="stale shm segment"):
            _ShmRing(path, 9999, a)
        # Wrong magic is refused the same way (a truncated / foreign file).
        bad = str(tmp_path / "bad")
        _make_segment(bad, token=1234)
        with open(bad, "r+b") as f:
            f.write(b"\x00" * 8)
        with pytest.raises(ConnectionError, match="stale shm segment"):
            _ShmRing(bad, 1234, a)
        # The negotiated token attaches, and the ring actually moves bytes.
        tx = _ShmRing(path, 1234, a)
        rx = _ShmRing(path, 1234, b)
        payload = np.arange(64, dtype=np.uint8)
        tx.write(payload, timeout=5.0)
        got = bytearray(64)
        rx.read_into(memoryview(got), timeout=5.0)
        assert bytes(got) == payload.tobytes()
        tx.close()
        rx.close()
    finally:
        a.close()
        b.close()


def test_shm_lanes_roundtrip_and_unlink(store) -> None:
    """2 ranks on shm lanes: transport resolves to shm, results match the
    tcp ring bitwise, segments exist while armed and are all unlinked on
    shutdown."""
    before = _segments()
    prefix = fresh_prefix()
    ref_prefix = fresh_prefix()
    outs = {}
    for transport, pfx in (("tcp", ref_prefix), ("shm", prefix)):
        cols = [
            TCPCollective(timeout=20.0, lanes=2, transport=transport,
                          chunk_bytes=4 << 10)
            for _ in range(2)
        ]
        mid_segments = {}

        def worker(rank: int):
            c = cols[rank]
            c.configure(f"{store.address()}/{pfx}", rank, 2)
            assert c.ring_transport == transport
            if rank == 0:
                mid_segments[0] = _segments() - before
            x = (np.arange(3001, dtype=np.float32) + 1) * (rank + 1)
            return c.allreduce([x], wire_codec="int8").wait(timeout=20)[0]

        with ThreadPoolExecutor(max_workers=2) as pool:
            got = [f.result(timeout=60)
                   for f in [pool.submit(worker, r) for r in range(2)]]
        if transport == "shm":
            # 2 lanes x 2 directed links -> negotiated segments were live.
            assert len(mid_segments[0]) >= 2, mid_segments
        assert np.array_equal(got[0], got[1])
        outs[transport] = got[0]
        for c in cols:
            c.shutdown()
    assert np.array_equal(
        outs["tcp"].view(np.uint8), outs["shm"].view(np.uint8)
    ), "shm lanes changed the bits"
    assert _segments() == before, "leaked shm segments"


_CHILD_SRC = """
import sys, time
import numpy as np
sys.path.insert(0, sys.argv[4])
from torchft_tpu.collectives import TCPCollective
addr, prefix, mode = sys.argv[1], sys.argv[2], sys.argv[3]
c = TCPCollective(timeout=30.0, lanes=2, transport="shm", chunk_bytes=4 << 10)
c.configure(addr + "/" + prefix, 1, 2)
out = c.allreduce([np.full(2048, 2.0, dtype=np.float32)]).wait(timeout=30)
assert float(out[0][0]) == 3.0, out[0][0]
print("READY", flush=True)
if mode == "hang":
    time.sleep(120)
c.shutdown()
print("DONE", flush=True)
"""


def _spawn_child(store, prefix: str, mode: str) -> subprocess.Popen:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.Popen(
        [sys.executable, "-c", _CHILD_SRC, store.address(), prefix, mode,
         _REPO_ROOT],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
    )


def test_shm_peer_sigkill_cleanup_and_heal(store) -> None:
    """Kill -9 a real subprocess peer while the survivor's op is in
    flight: the survivor latches (never raises), abort() unlinks every
    negotiated segment INCLUDING the dead peer's (both ends track every
    path), and a fresh configure() arms a working shm ring again."""
    before = _segments()
    prefix, prefix2 = fresh_prefix(), fresh_prefix()
    c = TCPCollective(timeout=10.0, lanes=2, transport="shm",
                      chunk_bytes=4 << 10)
    child = _spawn_child(store, prefix, mode="hang")
    try:
        c.configure(f"{store.address()}/{prefix}", 0, 2)
        assert c.ring_transport == "shm"
        out = c.allreduce([np.full(2048, 1.0, dtype=np.float32)]).wait(
            timeout=30
        )
        assert float(out[0][0]) == 3.0
        line = child.stdout.readline()
        assert "READY" in line, line
        # Second op: the child is asleep and never joins, so this blocks
        # in the shm wait loop — then the SIGKILL lands and the liveness
        # poll (socket EOF) fails the op.
        work = c.allreduce([np.full(2048, 1.0, dtype=np.float32)])
        time.sleep(0.2)
        child.kill()
        exc = work.exception(timeout=30)
        assert exc is not None, "expected failure after peer SIGKILL"
        assert c.errored() is not None
    finally:
        if child.poll() is None:
            child.kill()
        child.wait(timeout=10)
        child.stdout.close()
    c.abort()
    assert _segments(child.pid) == before, "survivor failed to reclaim segments"

    # Heal: a fresh peer process, a fresh prefix, a working shm ring.
    child2 = _spawn_child(store, prefix2, mode="exit")
    try:
        c.configure(f"{store.address()}/{prefix2}", 0, 2)
        assert c.errored() is None
        assert c.ring_transport == "shm"
        out = c.allreduce([np.full(2048, 1.0, dtype=np.float32)]).wait(
            timeout=30
        )
        assert float(out[0][0]) == 3.0
        assert child2.wait(timeout=30) == 0, child2.stdout.read()
    finally:
        if child2.poll() is None:
            child2.kill()
            child2.wait(timeout=10)
        child2.stdout.close()
        c.shutdown()
    assert _segments(child.pid, child2.pid) == before, "leaked shm segments after heal"
