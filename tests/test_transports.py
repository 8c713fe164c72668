"""Checkpoint transport contract tests.

Reference parity: torchft/checkpointing/transport_test.py:45-155 — one shared
multi-node recovery scenario applied to every transport (3 nodes, all/some
recover, timeout behavior), plus HTTP chunking parametrization
(http_transport_test.py:32-113) and RWLock tests (rwlock_test.py).
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List

import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu._native import StoreServer
from torchft_tpu.checkpointing._rwlock import RWLock
from torchft_tpu.checkpointing.collective_transport import CollectiveTransport
from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.checkpointing.transport import CheckpointTransport
from torchft_tpu.collectives import TCPCollective


@pytest.fixture(scope="module")
def store():
    server = StoreServer(bind="127.0.0.1:0")
    yield server
    server.shutdown()


def make_state_dict(seed: int):
    rng = np.random.RandomState(seed)
    return {
        "model": {
            "w": jnp.asarray(rng.randn(8, 16).astype(np.float32)),
            "b": jnp.asarray(rng.randn(16), dtype=jnp.bfloat16),
        },
        # 0-d leaves ride along on purpose: optax state carries scalar
        # arrays (e.g. adam's `count`) and they must round-trip with their
        # () shape intact, not crash as_u8 or get promoted to (1,).
        "optim": [
            np.arange(10, dtype=np.int64) * seed,
            {"lr": 0.125, "count": np.asarray(seed * 3, dtype=np.int32)},
        ],
        "scalar": jnp.asarray(float(seed), dtype=jnp.float32),
        "tpuft": {"step": 7, "batches_committed": 21},
    }


def assert_state_dicts_equal(a, b) -> None:
    import jax

    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        if hasattr(x, "shape"):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            assert x == y


_COUNTER = [0]


def run_multi_recovery_test(
    make_transport: Callable[[int, List[TCPCollective]], CheckpointTransport],
    store,
) -> None:
    """3 nodes; node 0 serves, nodes 1 and 2 recover; results must match
    node 0's state bitwise (the shared scenario of transport_test.py:45-155)."""
    world = 3
    _COUNTER[0] += 1
    prefix = f"transport/{_COUNTER[0]}"
    collectives = [TCPCollective(timeout=10.0) for _ in range(world)]
    state = make_state_dict(seed=1)
    results = {}
    barrier = threading.Barrier(world)
    # Transports must exist before recv (to read metadata): build eagerly.
    metadatas = {}
    transports = {}

    def boot(rank: int):
        collectives[rank].configure(f"{store.address()}/{prefix}", rank, world)
        transport = make_transport(rank, collectives)
        transports[rank] = transport
        metadatas[rank] = transport.metadata()

    with ThreadPoolExecutor(max_workers=world) as pool:
        list(pool.map(boot, range(world)))

    def node(rank: int):
        transport = transports[rank]
        try:
            if rank == 0:
                transport.send_checkpoint(
                    dst_ranks=[1, 2], step=7, state_dict=state, timeout=20.0
                )
                barrier.wait(timeout=20)
            else:
                got = transport.recv_checkpoint(
                    src_rank=0, metadata=metadatas[0], step=7, timeout=20.0
                )
                results[rank] = got
                barrier.wait(timeout=20)
        finally:
            transport.shutdown()
            collectives[rank].shutdown()

    with ThreadPoolExecutor(max_workers=world) as pool:
        futs = [pool.submit(node, r) for r in range(world)]
        for f in futs:
            f.result(timeout=60)

    assert set(results) == {1, 2}
    for rank in (1, 2):
        assert_state_dicts_equal(results[rank], state)


def test_http_transport_multi_recovery(store) -> None:
    run_multi_recovery_test(lambda rank, colls: HTTPTransport(timeout=10.0), store)


def test_http_transport_chunked_multi_recovery(store, monkeypatch) -> None:
    # Force the parallel-chunk receive path: the receiver's cpu-count
    # heuristic would otherwise (correctly) fall back to the single /full
    # stream on this 1-core host and leave chunk assembly uncovered.
    monkeypatch.setenv("TPUFT_HTTP_CHUNK_WORKERS", "3")
    run_multi_recovery_test(
        lambda rank, colls: HTTPTransport(timeout=10.0, num_chunks=3), store
    )


def test_collective_transport_multi_recovery(store) -> None:
    run_multi_recovery_test(
        lambda rank, colls: CollectiveTransport(colls[rank], timeout=10.0), store
    )


def test_http_transport_multi_donor_striped(store) -> None:
    """3 donors each serving the same snapshot: the receiver stripes the
    fetch across all of them and reassembles bitwise-identical state."""
    state = make_state_dict(seed=2)
    donors = [HTTPTransport(timeout=10.0) for _ in range(3)]
    rx = HTTPTransport(timeout=10.0)
    try:
        for d in donors:
            d.send_checkpoint([3], step=11, state_dict=state, timeout=10.0)
            assert d.wait_snapshot(10.0)
        got = rx.recv_checkpoint(
            0, [d.metadata() for d in donors], step=11, timeout=10.0
        )
        assert_state_dicts_equal(got, state)
    finally:
        for d in donors:
            d.shutdown()
        rx.shutdown()


def test_http_transport_always_stamps_crcs_and_refuses_a_flipped_byte(monkeypatch) -> None:
    """There is no way to turn the donor's CRC32C stamping off: with
    ``TPUFT_HTTP_CRC=0`` in the environment a served snapshot still carries
    one CRC a buffer, and a byte flipped after they were computed is refused
    by the healer instead of being installed."""
    monkeypatch.setenv("TPUFT_HTTP_CRC", "0")
    state = make_state_dict(seed=4)
    src = HTTPTransport(timeout=10.0)
    dst = HTTPTransport(timeout=10.0)
    try:
        src.send_checkpoint([1], step=5, state_dict=state, timeout=10.0)
        assert src.wait_snapshot(10.0)
        meta, buffers = src._state[0], src._state[1]
        assert meta.crcs is not None and len(meta.crcs) == len(buffers)
        assert_state_dicts_equal(
            dst.recv_checkpoint(1, src.metadata(), step=5, timeout=10.0), state
        )
        at = next(i for i, b in enumerate(buffers) if b.size)
        flipped = np.array(buffers[at])  # a device leaf's buffer is read-only
        flipped.reshape(-1).view(np.uint8)[0] ^= 0x01
        buffers[at] = flipped
        with pytest.raises(Exception, match="checksum mismatch"):
            dst.recv_checkpoint(1, src.metadata(), step=5, timeout=10.0)
    finally:
        src.shutdown()
        dst.shutdown()


def test_http_transport_donor_death_mid_heal_failover(store) -> None:
    """The serving donor dies AFTER the header is fetched (mid-heal): the
    receiver fails its stripes over to the second donor and still
    reassembles the full state."""
    state = make_state_dict(seed=3)
    a = HTTPTransport(timeout=5.0)
    b = HTTPTransport(timeout=5.0)
    rx = HTTPTransport(timeout=5.0)
    try:
        for d in (a, b):
            d.send_checkpoint([2], step=7, state_dict=state, timeout=5.0)
            assert d.wait_snapshot(5.0)
        a_url = a.metadata()
        orig = rx._urlopen
        killed = []

        def hooked(url, timeout):
            # Deterministic mid-heal death: the moment the receiver asks
            # donor A for its first STRIPE (header already served), A dies.
            if url.startswith(a_url) and "chunk_" in url and not killed:
                killed.append(url)
                a.shutdown()
            return orig(url, timeout)

        rx._urlopen = hooked
        got = rx.recv_checkpoint(0, [a_url, b.metadata()], step=7, timeout=5.0)
        assert killed, "no stripe was ever routed to donor A"
        assert_state_dicts_equal(got, state)
    finally:
        for t in (a, b, rx):
            t.shutdown()


def test_http_transport_all_donors_dead_raises(store) -> None:
    a = HTTPTransport(timeout=2.0)
    b = HTTPTransport(timeout=2.0)
    dead = [a.metadata(), b.metadata()]
    a.shutdown()
    b.shutdown()
    rx = HTTPTransport(timeout=2.0)
    try:
        with pytest.raises(Exception):
            rx.recv_checkpoint(0, dead, step=1, timeout=2.0)
    finally:
        rx.shutdown()


def test_http_transport_async_snapshot_off_critical_path(store, monkeypatch) -> None:
    """send_checkpoint must return without waiting for the device->host
    flatten (the background snapshotter does it); a fetch racing the flip
    blocks until the snapshot lands instead of 404ing."""
    import torchft_tpu.checkpointing.http_transport as ht

    orig_flatten = ht.flatten_state_dict

    def slow_flatten(sd, step=0):
        time.sleep(0.5)
        return orig_flatten(sd, step=step)

    monkeypatch.setattr(ht, "flatten_state_dict", slow_flatten)
    t = HTTPTransport(timeout=5.0)
    try:
        t0 = time.monotonic()
        t.send_checkpoint([1], step=2, state_dict={"x": np.ones(4)}, timeout=5.0)
        enqueue = time.monotonic() - t0
        assert enqueue < 0.25, f"send_checkpoint blocked {enqueue:.3f}s on the flatten"
        got = t.recv_checkpoint(0, t.metadata(), step=2, timeout=5.0)
        np.testing.assert_array_equal(got["x"], np.ones(4))
    finally:
        t.shutdown()


def test_http_transport_malformed_requests_4xx(store) -> None:
    """Garbage paths, stale steps, and out-of-range/malformed stripe params
    must come back as 4xx (never an unhandled 500 traceback) while a
    concurrent legitimate fetch succeeds."""
    import urllib.error
    import urllib.request

    state = {"a": np.ones(8, dtype=np.float32), "b": np.zeros(4, dtype=np.float32)}
    t = HTTPTransport(timeout=5.0, num_chunks=2)
    try:
        t.send_checkpoint([1], step=5, state_dict=state, timeout=5.0)
        assert t.wait_snapshot(5.0)
        base = t.metadata()

        def code_of(url: str) -> int:
            try:
                with urllib.request.urlopen(url, timeout=5.0) as r:
                    return r.status
            except urllib.error.HTTPError as e:
                return e.code

        garbage = {
            f"{base}/not/a/thing": 404,
            f"{base}/checkpoint/abc/full": 400,       # non-integer step
            f"{base}/checkpoint/-3/full": 404,        # negative step
            f"{base}/checkpoint/9/full": 404,         # stale step
            f"{base}/checkpoint/5/chunk_99": 404,     # out-of-range index
            f"{base}/checkpoint/5/chunk_xx": 404,     # malformed index
            f"{base}/checkpoint/5/chunk_0?n=0": 400,  # bad stripe count
            f"{base}/checkpoint/5/chunk_0?n=zz": 400,
            f"{base}/checkpoint/5/chunk_2?n=2": 404,  # idx >= n
        }
        for url, want in garbage.items():
            got = code_of(url)
            assert 400 <= got < 500 and got == want, f"{url}: got {got}, want {want}"

        # Legitimate fetch succeeds while garbage requests hammer the server.
        stop = threading.Event()

        def hammer() -> None:
            urls = list(garbage)
            i = 0
            while not stop.is_set():
                code_of(urls[i % len(urls)])
                i += 1

        th = threading.Thread(target=hammer)
        th.start()
        try:
            got = t.recv_checkpoint(0, base, step=5, timeout=5.0)
            np.testing.assert_array_equal(got["a"], state["a"])
        finally:
            stop.set()
            th.join(timeout=5)
    finally:
        t.shutdown()


def test_http_transport_wrong_step_404(store) -> None:
    t = HTTPTransport(timeout=5.0)
    try:
        t.send_checkpoint([1], step=3, state_dict={"x": np.ones(2)}, timeout=5.0)
        with pytest.raises(Exception):
            t.recv_checkpoint(src_rank=0, metadata=t.metadata(), step=9, timeout=5.0)
        # Correct step succeeds.
        got = t.recv_checkpoint(src_rank=0, metadata=t.metadata(), step=3, timeout=5.0)
        np.testing.assert_array_equal(got["x"], np.ones(2))
    finally:
        t.shutdown()


def test_http_transport_disallow_blocks_serving(store) -> None:
    t = HTTPTransport(timeout=0.5)
    try:
        t.send_checkpoint([1], step=1, state_dict={"x": np.ones(2)}, timeout=5.0)
        t.disallow_checkpoint()
        # Serving now times out (write lock held): 503 -> HTTPError.
        with pytest.raises(Exception):
            t.recv_checkpoint(src_rank=0, metadata=t.metadata(), step=1, timeout=3.0)
    finally:
        t.shutdown()


def test_rwlock_basics() -> None:
    lock = RWLock()
    assert lock.r_acquire(timeout=1)
    assert lock.r_acquire(timeout=1)  # shared
    assert not lock.w_acquire(timeout=0.05)  # blocked by readers
    lock.r_release()
    lock.r_release()
    assert lock.w_acquire(timeout=1)
    assert not lock.r_acquire(timeout=0.05)  # blocked by writer
    lock.w_release()
    assert lock.r_acquire(timeout=1)
    lock.r_release()


def test_rwlock_writer_preference() -> None:
    lock = RWLock()
    assert lock.r_acquire(timeout=1)
    acquired = []

    def writer():
        acquired.append(lock.w_acquire(timeout=5))

    t = threading.Thread(target=writer)
    t.start()
    time.sleep(0.1)
    # A new reader must queue behind the waiting writer.
    assert not lock.r_acquire(timeout=0.05)
    lock.r_release()
    t.join(timeout=5)
    assert acquired == [True]
    lock.w_release()
