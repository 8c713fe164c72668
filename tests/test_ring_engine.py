"""Native GIL-free ring engine tests (native/src/ring.cc behind
TCPCollective's TPUFT_RING_ENGINE knob):

- bitwise engine parity, native vs py, across topology (flat/striped/
  ring2d) x codec (f32 raw / bf16 wire / int8) x lanes {1, 2, 4} — the
  contract that lets "auto" switch engines without a numerics review;
- mixed-engine interop on ONE ring (a native rank and a py rank produce
  the same bits — same wire format, same hop order, same arithmetic);
- mid-op abort hygiene: every dup'd lane fd the engine owns closes on
  abort (the fd sweep), errors latch, and reconfigure rebuilds a working
  native engine;
- the GIL-convoy smoke: CPU-bound Python threads inflate the Python
  engine's op latency far more than the native engine's, because the
  native hot loop never re-acquires the GIL mid-op.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import numpy as np
import pytest

from torchft_tpu import _native
from torchft_tpu._native import StoreServer
from torchft_tpu.collectives import TCPCollective

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
        return f"ring_engine/{_PREFIX[0]}"


def _payloads(rank: int, world: int) -> List[List[np.ndarray]]:
    """Per-codec input sets: a stripe-unfriendly odd length (uneven
    np.array_split boundaries), a multi-array bucket, and a 0-d scalar —
    the empty-stripe edge (1 element split across world chunks x lane
    stripes produces all-empty stripe views, the native engine's
    zero-length-frame regression)."""
    rng = np.random.default_rng(1000 + rank)
    big = (rng.standard_normal(6311) * (rank + 1)).astype(np.float32)
    small = np.full((7,), 0.25 * (rank + 1), dtype=np.float32)
    scalar = np.asarray(np.float32(0.1) * (rank + 1))
    return [[big, small], [scalar]]


def _run_ring(
    store,
    world: int,
    lanes: int,
    topology: Optional[str],
    engines: List[str],
    prefix: str,
    transport: Optional[str] = None,
):
    """Runs every codec x payload combination on one ring (rank r uses
    ``engines[r]``); returns {rank: [outputs...]} plus the engine each
    rank's configuration resolved to.  ``transport`` pins the lane
    transport (tcp / shm) and is asserted to have armed."""
    cols = [
        TCPCollective(
            timeout=30.0,
            wire_dtype="bf16",
            lanes=lanes,
            topology=topology,
            engine=engines[r],
            chunk_bytes=4 << 10,  # several stripes even at small payloads
            **({"transport": transport} if transport else {}),
        )
        for r in range(world)
    ]
    results: Dict[int, List[np.ndarray]] = {}
    resolved: Dict[int, str] = {}

    def worker(rank: int) -> None:
        c = cols[rank]
        c.configure(f"{store.address()}/{prefix}", rank, world)
        resolved[rank] = c.ring_engine
        if transport is not None:
            assert c.ring_transport == transport, (
                f"rank {rank}: transport={c.ring_transport} want {transport}"
            )
        got: List[np.ndarray] = []
        for arrays in _payloads(rank, world):
            # f32 raw framing, the bf16 wire (avg covers the divide), and
            # the int8 + int4 codecs — one output list per hop codec.
            got += c.allreduce(
                arrays, op="sum", allow_wire_compression=False
            ).wait(timeout=30)
            got += c.allreduce(arrays, op="avg").wait(timeout=30)
            got += c.allreduce(arrays, op="sum", wire_codec="int8").wait(
                timeout=30
            )
            got += c.allreduce(arrays, op="sum", wire_codec="int4").wait(
                timeout=30
            )
        results[rank] = got

    with ThreadPoolExecutor(max_workers=world) as pool:
        for f in [pool.submit(worker, r) for r in range(world)]:
            f.result(timeout=90)
    for c in cols:
        c.shutdown()
    return results, resolved


def _assert_bitwise(a: List[np.ndarray], b: List[np.ndarray], ctx: str) -> None:
    assert len(a) == len(b), ctx
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.dtype == y.dtype and x.shape == y.shape, f"{ctx} out[{i}]"
        xb = np.ascontiguousarray(x).view(np.uint8)
        yb = np.ascontiguousarray(y).view(np.uint8)
        assert (xb == yb).all(), f"{ctx} out[{i}] differs bitwise"


@pytest.mark.parametrize(
    "world,topology,lanes",
    [
        (2, None, 1),
        (2, None, 2),
        (2, None, 4),
        (4, "ring2d", 1),
        (4, "ring2d", 2),
        (4, "ring2d", 4),
    ],
)
def test_engine_parity_bitwise(store, world, topology, lanes) -> None:
    """native == py BITWISE on every topology x codec x lane combination,
    on every rank — the pin that makes engine selection a pure perf
    knob."""
    outs = {}
    for engine in ("py", "native"):
        results, resolved = _run_ring(
            store, world, lanes, topology, [engine] * world, fresh_prefix()
        )
        assert all(v == engine for v in resolved.values()), resolved
        outs[engine] = results
    for rank in range(world):
        _assert_bitwise(
            outs["py"][rank],
            outs["native"][rank],
            f"world={world} topology={topology} lanes={lanes} rank={rank}",
        )


def test_mixed_engine_ring_interop(store) -> None:
    """A native rank and a py rank on ONE ring: same wire format, same
    results — bitwise equal to the all-py reference run."""
    ref, _ = _run_ring(store, 2, 2, None, ["py", "py"], fresh_prefix())
    mixed, resolved = _run_ring(
        store, 2, 2, None, ["native", "py"], fresh_prefix()
    )
    assert resolved == {0: "native", 1: "py"}
    for rank in range(2):
        _assert_bitwise(ref[rank], mixed[rank], f"mixed rank={rank}")


def test_transport_axis_parity_bitwise(store) -> None:
    """The transport axis of the parity matrix: shm lanes produce the
    SAME BITS as tcp lanes for both engines (and hence across engines),
    over every codec x payload combination — the pin that makes
    TPUFT_RING_TRANSPORT a pure perf knob, exactly like engine
    selection."""
    outs = {}
    for engine in ("py", "native"):
        for transport in ("tcp", "shm"):
            results, resolved = _run_ring(
                store, 2, 2, None, [engine] * 2, fresh_prefix(),
                transport=transport,
            )
            assert all(v == engine for v in resolved.values()), resolved
            outs[(engine, transport)] = results
    base = outs[("py", "tcp")]
    for key, results in outs.items():
        for rank in range(2):
            _assert_bitwise(
                base[rank], results[rank],
                f"engine={key[0]} transport={key[1]} rank={rank}",
            )


def test_mixed_engine_shm_ring_interop(store) -> None:
    """A native rank and a py rank on ONE shm ring: the native engine's
    mmap'd producer/consumer and the Python _ShmRing speak the same
    segment layout — bitwise equal to the all-py tcp reference."""
    ref, _ = _run_ring(store, 2, 2, None, ["py", "py"], fresh_prefix())
    mixed, resolved = _run_ring(
        store, 2, 2, None, ["native", "py"], fresh_prefix(), transport="shm"
    )
    assert resolved == {0: "native", 1: "py"}
    for rank in range(2):
        _assert_bitwise(ref[rank], mixed[rank], f"mixed shm rank={rank}")


def test_native_abort_sweeps_engine_fds_and_reconfigures(store) -> None:
    """Mid-op abort under the native engine: survivors latch (never
    raise), the engine handle detaches, EVERY dup'd lane fd the engine
    owned closes (open_fd_count sweep — the native counterpart of the
    fileno -1 peer sweep), and the next configure() rebuilds a working
    native ring at the shrunken world."""
    world, lanes = 4, 2
    prefix, prefix2 = fresh_prefix(), fresh_prefix()
    cols = [
        TCPCollective(timeout=5.0, lanes=lanes, topology="ring2d",
                      chunk_bytes=4 << 10, engine="native")
        for _ in range(world)
    ]
    engines: Dict[int, object] = {}
    old_sockets: Dict[int, List] = {}
    barrier = threading.Barrier(world)

    def worker(rank: int) -> str:
        c = cols[rank]
        c.configure(f"{store.address()}/{prefix}", rank, world)
        assert c.topology == "ring2d" and c.ring_engine == "native"
        engines[rank] = c._engine
        # Flat + both 2D tiers, all lanes, both directions, dup'd: > 0.
        assert engines[rank].open_fd_count() > 0
        old = list(c._next_lanes) + list(c._prev_lanes)
        old += c._row_tier.peers() + c._col_tier.peers()
        old_sockets[rank] = old
        x = np.ones(8192, dtype=np.float32)
        c.allreduce([x]).wait(timeout=20)
        barrier.wait(timeout=10)
        if rank == world - 1:
            c.abort()
            return "dead"
        work = c.allreduce([x])
        exc = work.exception(timeout=20)
        assert exc is not None, "expected failure after peer abort"
        assert c.errored() is not None
        return "latched"

    with ThreadPoolExecutor(max_workers=world) as pool:
        results = [
            f.result(timeout=90)
            for f in [pool.submit(worker, r) for r in range(world)]
        ]
    assert results.count("latched") == world - 1

    def recover(rank: int):
        c = cols[rank]
        c.configure(f"{store.address()}/{prefix2}", rank, 3)
        assert c.errored() is None
        # The failed generation's engine swept every dup'd fd...
        assert engines[rank].open_fd_count() == 0
        # ...and the Python-owned lane sockets closed too.
        assert all(p.sock.fileno() == -1 for p in old_sockets[rank])
        # The rebuilt (flat: 3 is prime) ring runs on a FRESH native engine.
        assert c.topology == "ring" and c.ring_engine == "native"
        out = c.allreduce(
            [np.full(4, float(rank + 1), dtype=np.float32)]
        ).wait(timeout=20)
        c.shutdown()
        return out[0]

    with ThreadPoolExecutor(max_workers=3) as pool:
        for f in [pool.submit(recover, r) for r in range(3)]:
            np.testing.assert_allclose(f.result(timeout=90), np.full(4, 6.0))


def test_native_engine_resists_gil_convoy(store) -> None:
    """CPU-bound Python threads starve the Python engine's lane workers at
    every GIL handoff (the 5 ms switch-interval convoy); the native
    engine's hot loop never re-acquires the GIL mid-op, so the same load
    inflates it far less.  Pinned: native op wall under load strictly
    below the Python engine's, with margin.  (On this 1-core CI host both
    engines lose raw CPU to the busy threads — measured ~2x native
    advantage; the pin uses 1.33x so scheduler noise cannot flake it.)"""
    N = (8 << 20) // 4
    data = [
        np.random.default_rng(r).standard_normal(N).astype(np.float32)
        for r in range(2)
    ]

    def measure(engine: str) -> float:
        cols = [
            TCPCollective(timeout=120.0, lanes=2, engine=engine)
            for _ in range(2)
        ]
        prefix = fresh_prefix()
        stop = threading.Event()

        def busy() -> None:
            while not stop.is_set():
                pass

        busy_threads = [threading.Thread(target=busy) for _ in range(2)]
        walls: Dict[str, float] = {}

        def run(rank: int) -> None:
            c = cols[rank]
            c.configure(f"{store.address()}/{prefix}_{engine}", rank, 2)
            assert c.ring_engine == engine
            c.allreduce([data[rank]], op="sum").wait(timeout=120)  # warm
            if rank == 0:
                for t in busy_threads:
                    t.start()
                t0 = time.perf_counter()
            for _ in range(4):
                c.allreduce([data[rank]], op="sum").wait(timeout=120)
            if rank == 0:
                walls["w"] = (time.perf_counter() - t0) / 4
                stop.set()
                for t in busy_threads:
                    t.join()

        threads = [threading.Thread(target=run, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for c in cols:
            c.shutdown()
        return walls["w"]

    # Best of 2 trials per engine: the convoy effect is large (~2x), the
    # scheduler noise on a shared host is not small.
    py_wall = min(measure("py") for _ in range(2))
    native_wall = min(measure("native") for _ in range(2))
    assert native_wall * 1.33 < py_wall, (
        f"native {native_wall * 1e3:.0f} ms vs py {py_wall * 1e3:.0f} ms "
        "under GIL load — expected the native engine to resist the convoy"
    )


def test_donate_zero_copy_matches_defensive_copy(store) -> None:
    """``donate=True`` (the zero-copy hint: the native engine reduces in
    place over the caller's buffer) must produce results bitwise equal to
    the defensive-copy path on both engines, and a NON-donated input must
    never be mutated — the default contract the hint opts out of."""
    outs = {}
    for engine in ("py", "native"):
        prefix = fresh_prefix()
        cols = [
            TCPCollective(timeout=30.0, lanes=2, engine=engine,
                          chunk_bytes=4 << 10)
            for _ in range(2)
        ]
        results: Dict[int, List[np.ndarray]] = {}

        def worker(rank: int, engine=engine, cols=cols, prefix=prefix,
                   results=results) -> None:
            c = cols[rank]
            c.configure(f"{store.address()}/{prefix}", rank, 2)
            keep = (np.random.default_rng(rank).standard_normal(4099)
                    .astype(np.float32))
            keep_bytes = keep.tobytes()
            kept = c.allreduce([keep], op="sum").wait(timeout=30)
            assert keep.tobytes() == keep_bytes, "non-donated input mutated"
            gift = keep.copy()
            donated = c.allreduce([gift], op="sum", donate=True).wait(
                timeout=30
            )
            results[rank] = kept + donated

        with ThreadPoolExecutor(max_workers=2) as pool:
            for f in [pool.submit(worker, r) for r in range(2)]:
                f.result(timeout=60)
        for c in cols:
            c.shutdown()
        outs[engine] = results
    for rank in range(2):
        # Donated == kept (same reduction), and native == py bitwise.
        _assert_bitwise(outs["py"][rank][:1], outs["py"][rank][1:],
                        f"py donate rank={rank}")
        _assert_bitwise(outs["native"][rank][:1], outs["native"][rank][1:],
                        f"native donate rank={rank}")
        _assert_bitwise(outs["py"][rank], outs["native"][rank],
                        f"donate engine parity rank={rank}")


def test_engine_construction_failure_warns_once_and_runs_python(
    store, monkeypatch, caplog
) -> None:
    """TPUFT_RING_ENGINE=native when the engine cannot be constructed: ONE
    clear warning, then the Python engine runs — never a silent fallback
    that reports CPU-bound numbers as native."""
    import logging

    from torchft_tpu import collectives as C

    def refuse(*args, **kwargs):
        raise RuntimeError("no lanes for you")

    monkeypatch.setattr(_native.RingEngine, "__init__", refuse)
    monkeypatch.setattr(C, "_native_fallback_warned", False)
    prefix = fresh_prefix()
    cols = [TCPCollective(timeout=10.0, engine="native") for _ in range(2)]
    with caplog.at_level(logging.WARNING, logger="torchft_tpu.collectives"):

        def worker(rank: int) -> None:
            c = cols[rank]
            c.configure(f"{store.address()}/{prefix}", rank, 2)
            assert c.ring_engine == "py"
            out = c.allreduce(
                [np.full(8, float(rank + 1), dtype=np.float32)]
            ).wait(timeout=10)
            np.testing.assert_allclose(out[0], np.full(8, 3.0))

        with ThreadPoolExecutor(max_workers=2) as pool:
            for f in [pool.submit(worker, r) for r in range(2)]:
                f.result(timeout=30)
    for c in cols:
        c.shutdown()
    warnings = [
        r for r in caplog.records
        if "PYTHON ring engine" in r.getMessage()
    ]
    assert len(warnings) == 1, [r.getMessage() for r in caplog.records]
    assert "no lanes for you" in warnings[0].getMessage()
