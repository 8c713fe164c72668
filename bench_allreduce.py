"""Allreduce data-plane benchmarks: the striped multi-lane ring + pipelined
bucket pipeline, measured end to end.

Three sections, written as one JSON artifact (``ALLREDUCE_BENCH.json``):

  lanes          — 2-rank TCPCollective under a shaped link
                   (``TPUFT_SHAPED_LINK``): a GradientAverager-style stream
                   of bucket allreduces for 1/2/4 lanes; GB/s = payload /
                   wall.  The per-peer LinkShaper budget is SHARED across
                   lanes (lanes cannot widen the modeled link), so lane
                   speedups here come only from overlap: stripe k's local
                   sum and encode/decode under stripe k+1's serialization,
                   bucket-to-bucket wire overlap, and per-frame half-RTT
                   hiding — the honest physics of parallel TCP streams on
                   one bottleneck path.  Each rank runs in its OWN
                   subprocess (the deployment shape: one process per
                   replica group) — in-process thread ranks share a GIL
                   and understate multi-lane overlap.

  e2e            — 2 full replica groups (real lighthouse + Managers, in
                   threads) training a synthetic step loop; pipelined
                   GradientAverager (per-bucket D2H + issue) vs the
                   monolithic reference path (one blocking fetch, then pack)
                   on the same shaped link and lane count — steps/s and
                   committed counts, plus the Manager's own
                   ``allreduce_gb_per_s`` step_summary telemetry.  The
                   ``--device-prep`` A/B adds the device-resident wire-prep
                   trial (on-device bf16 cast: the D2H fetch moves wire
                   bytes, ~half of f32) and a sharded-fetch trial on a
                   multi-device worker platform (``--sharded-devices``);
                   every e2e record carries ``d2h_bytes`` / ``h2d_bytes``
                   / ``wire_bytes`` / ``fetch_slices`` from the averager's
                   transfer accounting.

  peer_kill      — 3 replica groups, lanes > 1: one group dies mid-step
                   (collective aborted + manager gone).  The survivors'
                   in-flight allreduce must LATCH the error (not raise),
                   ``should_commit`` must fail cleanly, and the next quorum
                   must rebuild every lane against the shrunken world with
                   the old lane sockets closed (no fd leaks).

Run as
  python bench_allreduce.py [--mb 64] [--lanes 1 2 4] [--mbps 400]
                            [--rtt-ms 20] [--out ALLREDUCE_BENCH.json]
  python bench_allreduce.py --quick      # tier-1 smoke (small dict, 1 vs 2)
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from typing import Any, Dict, List, Optional

import numpy as np


def _shaped(mbps: float, rtt_ms: float):
    """Context manager setting TPUFT_SHAPED_LINK for the block."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        prior = os.environ.get("TPUFT_SHAPED_LINK")
        if mbps > 0:
            os.environ["TPUFT_SHAPED_LINK"] = f"{mbps}:{rtt_ms}"
        try:
            yield
        finally:
            if mbps > 0:
                if prior is None:
                    del os.environ["TPUFT_SHAPED_LINK"]
                else:
                    os.environ["TPUFT_SHAPED_LINK"] = prior

    return ctx()


def make_buckets(total_bytes: int, n_buckets: int) -> List[np.ndarray]:
    per = max(1, total_bytes // n_buckets // 4)
    return [np.full((per,), float(i), dtype=np.float32) for i in range(n_buckets)]


# ---------------------------------------------------------------------------
# Section 1: collective-level lane sweep
# ---------------------------------------------------------------------------


def _lane_rank_body(
    collective, rank: int, nbytes: int, n_buckets: int, timeout: float,
    world: int = 2,
) -> Dict[str, Any]:
    """One rank's bucket stream: issue every bucket, then drain — the
    GradientAverager traffic shape.  Shared by the threaded (--quick) and
    subprocess drivers."""
    buckets = make_buckets(nbytes, n_buckets)
    t0 = time.perf_counter()
    # The scaled bucket is a temporary — donate it so the native engine
    # reduces in place over the caller's buffer (zero working-buffer copy);
    # the Python engine ignores the hint, so the A/B stays same-workload.
    works = [
        collective.allreduce([b * (rank + 1)], op="sum", donate=True)
        for b in buckets
    ]
    outs = [w.wait(timeout=timeout) for w in works]
    wall = time.perf_counter() - t0
    expected_last = (n_buckets - 1) * world * (world + 1) / 2.0
    assert float(np.asarray(outs[0][0])[0]) == 0.0
    # Sanity tolerance scales with the sum: shaped links auto-select the
    # bf16 wire, whose per-hop quantization ulp grows with the magnitude
    # (at world 32 the bucket sum is ~5e2 and one bf16 ulp is ~2 — a fixed
    # 0.5 would flag correct arithmetic).
    tol = max(0.5, 0.02 * expected_last)
    assert abs(float(np.asarray(outs[-1][0])[0]) - expected_last) < tol
    return {"wall_s": wall, "lane_stats": collective.lane_stats(),
            "topology": collective.topology,
            "transport": collective.ring_transport}


def _lane_worker(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Subprocess entry for one lane-sweep rank (--worker lanes)."""
    from torchft_tpu.collectives import TCPCollective

    world = int(cfg.get("world", 2))
    c = TCPCollective(
        timeout=cfg["timeout"], wire_dtype=cfg["wire_dtype"], lanes=cfg["lanes"],
        topology=cfg.get("topology"), engine=cfg.get("engine"),
        transport=cfg.get("transport"),
    )
    try:
        c.configure(cfg["store"], cfg["rank"], world)
        return _lane_rank_body(
            c, cfg["rank"], cfg["nbytes"], cfg["n_buckets"], cfg["timeout"],
            world=world,
        )
    finally:
        c.shutdown()


def _spawn_workers(kind: str, cfgs: List[Dict[str, Any]], timeout: float) -> List[dict]:
    """Runs one worker subprocess per cfg (``--worker`` re-entry into this
    file), each writing its JSON result to a temp file — one OS process per
    rank, so lane worker threads never share a GIL across ranks."""
    import subprocess
    import sys
    import tempfile

    procs = []
    outs = []
    for cfg in cfgs:
        f = tempfile.NamedTemporaryFile(
            mode="w", suffix=".json", prefix="tpuft_bench_", delete=False
        )
        f.close()
        outs.append(f.name)
        cfg = dict(cfg, out=f.name)
        procs.append(
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--worker", kind, "--cfg", json.dumps(cfg)],
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
            )
        )
    results = []
    try:
        for p, path in zip(procs, outs):
            rc = p.wait(timeout=timeout)
            with open(path) as fh:
                raw = fh.read()
            if rc != 0 or not raw.strip():
                raise RuntimeError(f"{kind} worker failed (rc={rc})")
            results.append(json.loads(raw))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for path in outs:
            try:
                os.unlink(path)
            except OSError:
                pass
    return results


def bench_lanes(
    payload_mb: float,
    lanes: int,
    mbps: float,
    rtt_ms: float,
    n_buckets: int = 8,
    wire_dtype: str = "auto",
    timeout: float = 300.0,
    procs: bool = True,
    trials: int = 1,
    world: int = 2,
    topology: Optional[str] = None,
    engine: Optional[str] = None,
    transport: Optional[str] = None,
) -> Dict[str, Any]:
    """``world``-rank bucketed allreduce stream at the given lane count and
    topology under the shaped link.  ``procs=True`` (the artifact path)
    runs each rank in its own subprocess; ``procs=False`` (--quick) keeps
    threads for speed.  ``trials`` > 1 reports the BEST wall of N runs —
    the modeled link is deterministic, so the best trial is the one least
    polluted by OS scheduler noise (the 2-core CI hosts this runs on
    context-switch a dozen bench threads; a single trial can lose 30% to an
    unlucky schedule).  ``topology`` pins the cross-group ring layout
    ("ring"/"ring2d"); None keeps the collective's default.  ``engine``
    pins the ring hot-loop engine ("py"/"native" — the A/B the engine
    sweep records); None keeps the collective's default (auto).  Returns
    wall + GB/s + lane byte counters (per-tier under ring2d) + the engine
    the configuration actually resolved to."""
    from torchft_tpu._native import StoreServer

    nbytes = int(payload_mb * (1 << 20))
    store = StoreServer(bind="127.0.0.1:0")
    per_rank: List[dict] = []
    walls: List[float] = []
    try:
        with _shaped(mbps, rtt_ms):
            if procs:
                for trial in range(max(1, trials)):
                    prefix = (
                        f"{store.address()}/lanes{lanes}_{wire_dtype}"
                        f"_{topology or 'default'}_{engine or 'auto'}"
                        f"_{transport or 'default'}_w{world}_t{trial}"
                    )
                    cfgs = [
                        {"store": prefix, "rank": r, "lanes": lanes,
                         "nbytes": nbytes, "n_buckets": n_buckets,
                         "wire_dtype": wire_dtype, "timeout": timeout,
                         "world": world, "topology": topology,
                         "engine": engine, "transport": transport}
                        for r in range(world)
                    ]
                    attempt = _spawn_workers("lanes", cfgs, timeout + 60)
                    wall = max(r["wall_s"] for r in attempt)
                    if not per_rank or wall < max(r["wall_s"] for r in per_rank):
                        per_rank = attempt
                    walls.append(wall)
            else:
                from torchft_tpu.collectives import TCPCollective

                for trial in range(max(1, trials)):
                    prefix = (
                        f"{store.address()}/lanes{lanes}_{wire_dtype}"
                        f"_{topology or 'default'}_{engine or 'auto'}"
                        f"_{transport or 'default'}_w{world}_t{trial}"
                    )
                    cols = [
                        TCPCollective(timeout=timeout, wire_dtype=wire_dtype,
                                      lanes=lanes, topology=topology,
                                      engine=engine, transport=transport)
                        for _ in range(world)
                    ]
                    results: Dict[int, dict] = {}
                    errors: List[BaseException] = []
                    try:
                        threads = [
                            threading.Thread(
                                target=cols[r].configure, args=(prefix, r, world)
                            )
                            for r in range(world)
                        ]
                        for t in threads:
                            t.start()
                        for t in threads:
                            t.join()

                        def run(rank: int, cols=cols, results=results,
                                errors=errors) -> None:
                            try:
                                results[rank] = _lane_rank_body(
                                    cols[rank], rank, nbytes, n_buckets,
                                    timeout, world=world,
                                )
                            except BaseException as e:  # noqa: BLE001
                                errors.append(e)

                        rs = [threading.Thread(target=run, args=(r,))
                              for r in range(world)]
                        for t in rs:
                            t.start()
                        for t in rs:
                            t.join()
                        if errors:
                            raise errors[0]
                    finally:
                        for c in cols:
                            c.shutdown()
                    attempt = [results[r] for r in range(world)]
                    wall = max(r["wall_s"] for r in attempt)
                    if not per_rank or wall < max(r["wall_s"] for r in per_rank):
                        per_rank = attempt
                    walls.append(wall)
    finally:
        store.shutdown()
    wall = max(r["wall_s"] for r in per_rank)
    actual = sum(b.nbytes for b in make_buckets(nbytes, n_buckets))
    out = {
        "section": "lanes",
        "lanes": lanes,
        "world": world,
        "topology": per_rank[0].get("topology", "ring"),
        # The ring hot-loop engine this configuration RESOLVED to ("py" or
        # "native") — requested "native" on a stale .so degrades to "py"
        # and the record says so, per the no-silent-fallback contract.
        "engine": per_rank[0]["lane_stats"].get("engine", "py"),
        # The ring-lane transport that actually ran ("shm" only when the
        # same-host handshake armed at least one segment) — requested shm
        # that degraded to tcp must land under the truth.
        "transport": per_rank[0].get("transport", "tcp"),
        "payload_mb": round(actual / (1 << 20), 2),
        "buckets": n_buckets,
        "wire_dtype": wire_dtype,
        "link": {"mbps": mbps, "rtt_ms": rtt_ms},
        "ranks": "subprocess" if procs else "threads",
        "wall_s": round(wall, 3),
        "gb_per_s": round(actual / 1e9 / wall, 4),
        # Per-lane wire bytes from rank 0 (striping balance evidence).
        "lane_bytes_sent": per_rank[0]["lane_stats"].get("sent"),
    }
    tiers = per_rank[0]["lane_stats"].get("tiers")
    if tiers:
        # Per-tier byte attribution under ring2d (row vs column traffic).
        out["tier_bytes_sent"] = {
            name: sum(t["sent"]) for name, t in tiers.items()
        }
    if len(walls) > 1:
        out["trial_walls_s"] = [round(w, 3) for w in walls]
    return out


def check_engine_parity(
    n_elems: int = 1 << 14, lanes: int = 2, timeout: float = 60.0
) -> bool:
    """Bitwise engine parity on live rings: the SAME deterministic payload
    allreduced by a 2-rank py-engine pair and a 2-rank native-engine pair
    (f32 raw, bf16 wire, and the int8 codec) must produce IDENTICAL bits —
    the contract that lets "auto" switch engines without a numerics review.
    The exhaustive topology x codec x
    lanes matrix lives in tests/test_ring_engine.py; this is the live
    artifact-level pin."""
    from torchft_tpu._native import StoreServer
    from torchft_tpu.collectives import TCPCollective

    rng = np.random.default_rng(1234)
    data = [
        (rng.standard_normal(n_elems) * (r + 1)).astype(np.float32)
        for r in range(2)
    ]
    outs: Dict[str, List[np.ndarray]] = {}
    store = StoreServer(bind="127.0.0.1:0")
    try:
        for engine in ("py", "native"):
            cols = [
                TCPCollective(timeout=timeout, wire_dtype="bf16", lanes=lanes,
                              engine=engine)
                for _ in range(2)
            ]
            results: Dict[int, List[np.ndarray]] = {}
            errors: List[BaseException] = []

            def run(rank: int, cols=cols, results=results, errors=errors,
                    engine=engine) -> None:
                try:
                    c = cols[rank]
                    c.configure(f"{store.address()}/parity_{engine}", rank, 2)
                    got: List[np.ndarray] = []
                    # f32 raw framing (compression off), the bf16 wire, and
                    # the int8 codec — one output set per hop codec.
                    got.append(c.allreduce(
                        [data[rank]], op="sum", allow_wire_compression=False
                    ).wait(timeout=timeout)[0])
                    got.append(c.allreduce(
                        [data[rank]], op="avg"
                    ).wait(timeout=timeout)[0])
                    got.append(c.allreduce(
                        [data[rank]], op="sum", wire_codec="int8"
                    ).wait(timeout=timeout)[0])
                    results[rank] = got
                except BaseException as e:  # noqa: BLE001 — re-raised
                    errors.append(e)

            threads = [threading.Thread(target=run, args=(r,)) for r in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            # Read BEFORE shutdown — abort clears the engine handle, so a
            # post-shutdown ring_engine always reports "py".
            resolved = cols[0].ring_engine
            for c in cols:
                c.shutdown()
            if errors:
                raise errors[0]
            if resolved != engine:
                return False  # requested engine did not run — not a parity proof
            outs[engine] = results[0]
    finally:
        store.shutdown()
    return all(
        a.dtype == b.dtype
        and a.shape == b.shape
        and bool((a.view(np.uint32) == b.view(np.uint32)).all())
        for a, b in zip(outs["py"], outs["native"])
    )


def run_engine_quick(
    payload_mb: float = 8.0, lanes: int = 2, trials: int = 3
) -> Dict[str, Any]:
    """The engine A/B smoke (``--engine both`` at a small unshaped-loopback
    cell, threads): one py cell, one native cell, plus the live bitwise
    parity pin.  Wired into
    tests/test_bench_contract.py::test_ring_engine_quick_smoke."""
    cells = [
        bench_lanes(payload_mb=payload_mb, lanes=lanes, mbps=0.0, rtt_ms=0.0,
                    n_buckets=4, timeout=120.0, procs=False, trials=trials,
                    engine=engine)
        for engine in ("py", "native")
    ]
    by_engine = {c["engine"]: c for c in cells}
    out: Dict[str, Any] = {
        "section": "ring_engine",
        "native_available": True,
        "cells": cells,
        "parity_bitwise": check_engine_parity(),
    }
    if "py" in by_engine and "native" in by_engine:
        out["native_loopback_ok"] = (
            by_engine["native"]["gb_per_s"] >= by_engine["py"]["gb_per_s"]
        )
        out["native_loopback_speedup"] = round(
            by_engine["native"]["gb_per_s"] / by_engine["py"]["gb_per_s"], 2
        )
    return out


def check_transport_parity(
    n_elems: int = 1 << 14, lanes: int = 2, timeout: float = 60.0
) -> bool:
    """Bitwise transport parity on live rings: the SAME deterministic
    payload allreduced by a tcp pair and an shm pair (f32 raw, the int8
    codec, and the int4 codec) must produce IDENTICAL bits — the shm lane
    replaces the byte PIPE under the frame protocol, never the arithmetic,
    so any divergence is a framing bug."""
    from torchft_tpu._native import StoreServer
    from torchft_tpu.collectives import TCPCollective

    rng = np.random.default_rng(4321)
    data = [
        (rng.standard_normal(n_elems) * (r + 1)).astype(np.float32)
        for r in range(2)
    ]
    outs: Dict[str, List[np.ndarray]] = {}
    store = StoreServer(bind="127.0.0.1:0")
    try:
        for transport in ("tcp", "shm"):
            cols = [
                TCPCollective(timeout=timeout, lanes=lanes,
                              transport=transport)
                for _ in range(2)
            ]
            results: Dict[int, List[np.ndarray]] = {}
            errors: List[BaseException] = []

            def run(rank: int, cols=cols, results=results, errors=errors,
                    transport=transport) -> None:
                try:
                    c = cols[rank]
                    c.configure(
                        f"{store.address()}/tparity_{transport}", rank, 2
                    )
                    got: List[np.ndarray] = []
                    got.append(c.allreduce(
                        [data[rank]], op="sum", allow_wire_compression=False
                    ).wait(timeout=timeout)[0])
                    got.append(c.allreduce(
                        [data[rank]], op="sum", wire_codec="int8"
                    ).wait(timeout=timeout)[0])
                    got.append(c.allreduce(
                        [data[rank]], op="sum", wire_codec="int4"
                    ).wait(timeout=timeout)[0])
                    results[rank] = got
                except BaseException as e:  # noqa: BLE001 — re-raised
                    errors.append(e)

            threads = [threading.Thread(target=run, args=(r,)) for r in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            resolved = cols[0].ring_transport
            for c in cols:
                c.shutdown()
            if errors:
                raise errors[0]
            if resolved != transport:
                return False  # requested transport did not arm — not a proof
            outs[transport] = results[0]
    finally:
        store.shutdown()
    return all(
        a.dtype == b.dtype
        and a.shape == b.shape
        and bool((a.view(np.uint32) == b.view(np.uint32)).all())
        for a, b in zip(outs["tcp"], outs["shm"])
    )


def check_multi_stripe(
    n_elems: int = 1 << 16, lanes: int = 2, chunk_bytes: int = 32 << 10,
    ops: int = 4, timeout: float = 60.0,
) -> Dict[str, Any]:
    """Pins the one-call native multi-stripe entry: a striped allreduce
    (many stripes per op at this chunk size) must cross the C API ONCE per
    op (``tf_ring_pass_multi``), not once per stripe — the per-stripe
    ctypes round-trips were pure Python overhead the batch entry removed.
    Counts ``RingEngine.pass_calls`` on rank 0 across ``ops`` back-to-back
    allreduces."""
    from torchft_tpu._native import StoreServer
    from torchft_tpu.collectives import TCPCollective

    nstripes = max(1, (n_elems * 4 + chunk_bytes - 1) // chunk_bytes)
    store = StoreServer(bind="127.0.0.1:0")
    counts: Dict[int, int] = {}
    errors: List[BaseException] = []
    try:
        cols = [
            TCPCollective(timeout=timeout, lanes=lanes,
                          chunk_bytes=chunk_bytes, engine="native")
            for _ in range(2)
        ]

        def run(rank: int) -> None:
            try:
                c = cols[rank]
                c.configure(f"{store.address()}/multistripe", rank, 2)
                if c.ring_engine != "native":
                    return
                x = np.arange(n_elems, dtype=np.float32) * (rank + 1)
                for _ in range(ops):
                    c.allreduce([x], op="sum").wait(timeout=timeout)
                counts[rank] = c._engine.pass_calls
            except BaseException as e:  # noqa: BLE001 — re-raised
                errors.append(e)

        threads = [threading.Thread(target=run, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for c in cols:
            c.shutdown()
        if errors:
            raise errors[0]
    finally:
        store.shutdown()
    if 0 not in counts:
        return None  # native engine did not resolve
    return {
        "section": "multi_stripe",
        "ops": ops,
        "stripes_per_op": nstripes,
        "pass_calls": counts[0],
        "one_call_per_op": counts[0] == ops,
    }


def run_transport_quick(
    payload_mb: float = 4.0, lanes: int = 2, trials: int = 3
) -> Dict[str, Any]:
    """The same-host transport A/B (``--transport both`` at a small
    unshaped-loopback cell, threads): one tcp cell, one shm cell, the live
    bitwise parity pin, and the one-call multi-stripe pin.  Wired into
    tests/test_bench_contract.py::test_transport_quick_smoke.  shm moves
    stripe frames through a lock-free SPSC ring in /dev/shm instead of the
    kernel socket path — same frames, no syscalls per hop.

    The record carries ``cpu_count`` for the same honesty reason the
    engine-thread curve does: on a single-core host both transports
    bottleneck on scheduler alternation (loopback TCP and the shm ring
    each move bytes with two copies), so the A/B ratio there is noise
    around 1.0 rather than a transport signal — consumers should only
    read ``shm_ok`` as a regression gate when ``cpu_count > 1``."""
    cells = [
        bench_lanes(payload_mb=payload_mb, lanes=lanes, mbps=0.0, rtt_ms=0.0,
                    n_buckets=4, timeout=120.0, procs=False, trials=trials,
                    transport=t)
        for t in ("tcp", "shm")
    ]
    by_transport = {c["transport"]: c for c in cells}
    out: Dict[str, Any] = {
        "section": "transport",
        "cpu_count": os.cpu_count(),
        "cells": cells,
        "parity_bitwise": check_transport_parity(lanes=lanes),
        "multi_stripe": check_multi_stripe(lanes=lanes),
    }
    if "tcp" in by_transport and "shm" in by_transport:
        out["shm_ok"] = (
            by_transport["shm"]["gb_per_s"] >= by_transport["tcp"]["gb_per_s"]
        )
        out["shm_speedup"] = round(
            by_transport["shm"]["gb_per_s"] / by_transport["tcp"]["gb_per_s"], 2
        )
    return out


def bench_engine_threads(
    payload_mb: float = 4.0, lane_counts=(1, 2, 4), trials: int = 2,
) -> Dict[str, Any]:
    """GIL-liberation curve: the same THREADED 2-rank bucket stream at
    rising lane counts, Python engine vs native engine.  Both ranks and
    all lane workers share one process here, so the Python engine's lanes
    serialize on the GIL while the native engine's C++ lane threads run
    free — the native curve should hold or rise with lanes where the py
    curve flattens.  On a 1-core container BOTH flatten (nothing to run
    parallel on); the record carries ``cpu_count`` so readers can tell
    "GIL-bound" from "core-bound" honestly."""
    cells: List[Dict[str, Any]] = []
    for eng in ("py", "native"):
        for lanes in lane_counts:
            r = bench_lanes(payload_mb=payload_mb, lanes=lanes, mbps=0.0,
                            rtt_ms=0.0, n_buckets=4, timeout=120.0,
                            procs=False, trials=trials, engine=eng)
            r["section"] = "engine_threads"
            cells.append(r)
    curve: Dict[str, Dict[str, float]] = {}
    for c in cells:
        curve.setdefault(c["engine"], {})[str(c["lanes"])] = c["gb_per_s"]
    return {
        "section": "engine_threads",
        "cpu_count": os.cpu_count(),
        "cells": cells,
        "gb_per_s": curve,
    }


# ---------------------------------------------------------------------------
# Section 2: end-to-end pipelined vs monolithic steps/s
# ---------------------------------------------------------------------------


def _grad_tree(total_bytes: int, n_leaves: int) -> Dict[str, Any]:
    """A jax pytree of f32 gradient-like leaves (device-backed so the
    pipelined D2H path does real work)."""
    import jax.numpy as jnp

    per = max(1, total_bytes // n_leaves // 4)
    return {
        f"layer_{i}.grad": jnp.full((per,), float(i % 7), dtype=jnp.float32)
        for i in range(n_leaves)
    }


def _make_grad_fn(compute_iters: int):
    """Per-leaf jitted 'backward' stand-in: each leaf's gradient is its own
    XLA execution, so leaves land asynchronously in issue order — the shape
    real per-layer backward has, and the overlap the pipelined bucket path
    exists to exploit (bucket 0 on the wire while leaf k is still
    computing).  ``compute_iters`` scales the per-leaf compute cost."""
    import jax
    import jax.numpy as jnp

    def leaf_grad(v, seed):
        x = v * seed
        for _ in range(compute_iters):
            x = jnp.sin(x) * 1.0001 + jnp.cos(x) * 0.0001
        return x

    jitted = jax.jit(leaf_grad)

    def grad_step(params: Dict[str, Any], seed: float) -> Dict[str, Any]:
        return {k: jitted(v, seed) for k, v in params.items()}

    return grad_step


def _e2e_group_body(
    lighthouse_addr: str,
    gid: int,
    lanes: int,
    pipelined: bool,
    steps: int,
    nbytes: int,
    n_leaves: int,
    bucket_mb: float,
    timeout_s: float,
    compute_iters: int = 0,
    device_prep: bool = False,
    sharded: bool = False,
    wire_dtype: str = "auto",
) -> Dict[str, Any]:
    """One replica group's training loop: compute per-leaf grads (when
    ``compute_iters`` > 0) -> start_quorum -> averager.allreduce(grads) ->
    should_commit, `steps` times.  Shared by the threaded (--quick) and
    subprocess drivers; the quorum round itself aligns group start across
    processes.  ``device_prep``/``sharded`` select the averager's
    device-resident wire prep and sharding-aware fetch modes (the A/B the
    ``--device-prep`` sweep measures); per-step d2h/h2d/wire bytes come
    from the averager's transfer accounting."""
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.ddp import GradientAverager
    from torchft_tpu.manager import Manager

    collective = TCPCollective(timeout=timeout_s, lanes=lanes, wire_dtype=wire_dtype)
    manager = Manager(
        collective=collective,
        load_state_dict=None,
        state_dict=None,
        min_replica_size=2,
        use_async_quorum=True,
        timeout=timedelta(seconds=timeout_s),
        quorum_timeout=timedelta(seconds=timeout_s),
        rank=0,
        world_size=1,
        replica_id=f"g{gid}",
        lighthouse_addr=lighthouse_addr,
        init_sync=False,  # no transport; groups start identical
    )
    try:
        averager = GradientAverager(
            manager,
            bucket_bytes=int(bucket_mb * (1 << 20)),
            pipelined=pipelined,
            device_wire_prep=device_prep,
            sharded_fetch=sharded,
        )
        params = _grad_tree(nbytes, n_leaves)
        grad_fn = _make_grad_fn(compute_iters) if compute_iters else None
        if grad_fn is not None:
            # Compile + warm outside the timed window.
            import jax

            jax.block_until_ready(grad_fn(params, 1.0))
        committed = 0
        gbps = 0.0
        xfer = {"d2h_bytes": 0, "h2d_bytes": 0, "wire_bytes": 0, "slices": 0}
        slices_per_bucket = 0
        # First quorum outside the timed window: join/rendezvous cost is
        # startup, not steady-state data-plane throughput.
        manager.start_quorum()
        t0 = time.perf_counter()
        for step in range(steps):
            if step > 0:
                manager.start_quorum()
            # Fresh per-leaf gradient computation each step: leaves land
            # asynchronously, so the pipelined path puts bucket 0 on the
            # wire while later leaves are still computing — the monolithic
            # path must wait for the whole tree before the first byte moves.
            grads = grad_fn(params, 1.0 + 0.1 * step) if grad_fn else params
            averager.allreduce(grads)
            for k in xfer:
                xfer[k] += int(averager.last_stats.get(k, 0))
            ndev_buckets = int(averager.last_stats.get("device_buckets", 0))
            if ndev_buckets:
                # Measured shard factor — slices each bucket actually split
                # into this step (not the CLI's requested device count).
                slices_per_bucket = (
                    int(averager.last_stats.get("slices", 0)) // ndev_buckets
                )
            if manager.should_commit():
                committed += 1
            gbps = max(gbps, manager._ar_gbps)
        wall = time.perf_counter() - t0
        return {"committed": committed, "wall_s": wall, "gbps": gbps,
                "slices_per_bucket": slices_per_bucket, **xfer}
    finally:
        manager.shutdown()


def _e2e_worker(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Subprocess entry for one e2e replica group (--worker e2e)."""
    if cfg.get("virtual_devices"):
        # Must land before the first jax import: the sharded-fetch trial
        # needs a multi-device CPU platform in each worker process.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={cfg['virtual_devices']}"
            ).strip()
    return _e2e_group_body(
        cfg["lighthouse"], cfg["gid"], cfg["lanes"], cfg["pipelined"],
        cfg["steps"], cfg["nbytes"], cfg["n_leaves"], cfg["bucket_mb"],
        cfg["timeout_s"], cfg.get("compute_iters", 0),
        cfg.get("device_prep", False), cfg.get("sharded", False),
        cfg.get("wire_dtype", "auto"),
    )


def bench_e2e(
    lanes: int,
    pipelined: bool,
    steps: int,
    grads_mb: float,
    n_leaves: int,
    mbps: float,
    rtt_ms: float,
    bucket_mb: float = 4.0,
    timeout_s: float = 120.0,
    procs: bool = True,
    compute_iters: int = 0,
    trials: int = 1,
    device_prep: bool = False,
    sharded: bool = False,
    wire_dtype: str = "auto",
    virtual_devices: int = 0,
) -> Dict[str, Any]:
    """2 replica groups, real lighthouse + Managers; measures committed
    steps/s for the pipelined vs monolithic bucket path.  ``procs=True``
    (the artifact path) runs each group in its own subprocess; --quick
    keeps threads.  ``trials`` > 1 keeps the best (fastest-wall) trial —
    same scheduler-noise rationale as :func:`bench_lanes`: single e2e
    trials on a 2-core shared host vary by ±30%, far more than the
    pipelined-vs-monolithic effect being measured."""
    from torchft_tpu._native import LighthouseServer

    nbytes = int(grads_mb * (1 << 20))
    per_group: List[dict] = []
    walls: List[float] = []
    with _shaped(mbps, rtt_ms):
        if procs:
            for _trial in range(max(1, trials)):
                lighthouse = LighthouseServer(
                    bind="127.0.0.1:0", min_replicas=2,
                    join_timeout_ms=5000, quorum_tick_ms=20,
                )
                try:
                    cfgs = [
                        {"lighthouse": lighthouse.address(), "gid": g,
                         "lanes": lanes, "pipelined": pipelined,
                         "steps": steps, "nbytes": nbytes,
                         "n_leaves": n_leaves, "bucket_mb": bucket_mb,
                         "timeout_s": timeout_s,
                         "compute_iters": compute_iters,
                         "device_prep": device_prep, "sharded": sharded,
                         "wire_dtype": wire_dtype,
                         "virtual_devices": virtual_devices}
                        for g in range(2)
                    ]
                    attempt = _spawn_workers("e2e", cfgs, timeout_s + 120)
                finally:
                    lighthouse.shutdown()
                wall = max(r["wall_s"] for r in attempt)
                if not per_group or wall < max(r["wall_s"] for r in per_group):
                    per_group = attempt
                walls.append(wall)
        else:
            lighthouse = LighthouseServer(
                bind="127.0.0.1:0", min_replicas=2,
                join_timeout_ms=5000, quorum_tick_ms=20,
            )
            try:
                results: Dict[int, dict] = {}
                errors: List[BaseException] = []
                start_barrier = threading.Barrier(2)

                def group(gid: int) -> None:
                    try:
                        start_barrier.wait(timeout=timeout_s)
                        results[gid] = _e2e_group_body(
                            lighthouse.address(), gid, lanes, pipelined,
                            steps, nbytes, n_leaves, bucket_mb, timeout_s,
                            compute_iters, device_prep, sharded, wire_dtype,
                        )
                    except BaseException as e:  # noqa: BLE001 — re-raised
                        errors.append(e)

                threads = [
                    threading.Thread(target=group, args=(g,)) for g in range(2)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                if errors:
                    raise errors[0]
                per_group = [results[g] for g in range(2)]
            finally:
                lighthouse.shutdown()
    wall = max(r["wall_s"] for r in per_group)
    committed = min(r["committed"] for r in per_group)
    gbps_seen = [r["gbps"] for r in per_group if r["gbps"] > 0]
    mode = "pipelined" if pipelined else "monolithic"
    if device_prep:
        mode += "+device_prep"
    if sharded:
        mode += "+sharded"
    out = {
        "section": "e2e",
        "mode": mode,
        "device_prep": device_prep,
        "sharded_fetch": sharded,
        "wire_dtype": wire_dtype,
        # Per-host transfer accounting over the whole kept trial (group 0's
        # view; groups are symmetric): D2H fetch bytes, H2D scatter-back
        # bytes, and the payload bytes handed to the ring — with device
        # wire prep the d2h side reads wire (bf16) bytes, the ~2x the
        # artifact pins.
        "d2h_bytes": per_group[0].get("d2h_bytes", 0),
        "h2d_bytes": per_group[0].get("h2d_bytes", 0),
        "wire_bytes": per_group[0].get("wire_bytes", 0),
        "fetch_slices": per_group[0].get("slices", 0),
        "slices_per_bucket": per_group[0].get("slices_per_bucket", 0),
        "lanes": lanes,
        "grads_mb": grads_mb,
        "leaves": n_leaves,
        "bucket_mb": bucket_mb,
        "compute_iters": compute_iters,
        "link": {"mbps": mbps, "rtt_ms": rtt_ms},
        "ranks": "subprocess" if procs else "threads",
        "steps": steps,
        "committed": committed,
        "wall_s": round(wall, 3),
        "steps_per_s": round(committed / wall, 4) if wall > 0 else None,
        "allreduce_gb_per_s": round(max(gbps_seen), 4) if gbps_seen else None,
    }
    if len(walls) > 1:
        out["trial_walls_s"] = [round(w, 3) for w in walls]
    return out


# ---------------------------------------------------------------------------
# Section 3: mid-allreduce peer kill
# ---------------------------------------------------------------------------


def bench_peer_kill(
    lanes: int = 2,
    grads_mb: float = 16.0,
    mbps: float = 200.0,
    rtt_ms: float = 10.0,
    timeout_s: float = 60.0,
) -> Dict[str, Any]:
    """3 replica groups; group 2 dies mid-allreduce at step 1 (collective
    abort + manager shutdown, the in-process stand-in for kill -9).  Proves:
    survivors LATCH the error (no raise into the loop), should_commit fails
    cleanly, and the next quorum rebuilds every lane with the old lane
    sockets closed."""
    from torchft_tpu._native import LighthouseServer
    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.ddp import GradientAverager
    from torchft_tpu.manager import Manager

    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=1000,
        quorum_tick_ms=20, heartbeat_timeout_ms=1000,
    )
    nbytes = int(grads_mb * (1 << 20))
    evidence: Dict[str, Any] = {}
    errors: List[BaseException] = []
    barrier = threading.Barrier(3)
    victim_killed = threading.Event()

    def group(gid: int) -> None:
        manager = None
        collective = None
        try:
            collective = TCPCollective(timeout=timeout_s, lanes=lanes)
            # A real checkpoint transport + state dict: the survivors' retry
            # loops run independently, so one may commit a step the other
            # failed — the next quorum then assigns a heal, which must work
            # for the cluster to reconverge (the deployment shape).
            state: Dict[str, Any] = {"tensor": np.zeros(4, dtype=np.float32)}
            transport = HTTPTransport(timeout=timeout_s)
            manager = Manager(
                collective=collective,
                load_state_dict=lambda sd: state.update(sd),
                state_dict=lambda: dict(state),
                min_replica_size=2,
                use_async_quorum=True,
                timeout=timedelta(seconds=timeout_s),
                quorum_timeout=timedelta(seconds=timeout_s),
                rank=0,
                world_size=1,
                replica_id=f"k{gid}",
                lighthouse_addr=lighthouse.address(),
                checkpoint_transport=transport,
                init_sync=False,  # groups start identical
            )
            averager = GradientAverager(manager, bucket_bytes=4 << 20)
            grads = _grad_tree(nbytes, 8)
            barrier.wait(timeout=timeout_s)

            # Step 0: everyone commits (healthy 3-way quorum, all lanes up).
            manager.start_quorum()
            averager.allreduce(grads)
            ok0 = manager.should_commit()
            if gid == 0:
                evidence["step0_committed"] = ok0
                evidence["lanes_before"] = collective.lane_stats()["lanes"]

            if gid == 2:
                # The victim dies "mid-step": its sockets go away while the
                # survivors' stripes are in flight.
                def die() -> None:
                    evidence["kill_ts"] = time.time()
                    collective.abort()
                    victim_killed.set()

                threading.Timer(0.3, die).start()
                manager.start_quorum()
                averager.allreduce(grads)  # fails locally too; latched
                manager.should_commit()
                manager.shutdown()
                manager = None
                return

            # Survivors: step 1 overlaps the victim's death.
            old_next = list(collective._next_lanes)
            old_prev = list(collective._prev_lanes)
            manager.start_quorum()
            averager.allreduce(grads)  # must latch, not raise
            latched = manager.errored() is not None or collective.errored() is not None
            committed = manager.should_commit()
            if gid == 0:
                evidence["victim_kill_fired"] = victim_killed.is_set()
                evidence["step1_error_latched"] = bool(latched)
                evidence["step1_committed"] = committed

            # Next quorum: lighthouse drops the victim (heartbeat timeout),
            # survivors reconfigure as a 2-world with every lane rebuilt.
            deadline = time.monotonic() + timeout_s
            recovered = False
            while time.monotonic() < deadline and not recovered:
                manager.start_quorum()
                averager.allreduce(grads)
                recovered = manager.should_commit()
            if gid == 0:
                stats = collective.lane_stats()
                evidence["recovered_committed"] = recovered
                evidence["lanes_after"] = stats["lanes"]
                evidence["lanes_rebuilt"] = (
                    len(stats["sent"]) == lanes and len(stats["recv"]) == lanes
                )
                # No leaked sockets: abort()/configure closed every old lane
                # (closed sockets report fileno -1).
                evidence["old_lane_sockets_closed"] = all(
                    p.sock.fileno() == -1 for p in old_next + old_prev
                )
                # Fault-window hop bracketing: the sampled hop timeline is
                # the black box a post-mortem reads, so it must hold
                # records from BOTH sides of the kill — the pre-fault hops
                # banked when abort() tore the generation down AND hops
                # from the rebuilt lanes — or the window of interest is
                # exactly the part the recorder lost.
                hop_ts = [
                    r.get("ts", 0.0) for r in collective.hop_records()
                ]
                kill_ts = evidence.get("kill_ts")
                evidence["hop_timeline_records"] = len(hop_ts)
                evidence["hop_timeline_brackets_fault"] = bool(
                    hop_ts and kill_ts and min(hop_ts) < kill_ts < max(hop_ts)
                )
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)
        finally:
            if manager is not None:
                manager.shutdown()

    with _shaped(mbps, rtt_ms):
        threads = [threading.Thread(target=group, args=(g,)) for g in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    lighthouse.shutdown()
    if errors:
        raise errors[0]
    evidence.update(
        {
            "section": "peer_kill",
            "lanes": lanes,
            "grads_mb": grads_mb,
            "ok": bool(
                evidence.get("step0_committed")
                and evidence.get("victim_kill_fired")
                and evidence.get("step1_error_latched")
                and evidence.get("step1_committed") is False
                and evidence.get("recovered_committed")
                and evidence.get("lanes_rebuilt")
                and evidence.get("old_lane_sockets_closed")
                and evidence.get("hop_timeline_brackets_fault")
            ),
        }
    )
    return evidence


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Section: slow-link sentinel cell (data-plane flight recorder, PR 14)
# ---------------------------------------------------------------------------


def _scoped_env(overrides: Dict[str, Optional[str]]):
    """Context manager applying env overrides for the block (None = unset)."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        prior = {k: os.environ.get(k) for k in overrides}
        for k, v in overrides.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            yield
        finally:
            for k, v in prior.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    return ctx()


def bench_recorder_overhead(trials: int = 5, payload_mb: float = 2.0) -> Dict[str, Any]:
    """Hop-recorder cost guard: the same unshaped loopback bucket stream
    with the hop timeline ON (TPUFT_HOP_SAMPLE=1, the default) vs OFF (0).
    Unshaped because a modeled link hides microsecond recorder costs under
    millisecond pacing sleeps; loopback wall IS engine cost here.
    Best-of-N per side (scheduler noise on 1-2 core hosts dominates single
    trials).  ``impact`` = off-throughput / on-throughput; the committed
    artifact pins it under the <2%-overhead budget."""
    # Paired A/B: each trial runs off-then-on back to back and contributes
    # one off/on throughput RATIO; the reported impact is the MEDIAN of
    # those paired ratios.  Two back-to-back best-of-N blocks measure the
    # host's drift (page cache, scheduler settling), not the microsecond
    # recorder cost — pairing cancels slow drift, the median rejects the
    # occasional trial a context-switch storm ruins.
    ratios: List[float] = []
    best: Dict[str, float] = {"on": 0.0, "off": 0.0}
    for _ in range(trials):
        pair: Dict[str, float] = {}
        for label, sample in (("off", "0"), ("on", "1")):
            with _scoped_env({"TPUFT_HOP_SAMPLE": sample}):
                r = bench_lanes(payload_mb, 2, 0.0, 0.0, n_buckets=4,
                                timeout=60.0, procs=False, trials=1)
            pair[label] = r["gb_per_s"]
            best[label] = max(best[label], r["gb_per_s"])
        if pair["on"]:
            ratios.append(pair["off"] / pair["on"])
    ratios.sort()
    out: Dict[str, Any] = {
        "on_gb_per_s": round(best["on"], 4),
        "off_gb_per_s": round(best["off"], 4),
        "trials": trials,
    }
    out["impact"] = (
        round(ratios[len(ratios) // 2], 4) if ratios else None
    )
    return out


def _link_group_loop(
    gid: int,
    groups: int,
    lighthouse_addr: str,
    steps: int,
    payload_elems: int,
    degrade_at: Optional[int],
    degrade_mbps: float,
    rtt_ms: float,
    engine: Optional[str],
    out: Dict[str, Any],
) -> None:
    """One replica group of the link cell: real Manager + shaped
    TCPCollective, a commit loop moving one gradient payload per round.
    Group 0 is the victim: at round ``degrade_at`` it re-shapes its OWN
    outbound (next-direction) link ``degrade_mbps`` — the modeled analogue
    of the physical edge victim->successor degrading — with no
    reconfigure, which is exactly why the straggler sentinel cannot see
    it and the slow-link sentinel must."""
    from datetime import timedelta

    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.manager import Manager

    state = {"w": np.zeros(8, dtype=np.float32)}
    collective = TCPCollective(timeout=30.0, lanes=2, engine=engine)
    manager = Manager(
        collective=collective,
        load_state_dict=lambda sd: state.update(sd),
        state_dict=lambda: dict(state),
        min_replica_size=groups,
        rank=0,
        world_size=1,
        replica_id=f"link{gid}",
        lighthouse_addr=lighthouse_addr,
        quorum_timeout=timedelta(seconds=60.0),
        timeout=timedelta(seconds=30.0),
        connect_timeout=timedelta(seconds=15.0),
        checkpoint_transport=HTTPTransport(timeout=30.0),
        init_sync=False,
    )
    payload = np.full((payload_elems,), 0.5 + gid, dtype=np.float32)
    commits: List[float] = []
    failed = 0
    degraded_ts: Optional[float] = None
    try:
        for step in range(steps):
            try:
                manager.start_quorum()
                fut = manager.allreduce(payload.copy())
                fut.result()
                if manager.should_commit():
                    commits.append(time.time())
                else:
                    failed += 1
            except Exception:  # noqa: BLE001 — recoverable control faults
                failed += 1
            if degrade_at is not None and gid == 0 and step + 1 == degrade_at:
                collective.set_link_shaping(degrade_mbps, rtt_ms)
                degraded_ts = time.time()
                manager.metrics.emit(
                    "link_shaped", mbps=degrade_mbps, rtt_ms=rtt_ms,
                    group=gid, step=step,
                )
        out["hop_records"] = collective.hop_records()
        out["lane_totals"] = collective.lane_totals()
    finally:
        out["replica_id"] = manager.replica_id()
        out["commits"] = commits
        out["failed"] = failed
        out["degraded_ts"] = degraded_ts
        manager.shutdown()


def _link_cell(
    groups: int,
    steps: int,
    payload_elems: int,
    mbps: float,
    rtt_ms: float,
    degrade_at: Optional[int],
    degrade_factor: float,
    engine: Optional[str],
    workdir: str,
    tag: str,
) -> Dict[str, Any]:
    """One live sentinel cell (healthy control when degrade_at is None):
    in-process native lighthouse + ``groups`` threaded real Managers whose
    heartbeats carry the link-health EWMAs; returns commit timelines, the
    lighthouse's link gauges/alerts, and the metrics-stream path for the
    attribution rollup."""
    import threading
    import urllib.request

    from torchft_tpu._native import LighthouseServer
    from torchft_tpu.metrics import MetricsLogger

    metrics_path = os.path.join(workdir, f"metrics_{tag}.jsonl")
    overrides = {
        "TPUFT_SHAPED_LINK": f"{mbps}:{rtt_ms}",
        "TPUFT_METRICS_PATH": metrics_path,
        # Tight sentinel tuning for a bounded cell: 2-step grace both
        # directions, 2-observation warmup, ratio 3 (the injected 10x
        # degradation scores ~10x below median — far past threshold).
        "TPUFT_LINK_RATIO": "3.0",
        "TPUFT_LINK_GRACE_STEPS": "2",
        "TPUFT_LINK_WARMUP_STEPS": "2",
        "TPUFT_LINK_AUTO_DRAIN": None,
        "TPUFT_HOP_SAMPLE": "1",
    }
    with _scoped_env(overrides):
        lighthouse = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=groups, join_timeout_ms=10000,
            quorum_tick_ms=50, heartbeat_timeout_ms=5000,
        )
        driver_log = MetricsLogger(metrics_path, replica_id="bench-driver")
        outs: List[Dict[str, Any]] = [{} for _ in range(groups)]
        threads = [
            threading.Thread(
                target=_link_group_loop,
                args=(g, groups, lighthouse.address(), steps, payload_elems,
                      degrade_at, mbps / degrade_factor, rtt_ms, engine,
                      outs[g]),
                name=f"linkcell-{g}",
            )
            for g in range(groups)
        ]
        alerts_seen: List[dict] = []
        stop_poll = threading.Event()
        http = lighthouse.http_address()
        port = http.rsplit(":", 1)[1]

        def get_json(path: str) -> Optional[dict]:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5
                ) as resp:
                    return json.loads(resp.read().decode())
            except Exception:  # noqa: BLE001 — poller
                return None

        # Incident auto-capture: the alert raise also records a trigger on
        # /incident.json; bundle the live evidence the moment it appears
        # (the slow-link cell's half of the cross-plane capture contract).
        from torchft_tpu.obs import incident as obs_incident

        incident_watch = obs_incident.IncidentWatcher(f"http://127.0.0.1:{port}")
        incident_bundles: List[str] = []

        def poll_alerts() -> None:
            seen_ids = set()
            while not stop_poll.is_set():
                doc = get_json("/alerts.json")
                if doc:
                    for a in doc.get("alerts", []):
                        if a.get("kind") == "slow_link" and a["id"] not in seen_ids:
                            seen_ids.add(a["id"])
                            a = dict(a)
                            a["observed_ts"] = time.time()
                            alerts_seen.append(a)
                            driver_log.emit(
                                "link_alert", alert_id=a["id"],
                                src_replica_id=a.get("src_replica_id"),
                                alert_replica_id=a.get("replica_id"),
                                gbps=a.get("gbps"),
                            )
                for trig in incident_watch.poll():
                    try:
                        bundle = obs_incident.capture_bundle(
                            workdir, f"http://127.0.0.1:{port}", trig,
                            metrics_paths=[metrics_path],
                        )
                    except OSError:
                        # Transient capture failure: re-queue so the next
                        # poll tick retries.
                        incident_watch.unsee(trig.get("id"))
                        continue
                    if bundle not in incident_bundles:
                        incident_bundles.append(bundle)
                    driver_log.emit(
                        "incident_captured",
                        bundle=os.path.basename(bundle),
                        reason=trig.get("reason"),
                        incident_replica=trig.get("replica_id"),
                        incident_id=trig.get("id"),
                    )
                stop_poll.wait(0.2)

        poller = threading.Thread(target=poll_alerts, name="linkcell-poll")
        try:
            for t in threads:
                t.start()
            poller.start()
            for t in threads:
                t.join(timeout=600)
        finally:
            stop_poll.set()
            poller.join(timeout=5)
            metrics_text = None
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5
                ) as resp:
                    metrics_text = resp.read().decode()
            except Exception:  # noqa: BLE001
                pass
            driver_log.close()
            lighthouse.shutdown()
    link_gauges = {}
    if metrics_text:
        for line in metrics_text.splitlines():
            if line.startswith("tpuft_link") and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                link_gauges[name] = float(value)
    return {
        "groups": outs,
        "alerts": alerts_seen,
        "link_gauges": link_gauges,
        "metrics_path": metrics_path,
        "incident_bundles": incident_bundles,
    }


def run_link(
    groups: int = 3,
    steps: int = 30,
    payload_kb: int = 512,
    mbps: float = 100.0,
    rtt_ms: float = 5.0,
    degrade_at: int = 10,
    degrade_factor: float = 10.0,
    engine: Optional[str] = None,
    overhead_trials: int = 11,
    quick: bool = False,
    workdir: Optional[str] = None,
) -> Dict[str, Any]:
    """The slow-link sentinel cell (docs/architecture.md "Data-plane
    observability"):

    * ``healthy`` — the control run: same cluster, no fault; MUST raise
      zero slow_link alerts, and its round pace is the added-wall
      baseline.
    * ``degraded`` — at round ``degrade_at`` the victim's outbound link is
      re-shaped ``degrade_factor``x slower mid-run (no reconfigure, no
      process fault: invisible to heartbeat timeouts AND to the straggler
      sentinel's wall-minus-waits signal, which equalizes across the
      lockstep ring).  The cell measures detection latency in victim
      commit rounds and runs obs.report.link_attribution over both runs'
      step_summary streams: the ADDED wall must land in the
      wire/shaping/stall buckets, not combine.
    * ``overhead`` — the hop recorder's own cost on unshaped loopback
      (timeline on vs off), pinning the <2% budget.
    """
    import shutil
    import tempfile

    from torchft_tpu.obs.report import link_attribution, read_events

    own_workdir = workdir is None
    if own_workdir:
        workdir = tempfile.mkdtemp(prefix="tpuft_link_")
    overhead_mb = 24.0
    if quick:
        groups, steps, payload_kb = 3, 14, 192
        mbps, rtt_ms, degrade_at = 60.0, 4.0, 5
        overhead_trials, overhead_mb = 3, 2.0
    payload_elems = payload_kb * 1024 // 4
    try:
        healthy = _link_cell(
            groups, steps, payload_elems, mbps, rtt_ms, None, degrade_factor,
            engine, workdir, "healthy",
        )
        degraded = _link_cell(
            groups, steps, payload_elems, mbps, rtt_ms, degrade_at,
            degrade_factor, engine, workdir, "degraded",
        )
        overhead = bench_recorder_overhead(
            trials=overhead_trials, payload_mb=overhead_mb
        )

        def cell_summary(cell: Dict[str, Any]) -> Dict[str, Any]:
            events = read_events([cell["metrics_path"]])
            attr = link_attribution(events)
            commits = [len(g.get("commits") or []) for g in cell["groups"]]
            return {
                "commits": commits,
                "failed": [g.get("failed", 0) for g in cell["groups"]],
                "link_alerts": len(cell["alerts"]),
                "attribution": attr,
                "link_gauges": {
                    k: v for k, v in cell["link_gauges"].items()
                    if "state" in k or "ratio" in k
                },
            }

        h, d = cell_summary(healthy), cell_summary(degraded)
        victim = degraded["groups"][0]
        victim_rid = str(victim.get("replica_id", ""))
        degraded_ts = victim.get("degraded_ts")
        detection_rounds = None
        detected = bool(degraded["alerts"])
        if detected and degraded_ts:
            raise_s = degraded["alerts"][0]["raised_ms"] / 1000.0
            detection_rounds = sum(
                1 for ts in victim.get("commits") or []
                if degraded_ts <= ts <= raise_s
            )
        # Fault-window hop bracketing: the victim's sampled hop timeline
        # must carry records from before AND after the mid-run re-shaping
        # — the shape change never tears a lane down, so a timeline gap
        # around the fault would mean the sampler (not the fault) went
        # quiet exactly when the post-mortem needs it.
        victim_hop_ts = [
            r.get("ts", 0.0) for r in victim.get("hop_records") or []
        ]
        hop_brackets_fault = bool(
            victim_hop_ts
            and degraded_ts
            and min(victim_hop_ts) < degraded_ts < max(victim_hop_ts)
        )
        # The alert must name the right EDGE: reported by the victim (the
        # sender whose send-blocked time exploded), alerting its ring
        # successor (the endpoint whose inbound path degraded).
        src_ok = bool(
            degraded["alerts"]
            and str(degraded["alerts"][0].get("src_replica_id", ""))
            == victim_rid
        )
        # Added-wall attribution: per-bucket growth of the degraded run
        # over the healthy control (same round count) — the fault's cost
        # must land on the wire/shaping/stall side, not combine.
        added = {}
        for k in ("wire_s", "stall_s", "combine_s", "shaping_s"):
            added[k] = round(
                d["attribution"]["totals"][k] - h["attribution"]["totals"][k], 4
            )
        added_total = sum(added.values())
        added_wire_stall_fraction = (
            round(
                (added["wire_s"] + added["stall_s"] + added["shaping_s"])
                / added_total,
                4,
            )
            if added_total > 0
            else None
        )
        frac = d["attribution"]["fractions"]
        fraction_sum = round(
            sum(v for v in frac.values() if v is not None), 4
        )
        # Incident auto-capture verdict: the degraded cell's slow_link
        # trigger must have produced a bundle whose verdict names the
        # injected edge (victim group as the sender).
        from torchft_tpu.obs import incident as obs_incident

        incident_verdict = None
        incident_ok = False
        victim_group = victim_rid.split(":", 1)[0]
        degraded_events = (
            read_events([degraded["metrics_path"]])
            if degraded.get("incident_bundles")
            else []
        )
        for bundle in degraded.get("incident_bundles", []):
            try:
                manifest = obs_incident.finalize_bundle(
                    bundle, workdir, events=degraded_events,
                )
            except (OSError, ValueError):
                continue
            v = manifest.get("verdict", {})
            if v.get("kind") == "slow_link" and v.get("replica") == victim_group:
                incident_verdict = v
                incident_ok = True
        return {
            "section": "link",
            "quick": quick,
            "config": {
                "groups": groups, "steps": steps, "payload_kb": payload_kb,
                "mbps": mbps, "rtt_ms": rtt_ms, "degrade_at": degrade_at,
                "degrade_factor": degrade_factor,
            },
            "healthy": h,
            "degraded": d,
            "detected": detected,
            "detection_rounds": detection_rounds,
            "hop_timeline_records": len(victim_hop_ts),
            "hop_timeline_brackets_fault": hop_brackets_fault,
            "alert_src_is_victim": src_ok,
            "victim": victim_rid,
            "alert": (degraded["alerts"][0] if degraded["alerts"] else None),
            "added_wall": added,
            "added_wire_stall_fraction": added_wire_stall_fraction,
            "attribution_fraction_sum": fraction_sum,
            "incident_verdict": incident_verdict,
            "incident_ok": incident_ok,
            "overhead": overhead,
            "ok": bool(
                detected
                and h["link_alerts"] == 0
                and (detection_rounds is None or detection_rounds <= 10)
                and incident_ok
                and hop_brackets_fault
            ),
        }
    finally:
        if own_workdir:
            shutil.rmtree(workdir, ignore_errors=True)


def run_quick() -> Dict[str, Any]:
    """Tier-1 smoke (``--quick``): small payloads, 1 vs 2 lanes at the
    collective level, pipelined vs monolithic commit counts end to end,
    plus the device-wire-prep A/B (bf16 wire so the cast has something to
    halve; sharded fetch engages when the process has multiple local
    devices, e.g. under the test suite's forced 8-device CPU platform).
    Wired into tests/test_bench_contract.py::test_allreduce_quick_smoke
    and ::test_device_prep_quick_smoke."""
    lanes_results = [
        bench_lanes(payload_mb=2.0, lanes=l, mbps=0.0, rtt_ms=0.0,
                    n_buckets=4, timeout=60.0, procs=False)
        for l in (1, 2)
    ]
    e2e_results = [
        bench_e2e(lanes=2, pipelined=p, steps=3, grads_mb=2.0, n_leaves=8,
                  mbps=0.0, rtt_ms=0.0, bucket_mb=0.5, timeout_s=60.0,
                  procs=False)
        for p in (True, False)
    ]
    prep_results = [
        bench_e2e(lanes=2, pipelined=True, steps=3, grads_mb=2.0, n_leaves=8,
                  mbps=0.0, rtt_ms=0.0, bucket_mb=0.5, timeout_s=60.0,
                  procs=False, device_prep=prep, sharded=shard,
                  wire_dtype="bf16")
        for prep, shard in ((False, False), (True, False), (True, True))
    ]
    pipe = next(r for r in e2e_results if r["mode"] == "pipelined")
    mono = next(r for r in e2e_results if r["mode"] == "monolithic")
    host_cast = prep_results[0]
    dev_prep = prep_results[1]
    dev_sharded = prep_results[2]
    return {
        "quick": True,
        "lanes": lanes_results,
        "e2e": e2e_results,
        "device_prep": prep_results,
        "pipelined_commits_ok": pipe["committed"] >= mono["committed"],
        "device_prep_commits_ok": (
            dev_prep["committed"] >= host_cast["committed"]
            and dev_sharded["committed"] >= host_cast["committed"]
        ),
        "d2h_reduction": (
            round(host_cast["d2h_bytes"] / dev_prep["d2h_bytes"], 3)
            if dev_prep["d2h_bytes"]
            else None
        ),
        "sharded_fetch_slices": dev_sharded["fetch_slices"],
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--worker", choices=["lanes", "e2e"], default=None,
        help="internal: run one rank/group body and write JSON to --cfg's 'out'",
    )
    parser.add_argument("--cfg", default=None, help="internal: worker JSON config")
    parser.add_argument("--mb", type=float, default=64.0, help="allreduce payload")
    parser.add_argument("--lanes", type=int, nargs="*", default=[1, 2, 4])
    parser.add_argument("--buckets", type=int, default=8)
    parser.add_argument(
        "--mbps", type=float, default=400.0,
        help="shaped per-peer link bandwidth (shared across lanes)",
    )
    parser.add_argument("--rtt-ms", type=float, default=20.0)
    parser.add_argument(
        "--trials", type=int, default=3,
        help="lane-sweep trials per lane count (best wall wins; scheduler "
        "noise on small shared hosts costs a single trial up to 30%%)",
    )
    parser.add_argument(
        "--engine", choices=["py", "native", "both"], default="both",
        help="ring hot-loop engine A/B: 'both' runs every lane cell under "
        "the Python engine AND the native GIL-free engine (plus an "
        "unshaped-loopback engine section and a live bitwise parity pin); "
        "'py'/'native' pin one side",
    )
    parser.add_argument(
        "--transport", choices=["tcp", "shm", "both"], default="both",
        help="ring-lane transport A/B: 'both' adds a tcp-vs-shm section "
        "(same-host SPSC shm ring vs the kernel socket path, bitwise "
        "parity pin, one-call multi-stripe pin, GIL-liberation thread "
        "sweep); 'tcp'/'shm' pin the transport for every cell",
    )
    parser.add_argument(
        "--topology", choices=["ring", "ring2d", "both"], default="both",
        help="cross-group topology A/B: 'both' adds a flat-vs-ring2d sweep "
        "at --topo-world ranks on the same shaped link (the per-topology "
        "records the artifact quotes); 'ring'/'ring2d' pin one side",
    )
    parser.add_argument(
        "--topo-world", type=int, default=4,
        help="rank count for the topology A/B (ring2d needs a non-prime "
        "world >= 4; the flat ring's 2(N-1) hop latency is what the 2D "
        "grid undercuts)",
    )
    parser.add_argument(
        "--topo-mb", type=float, default=8.0,
        help="payload for the topology A/B (latency-bound regime: small "
        "enough that per-hop RTT, not serialization, dominates)",
    )
    parser.add_argument("--e2e-steps", type=int, default=6)
    parser.add_argument("--e2e-mb", type=float, default=12.0)
    parser.add_argument("--e2e-leaves", type=int, default=16)
    parser.add_argument("--e2e-bucket-mb", type=float, default=3.0)
    parser.add_argument(
        "--e2e-lanes", type=int, default=2,
        help="ring lanes for the e2e section (coarser than the lane sweep: "
        "on small shared hosts many tiny lane frames lose their overlap to "
        "scheduler latency, so the pipelined-vs-monolithic A/B runs at the "
        "granularity a 2-core host can actually schedule)",
    )
    parser.add_argument(
        "--e2e-compute-iters", type=int, default=10,
        help="per-leaf jitted compute iterations (0 = pre-materialized grads)",
    )
    parser.add_argument(
        "--device-prep", choices=["on", "off", "both"], default="both",
        help="device-resident wire prep for the e2e section: 'both' runs "
        "the pipelined trial with the on-TPU bf16 cast AND the host-cast "
        "reference (the A/B the artifact quotes); 'on'/'off' pin one side",
    )
    parser.add_argument(
        "--sharded-devices", type=int, default=4,
        help="virtual devices per e2e worker for the sharded-fetch trial "
        "(0 disables the trial)",
    )
    parser.add_argument("--quick", action="store_true")
    parser.add_argument(
        "--link", action="store_true",
        help="run ONLY the slow-link sentinel cell (healthy control + "
        "mid-run 10x degraded edge + recorder-overhead guard) and merge "
        "its record into --out under the 'link' key",
    )
    parser.add_argument(
        "--link-quick", action="store_true",
        help="with --link: the small tier-1 configuration",
    )
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    if args.worker:
        cfg = json.loads(args.cfg)
        body = {"lanes": _lane_worker, "e2e": _e2e_worker}[args.worker]
        result = body(cfg)
        with open(cfg["out"], "w") as f:
            json.dump(result, f)
        return

    if args.quick:
        payload = run_quick()
        print(json.dumps(payload), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=1)
        return

    if args.link:
        link = run_link(quick=args.link_quick)
        print(json.dumps(link), flush=True)
        if args.out:
            # Merge into the existing artifact: the link cell is additive —
            # regenerating the full lane/e2e/topology sweeps to add one
            # sentinel cell would churn every other number.
            doc: Dict[str, Any] = {}
            if os.path.exists(args.out):
                with open(args.out) as f:
                    doc = json.load(f)
            doc["link"] = link
            with open(args.out, "w") as f:
                json.dump(doc, f, indent=1)
        return

    results: List[Dict[str, Any]] = []
    engines = ["py", "native"] if args.engine == "both" else [args.engine]
    # lane_gbps[engine][lanes]; the flat summary keys quote the engine the
    # deployment default (auto) runs — native when available.
    lane_gbps: Dict[str, Dict[int, float]] = {e: {} for e in engines}
    pinned_transport = None if args.transport == "both" else args.transport
    for l in args.lanes:
        for eng in engines:
            r = bench_lanes(args.mb, l, args.mbps, args.rtt_ms, args.buckets,
                            trials=args.trials, engine=eng,
                            transport=pinned_transport)
            # Key by the engine that actually RAN: a stale .so degrades a
            # requested native cell to py (one warning) and the record must
            # land under the truth, not crash the sweep.
            lane_gbps.setdefault(r["engine"], {})[l] = r["gb_per_s"]
            results.append(r)
            print(json.dumps(r), flush=True)

    # Engine loopback A/B: the same bucket stream UNSHAPED (mbps=0) — no
    # modeled link, so the wall is pure engine cost: GIL + per-stripe
    # copies for the Python engine, scatter-gather C++ for the native one.
    # This is the ceiling every shaped number saturates against.
    engine_loopback: Dict[str, float] = {}
    if args.engine == "both":
        for eng in engines:
            r = bench_lanes(args.mb, 4, 0.0, 0.0, args.buckets,
                            trials=args.trials, engine=eng)
            r["section"] = "engine_loopback"
            engine_loopback[r["engine"]] = r["gb_per_s"]
            results.append(r)
            print(json.dumps(r), flush=True)
        parity = check_engine_parity()
        results.append({"section": "engine_parity", "parity_bitwise": parity})
        print(json.dumps(results[-1]), flush=True)

    # Transport A/B: tcp vs same-host shm lanes on the unshaped loopback
    # (a shaped link would bury the syscall cost the shm path removes),
    # plus the bitwise parity pin, the one-call multi-stripe pin, and the
    # GIL-liberation thread sweep.
    transport_section: Optional[Dict[str, Any]] = None
    if args.transport == "both":
        transport_section = run_transport_quick(
            payload_mb=min(args.mb, 16.0), trials=args.trials
        )
        results.append(transport_section)
        print(json.dumps(transport_section), flush=True)
        r = bench_engine_threads(
            payload_mb=min(args.mb, 8.0), trials=max(1, args.trials - 1)
        )
        results.append(r)
        print(json.dumps(r), flush=True)

    # Topology A/B: the same bucket stream at --topo-world ranks, flat ring
    # vs 2D ring-of-rings, on the same shaped link.  Paired same-host
    # best-of-N trials; GB/s from the identical payload/wall arithmetic so
    # the records compare directly.
    topo_gbps: Dict[str, float] = {}
    topo_selection = (
        ["ring", "ring2d"] if args.topology == "both" else [args.topology]
    )
    for topo in topo_selection:
        r = bench_lanes(args.topo_mb, 2, args.mbps, args.rtt_ms,
                        n_buckets=max(2, args.buckets // 2),
                        trials=args.trials, world=args.topo_world,
                        topology=topo)
        r["section"] = "topology"
        r["requested_topology"] = topo
        if r["topology"] != topo:
            # ring2d degrades at primes / worlds < 4: the "A/B" would then
            # be two identical flat-ring trials silently keyed as one —
            # surface it instead of recording a speedup that never ran.
            import sys as _sys

            print(
                f"warning: requested topology {topo!r} resolved to "
                f"{r['topology']!r} at world {args.topo_world} (no 2D grid)"
                " — topology A/B skipped for this side",
                file=_sys.stderr, flush=True,
            )
        else:
            topo_gbps[topo] = r["gb_per_s"]
        results.append(r)
        print(json.dumps(r), flush=True)

    e2e: List[Dict[str, Any]] = []
    # The e2e matrix: monolithic reference, pipelined host-cast, pipelined
    # device-prep (same trial setup — only the wire-prep locus moves), and
    # a sharded-fetch trial on a multi-device worker platform.
    trial_modes: List[Dict[str, Any]] = [dict(pipelined=False)]
    if args.device_prep in ("off", "both"):
        trial_modes.append(dict(pipelined=True, device_prep=False))
    if args.device_prep in ("on", "both"):
        trial_modes.append(dict(pipelined=True, device_prep=True))
        if args.sharded_devices:
            trial_modes.append(
                dict(pipelined=True, device_prep=True, sharded=True,
                     virtual_devices=args.sharded_devices)
            )
    for mode_kw in trial_modes:
        r = bench_e2e(
            lanes=args.e2e_lanes, steps=args.e2e_steps,
            grads_mb=args.e2e_mb, n_leaves=args.e2e_leaves,
            mbps=args.mbps, rtt_ms=args.rtt_ms, bucket_mb=args.e2e_bucket_mb,
            compute_iters=args.e2e_compute_iters, trials=args.trials,
            **mode_kw,
        )
        e2e.append(r)
        results.append(r)
        print(json.dumps(r), flush=True)

    kill = bench_peer_kill(lanes=2)
    results.append(kill)
    print(json.dumps(kill), flush=True)

    def find(mode: str) -> Optional[Dict[str, Any]]:
        return next((r for r in e2e if r["mode"] == mode), None)

    pipe = find("pipelined")
    mono = find("monolithic")
    prep = find("pipelined+device_prep")
    sharded = find("pipelined+device_prep+sharded")
    # The flat lane keys quote what the deployment default (auto) runs:
    # the native engine when its cells exist, the Python engine otherwise.
    main_engine = (
        "native" if lane_gbps.get("native") else
        next(e for e in engines if lane_gbps.get(e))
    )
    main_lanes = lane_gbps[main_engine]
    summary: Dict[str, Any] = {
        "link": {"mbps": args.mbps, "rtt_ms": args.rtt_ms},
        "payload_mb": args.mb,
        "engine": main_engine,
        "lane_gb_per_s": {str(l): g for l, g in sorted(main_lanes.items())},
        "monolithic_steps_per_s": mono["steps_per_s"] if mono else None,
        "peer_kill_ok": kill["ok"],
    }
    if "py" in lane_gbps and main_engine != "py":
        # The Python-engine reference cells (comparable to the pre-native
        # artifacts) plus the shaped native-over-py ceiling ratio.
        summary["lane_gb_per_s_py"] = {
            str(l): g for l, g in sorted(lane_gbps["py"].items())
        }
        shared = [
            l for l in main_lanes
            if l in lane_gbps["py"] and lane_gbps["py"][l]
        ]
        if shared:
            top = max(shared)
            summary["shaped_native_over_py"] = round(
                main_lanes[top] / lane_gbps["py"][top], 3
            )
    if engine_loopback:
        summary["engine_loopback_gb_per_s"] = dict(sorted(engine_loopback.items()))
        if engine_loopback.get("py"):
            summary["native_loopback_speedup"] = round(
                engine_loopback.get("native", 0.0) / engine_loopback["py"], 2
            )
    if args.engine == "both":
        summary["engine_parity_bitwise"] = parity
    if transport_section is not None:
        summary["transport_parity_bitwise"] = transport_section["parity_bitwise"]
        if "shm_speedup" in transport_section:
            summary["shm_speedup"] = transport_section["shm_speedup"]
        ms = transport_section.get("multi_stripe")
        if ms is not None:
            summary["multi_stripe_one_call_per_op"] = ms["one_call_per_op"]
    if pipe:
        summary["pipelined_steps_per_s"] = pipe["steps_per_s"]
        if mono and mono["steps_per_s"]:
            summary["pipelined_speedup"] = round(
                pipe["steps_per_s"] / mono["steps_per_s"], 3
            )
    if prep:
        summary["device_prep_steps_per_s"] = prep["steps_per_s"]
        summary["device_prep_d2h_bytes"] = prep["d2h_bytes"]
        if pipe:
            summary["host_cast_d2h_bytes"] = pipe["d2h_bytes"]
            if prep["d2h_bytes"]:
                summary["d2h_reduction"] = round(
                    pipe["d2h_bytes"] / prep["d2h_bytes"], 3
                )
    if sharded:
        summary["sharded_steps_per_s"] = sharded["steps_per_s"]
        summary["sharded_fetch_slices"] = sharded["fetch_slices"]
        if sharded["slices_per_bucket"]:
            # Per-slice fetch granularity: on a multi-host group each host
            # pulls only its addressable slices, so per-host bytes shrink
            # by the shard factor; on this single-host bench the factor
            # shows up as the MEASURED slice count per bucket (not the
            # requested --sharded-devices, which an inherited XLA_FLAGS
            # can override in the workers).
            summary["shard_factor"] = sharded["slices_per_bucket"]
    if 1 in main_lanes and 4 in main_lanes:
        summary["speedup_4_lanes"] = round(main_lanes[4] / main_lanes[1], 2)
    if 1 in main_lanes and 2 in main_lanes:
        summary["speedup_2_lanes"] = round(main_lanes[2] / main_lanes[1], 2)
    if topo_gbps:
        summary["topology_gb_per_s"] = {
            t: g for t, g in sorted(topo_gbps.items())
        }
        summary["topology_world"] = args.topo_world
        if "ring" in topo_gbps and "ring2d" in topo_gbps and topo_gbps["ring"]:
            summary["ring2d_speedup"] = round(
                topo_gbps["ring2d"] / topo_gbps["ring"], 3
            )
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        # The full sweep replaces results+summary but must not drop the
        # additive cells other invocations merge in (--link writes
        # doc["link"]); the artifact is one document with two writers.
        doc: Dict[str, Any] = {}
        if os.path.exists(args.out):
            try:
                with open(args.out) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                doc = {}
        doc["results"] = results
        doc["summary"] = summary
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)


if __name__ == "__main__":
    main()
