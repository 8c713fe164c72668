"""Live cells of the cross-group allreduce, driven by the integration
smokes (tests/test_integration_smokes.py).  Everything runs in this process,
ranks and replica groups as threads, on localhost; what a cell returns is
counts and booleans (bytes per lane, commits, bitwise parity, order of
records), never a time or a rate: nothing timed on this host is a speed.

  lanes       -- 2-rank TCPCollective, a GradientAverager-shaped stream of
                 bucket allreduces at a given lane count, ring engine and
                 lane transport: per-lane wire bytes and what the
                 configuration resolved to.
  e2e         -- 2 full replica groups (real lighthouse + Managers) through
                 a step loop: pipelined vs monolithic bucket path, host
                 cast vs device wire prep vs sharded fetch; committed
                 counts and the averager's transfer accounting.
  peer_kill   -- 3 replica groups, one dies mid-allreduce: the survivors
                 latch the error, fail the commit cleanly and rebuild every
                 lane against the shrunken world.
  link        -- the slow-link sentinel: one peer's outbound link re-shaped
                 mid-run, detected within a bounded number of rounds.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from datetime import timedelta
from typing import Any, Dict, Iterator, List, Optional

import numpy as np


@contextlib.contextmanager
def _scoped_env(overrides: Dict[str, Optional[str]]) -> Iterator[None]:
    """Applies env overrides for the block (None = unset)."""
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


def _shaped(mbps: float, rtt_ms: float):
    """TPUFT_SHAPED_LINK for the block (unshaped when ``mbps`` is 0)."""
    if mbps <= 0:
        return contextlib.nullcontext()
    return _scoped_env({"TPUFT_SHAPED_LINK": f"{mbps}:{rtt_ms}"})


def _await_heartbeats(lighthouse, groups: int, timeout_s: float) -> None:
    """Blocks until the lighthouse has a heartbeat on file from ``groups``
    replica groups.  Its join wait covers only replicas it can SEE: a
    Manager that is constructed but has not heartbeated yet is invisible,
    and a first quorum formed without it drags it in one step later."""
    import urllib.request

    url = f"http://127.0.0.1:{lighthouse.http_address().rsplit(':', 1)[1]}/status.json"
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=5) as resp:
                seen = json.loads(resp.read().decode()).get("heartbeat_age_ms", {})
        except (OSError, ValueError):
            seen = {}
        if len({str(k).split(":", 1)[0] for k in seen}) >= groups:
            return
        time.sleep(0.05)
    raise TimeoutError(f"lighthouse never saw {groups} groups heartbeat")


def _run_ranks(body, world: int) -> Dict[int, Any]:
    """``body(rank)`` on one thread per rank; the first error is re-raised."""
    results: Dict[int, Any] = {}
    errors: List[BaseException] = []

    def run(rank: int) -> None:
        try:
            results[rank] = body(rank)
        except BaseException as e:  # noqa: BLE001 -- re-raised below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results


def make_buckets(total_bytes: int, n_buckets: int) -> List[np.ndarray]:
    per = max(1, total_bytes // n_buckets // 4)
    return [np.full((per,), float(i), dtype=np.float32) for i in range(n_buckets)]


# ---------------------------------------------------------------------------
# Collective-level lane cells
# ---------------------------------------------------------------------------


def _lane_rank_body(
    collective, rank: int, nbytes: int, n_buckets: int, timeout: float,
    world: int = 2,
) -> Dict[str, Any]:
    """One rank's bucket stream: issue every bucket, then drain -- the
    GradientAverager traffic shape."""
    buckets = make_buckets(nbytes, n_buckets)
    # The scaled bucket is a temporary -- donate it so the native engine
    # reduces in place over the caller's buffer; the Python engine ignores
    # the hint, so both engines see the same workload.
    works = [
        collective.allreduce([b * (rank + 1)], op="sum", donate=True)
        for b in buckets
    ]
    outs = [w.wait(timeout=timeout) for w in works]
    expected_last = (n_buckets - 1) * world * (world + 1) / 2.0
    assert float(np.asarray(outs[0][0])[0]) == 0.0
    assert abs(float(np.asarray(outs[-1][0])[0]) - expected_last) < 0.5
    return {"lane_stats": collective.lane_stats(),
            "topology": collective.topology,
            "transport": collective.ring_transport}


def bench_lanes(
    payload_mb: float,
    lanes: int,
    n_buckets: int = 8,
    timeout: float = 300.0,
    engine: Optional[str] = None,
    transport: Optional[str] = None,
) -> Dict[str, Any]:
    """2-rank bucketed allreduce stream at the given lane count on unshaped
    loopback.  ``engine`` pins the ring hot-loop engine ("py"/"native") and
    ``transport`` the lane transport ("tcp"/"shm"); None keeps the
    collective's default.  Returns the lane byte counters and what the
    configuration actually resolved to."""
    from torchft_tpu._native import StoreServer
    from torchft_tpu.collectives import TCPCollective

    world = 2
    nbytes = int(payload_mb * (1 << 20))
    store = StoreServer(bind="127.0.0.1:0")
    cols = [
        TCPCollective(timeout=timeout, lanes=lanes, engine=engine,
                      transport=transport)
        for _ in range(world)
    ]
    try:
        prefix = (
            f"{store.address()}/lanes{lanes}_{engine or 'auto'}"
            f"_{transport or 'default'}"
        )
        _run_ranks(lambda r: cols[r].configure(prefix, r, world), world)
        per_rank = _run_ranks(
            lambda r: _lane_rank_body(cols[r], r, nbytes, n_buckets, timeout,
                                      world=world),
            world,
        )
    finally:
        for c in cols:
            c.shutdown()
        store.shutdown()
    return {
        "lanes": lanes,
        "topology": per_rank[0].get("topology", "ring"),
        # The ring hot-loop engine this configuration RESOLVED to -- a
        # requested "native" on a stale .so degrades to "py" and the record
        # says so, per the no-silent-fallback contract.
        "engine": per_rank[0]["lane_stats"].get("engine", "py"),
        # The lane transport that actually ran ("shm" only when the
        # same-host handshake armed at least one segment).
        "transport": per_rank[0].get("transport", "tcp"),
        "payload_bytes": sum(b.nbytes for b in make_buckets(nbytes, n_buckets)),
        "buckets": n_buckets,
        # Per-lane wire bytes from rank 0 (striping balance evidence).
        "lane_bytes_sent": per_rank[0]["lane_stats"].get("sent"),
    }


def _bitwise_parity(
    option: str, values, calls, seed: int, n_elems: int, lanes: int,
    timeout: float, **fixed,
) -> bool:
    """The SAME deterministic payload through a 2-rank ring built once per
    value of one constructor option (``engine`` or ``transport``), each
    entry of ``calls`` one allreduce: every output must be IDENTICAL bits
    across the two values.  False too when a requested value did not
    resolve to itself -- that run proves nothing."""
    from torchft_tpu._native import StoreServer
    from torchft_tpu.collectives import TCPCollective

    rng = np.random.default_rng(seed)
    data = [
        (rng.standard_normal(n_elems) * (r + 1)).astype(np.float32)
        for r in range(2)
    ]
    outs: List[List[np.ndarray]] = []
    store = StoreServer(bind="127.0.0.1:0")
    try:
        for value in values:
            cols = [
                TCPCollective(timeout=timeout, lanes=lanes, **fixed,
                              **{option: value})
                for _ in range(2)
            ]

            def body(rank: int, cols=cols, value=value) -> List[np.ndarray]:
                c = cols[rank]
                c.configure(f"{store.address()}/parity_{option}_{value}", rank, 2)
                return [
                    c.allreduce([data[rank]], **kw).wait(timeout=timeout)[0]
                    for kw in calls
                ]

            try:
                results = _run_ranks(body, 2)
                # Read BEFORE shutdown -- abort clears the engine handle, so
                # a post-shutdown ring_engine always reports "py".
                resolved = getattr(cols[0], f"ring_{option}")
            finally:
                for c in cols:
                    c.shutdown()
            if resolved != value:
                return False
            outs.append(results[0])
    finally:
        store.shutdown()
    return all(
        a.dtype == b.dtype
        and a.shape == b.shape
        and bool((a.view(np.uint32) == b.view(np.uint32)).all())
        for a, b in zip(*outs)
    )


def check_engine_parity(
    n_elems: int = 1 << 14, lanes: int = 2, timeout: float = 60.0
) -> bool:
    """Bitwise engine parity on live rings: f32 raw framing (compression
    off), the bf16 wire and the int8 codec through a py-engine pair and a
    native-engine pair -- the contract that lets "auto" switch engines
    without a numerics review.  The exhaustive topology x codec x lanes
    matrix lives in tests/test_ring_engine.py; this is the live pin."""
    return _bitwise_parity(
        "engine", ("py", "native"),
        [{"op": "sum", "allow_wire_compression": False}, {"op": "avg"},
         {"op": "sum", "wire_codec": "int8"}],
        seed=1234, n_elems=n_elems, lanes=lanes, timeout=timeout,
        wire_dtype="bf16",
    )


def run_engine_quick(payload_mb: float = 8.0, lanes: int = 2) -> Dict[str, Any]:
    """The ring engines side by side: one py cell and one native cell at the
    same unshaped-loopback configuration, plus the live bitwise parity pin."""
    return {
        "cells": [
            bench_lanes(payload_mb=payload_mb, lanes=lanes, n_buckets=4,
                        timeout=120.0, engine=engine)
            for engine in ("py", "native")
        ],
        "parity_bitwise": check_engine_parity(),
    }


def check_transport_parity(
    n_elems: int = 1 << 14, lanes: int = 2, timeout: float = 60.0
) -> bool:
    """Bitwise transport parity on live rings: f32 raw, the int8 codec and
    the int4 codec through a tcp pair and an shm pair -- the shm lane
    replaces the byte PIPE under the frame protocol, never the arithmetic,
    so any divergence is a framing bug."""
    return _bitwise_parity(
        "transport", ("tcp", "shm"),
        [{"op": "sum", "allow_wire_compression": False},
         {"op": "sum", "wire_codec": "int8"},
         {"op": "sum", "wire_codec": "int4"}],
        seed=4321, n_elems=n_elems, lanes=lanes, timeout=timeout,
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
    cols = [
        TCPCollective(timeout=timeout, lanes=lanes, chunk_bytes=chunk_bytes,
                      engine="native")
        for _ in range(2)
    ]

    def body(rank: int) -> Optional[int]:
        c = cols[rank]
        c.configure(f"{store.address()}/multistripe", rank, 2)
        if c.ring_engine != "native":
            return None
        x = np.arange(n_elems, dtype=np.float32) * (rank + 1)
        for _ in range(ops):
            c.allreduce([x], op="sum").wait(timeout=timeout)
        return c._engine.pass_calls

    try:
        pass_calls = _run_ranks(body, 2)[0]
    finally:
        for c in cols:
            c.shutdown()
        store.shutdown()
    if pass_calls is None:
        return None  # native engine did not resolve
    return {
        "ops": ops,
        "stripes_per_op": nstripes,
        "pass_calls": pass_calls,
        "one_call_per_op": pass_calls == ops,
    }


def run_transport_quick(payload_mb: float = 4.0, lanes: int = 2) -> Dict[str, Any]:
    """The same-host lane transports side by side: one tcp cell, one shm
    cell (stripe frames through a lock-free SPSC ring in /dev/shm instead of
    the kernel socket path -- same frames, no syscalls per hop), the live
    bitwise parity pin, and the one-call multi-stripe pin."""
    return {
        "cells": [
            bench_lanes(payload_mb=payload_mb, lanes=lanes, n_buckets=4,
                        timeout=120.0, transport=t)
            for t in ("tcp", "shm")
        ],
        "parity_bitwise": check_transport_parity(lanes=lanes),
        "multi_stripe": check_multi_stripe(lanes=lanes),
    }


# ---------------------------------------------------------------------------
# End to end: pipelined vs monolithic, host cast vs device wire prep
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
    device_prep: bool = False,
    sharded: bool = False,
    wire_dtype: str = "auto",
) -> Dict[str, Any]:
    """One replica group's loop: start_quorum -> averager.allreduce(grads)
    -> should_commit, ``steps`` times.  ``device_prep``/``sharded`` select
    the averager's device-resident wire prep and sharding-aware fetch; the
    d2h/h2d/wire bytes come from the averager's transfer accounting."""
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
        grads = _grad_tree(nbytes, n_leaves)
        committed = 0
        xfer = {"d2h_bytes": 0, "h2d_bytes": 0, "wire_bytes": 0, "slices": 0}
        for _ in range(steps):
            manager.start_quorum()
            averager.allreduce(grads)
            for k in xfer:
                xfer[k] += int(averager.last_stats.get(k, 0))
            if manager.should_commit():
                committed += 1
        return {"committed": committed, **xfer}
    finally:
        manager.shutdown()


def bench_e2e(
    lanes: int,
    pipelined: bool,
    steps: int,
    grads_mb: float,
    n_leaves: int,
    bucket_mb: float = 4.0,
    timeout_s: float = 120.0,
    device_prep: bool = False,
    sharded: bool = False,
    wire_dtype: str = "auto",
) -> Dict[str, Any]:
    """2 replica groups (threads), real lighthouse + Managers: committed
    steps and transfer bytes for one bucket path."""
    from torchft_tpu._native import LighthouseServer

    nbytes = int(grads_mb * (1 << 20))
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=2,
        join_timeout_ms=5000, quorum_tick_ms=20,
    )
    try:
        per_group = _run_ranks(
            lambda gid: _e2e_group_body(
                lighthouse.address(), gid, lanes, pipelined, steps, nbytes,
                n_leaves, bucket_mb, timeout_s, device_prep, sharded,
                wire_dtype,
            ),
            2,
        )
    finally:
        lighthouse.shutdown()
    mode = "pipelined" if pipelined else "monolithic"
    if device_prep:
        mode += "+device_prep"
    if sharded:
        mode += "+sharded"
    return {
        "mode": mode,
        "device_prep": device_prep,
        "sharded_fetch": sharded,
        "wire_dtype": wire_dtype,
        # Transfer accounting over the run (group 0's view; groups are
        # symmetric): D2H fetch bytes, H2D scatter-back bytes, and the
        # payload bytes handed to the ring -- with device wire prep the d2h
        # side reads wire (bf16) bytes, half of f32.
        "d2h_bytes": per_group[0]["d2h_bytes"],
        "h2d_bytes": per_group[0]["h2d_bytes"],
        "wire_bytes": per_group[0]["wire_bytes"],
        "fetch_slices": per_group[0]["slices"],
        "steps": steps,
        "committed": min(r["committed"] for r in per_group.values()),
    }


# ---------------------------------------------------------------------------
# Mid-allreduce peer kill
# ---------------------------------------------------------------------------


# Where in step 1's allreduce the victim dies: after this many bytes of its
# own sends (the op moves some 10 MB a group at the cell's default size).
_KILL_AFTER_BYTES = 1 << 20


def bench_peer_kill(
    lanes: int = 2,
    grads_mb: float = 16.0,
    mbps: float = 200.0,
    rtt_ms: float = 10.0,
    timeout_s: float = 60.0,
) -> Dict[str, Any]:
    """3 replica groups; group 2 dies mid-allreduce at step 1 (collective
    abort + manager shutdown, the in-process stand-in for kill -9) once it
    has sent ``_KILL_AFTER_BYTES`` of that step's payload.  Proves:
    survivors LATCH the error (no raise into the loop), should_commit fails
    cleanly, and the next quorum rebuilds every lane with the old lane
    sockets closed."""
    from torchft_tpu._native import LighthouseServer
    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.ddp import GradientAverager
    from torchft_tpu.manager import Manager

    # The floor of 2 lets the survivors go on alone.  The join wait is what
    # makes the FIRST quorum hold all three however loaded the host is (it
    # covers only replicas that heartbeat, so it does not hold the
    # survivors' quorum back once the victim's manager is gone), and the
    # heartbeat timeout is wide enough that a starved survivor is not
    # declared dead.
    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=10000,
        quorum_tick_ms=20, heartbeat_timeout_ms=3000,
    )
    nbytes = int(grads_mb * (1 << 20))
    evidence: Dict[str, Any] = {}
    errors: List[BaseException] = []
    barrier = threading.Barrier(4)  # the three groups and this driver
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
                # survivors' stripes are in flight.  The kill is placed by a
                # COUNT, not a clock: once the victim has put
                # ``_KILL_AFTER_BYTES`` of step 1's allreduce on the wire
                # (its own lane counters, polled), however slow the host.
                def die() -> None:
                    # The counters restart at a reconfigure (a late joiner
                    # can cause one between the steps): add up increments.
                    last = sum(collective.lane_stats()["sent"])
                    sent = 0
                    while sent < _KILL_AFTER_BYTES:
                        time.sleep(0.001)
                        now = sum(collective.lane_stats()["sent"])
                        sent += now - last if now >= last else now
                        last = now
                    evidence["kill_ts"] = time.time()
                    victim_killed.set()  # before abort(): it returns late
                    collective.abort()

                threading.Thread(target=die, daemon=True).start()
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
        try:
            # Step 0 must be a merged step of all three.
            _await_heartbeats(lighthouse, 3, timeout_s)
            barrier.wait(timeout=timeout_s)
        except BaseException:
            barrier.abort()
            raise
        finally:
            for t in threads:
                t.join()
            lighthouse.shutdown()
    if errors:
        raise errors[0]
    evidence.update(
        {
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
# Slow-link sentinel cell (data-plane flight recorder)
# ---------------------------------------------------------------------------


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
    steps: int = 14,
    payload_kb: int = 192,
    mbps: float = 60.0,
    rtt_ms: float = 4.0,
    degrade_at: int = 5,
    degrade_factor: float = 10.0,
    engine: Optional[str] = None,
    workdir: Optional[str] = None,
) -> Dict[str, Any]:
    """The slow-link sentinel cell (docs/architecture.md "Data-plane
    observability"):

    * ``healthy`` -- the control run: same cluster, no fault; MUST raise
      zero slow_link alerts, and its byte attribution is the baseline.
    * ``degraded`` -- at round ``degrade_at`` the victim's outbound link is
      re-shaped ``degrade_factor``x slower mid-run (no reconfigure, no
      process fault: invisible to heartbeat timeouts AND to the straggler
      sentinel's wall-minus-waits signal, which equalizes across the
      lockstep ring).  The cell counts the victim's commit rounds until
      the alert and runs obs.report.link_attribution over both runs'
      step_summary streams: the ADDED wall must land in the
      wire/shaping/stall buckets, not combine.
    """
    import shutil
    import tempfile

    from torchft_tpu.obs.report import link_attribution, read_events

    own_workdir = workdir is None
    if own_workdir:
        workdir = tempfile.mkdtemp(prefix="tpuft_link_")
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
    """Small payloads: 1 vs 2 lanes at the collective level, and pipelined
    vs monolithic commit counts end to end."""
    return {
        "lanes": [
            bench_lanes(payload_mb=2.0, lanes=n, n_buckets=4, timeout=60.0)
            for n in (1, 2)
        ],
        "e2e": [
            bench_e2e(lanes=2, pipelined=p, steps=3, grads_mb=2.0, n_leaves=8,
                      bucket_mb=0.5, timeout_s=60.0)
            for p in (True, False)
        ],
    }
