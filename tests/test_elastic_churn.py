"""Elastic membership-churn soak over live TCPCollectives.

The tentpole's correctness claims under churn, exercised at the collective
layer where they are cheapest to drive hard:

- ``test_churn_soak_bitwise_and_no_leaks`` walks >=20 seeded join/leave
  transitions (membership 2..6) crossing the ring2d<->ring boundary in BOTH
  directions, with one heal-racing-admit generation (a surviving member is
  replaced by a fresh incarnation in the same transition that admits a new
  member).  Every generation's allreduce must be bitwise identical across
  members (the property the commit protocol votes on), no survivor op may
  fail, and the soak must leak neither fds nor /dev/shm segments.

- ``test_incremental_vs_full_bitwise_parity`` is the parity matrix: the
  same membership walk + payloads run with TPUFT_INCREMENTAL_RECONF=1
  (lane-reuse fast path) and =0 (full teardown-and-rendezvous every
  transition — the baseline collectives.py names for exactly this soak)
  must produce bitwise-identical reductions in every generation, for f32
  and bf16 payloads both.

- ``test_shm_lane_churn_reuse_and_cleanup`` runs the churn over shm lanes:
  surviving segments must be reused by the incremental path and every
  segment reclaimed at shutdown.
"""

import gc
import glob
import os
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np
import pytest

from torchft_tpu._native import StoreServer
from torchft_tpu.collectives import TCPCollective


@pytest.fixture(scope="module")
def store():
    server = StoreServer(bind="127.0.0.1:0")
    yield server
    server.shutdown()


_PREFIX_COUNTER = [0]
_PREFIX_LOCK = threading.Lock()


def fresh_prefix() -> str:
    with _PREFIX_LOCK:
        _PREFIX_COUNTER[0] += 1
        return f"churn/{_PREFIX_COUNTER[0]}"


def _fd_count() -> int:
    return len(os.listdir("/proc/self/fd"))


def _shm_segments() -> set:
    # This process's segments only (`_create_shm_segment` names them
    # tpuft-<pid>-...): other test workers on the machine make their own.
    return set(glob.glob(f"/dev/shm/tpuft-{os.getpid()}-*"))


def _settle_fds(target: int, timeout_s: float = 10.0) -> int:
    """Closed sockets and joined accept threads release fds a beat after
    shutdown() returns; poll with gc until the count drops to target."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        gc.collect()
        n = _fd_count()
        if n <= target:
            return n
        time.sleep(0.2)
    gc.collect()
    return _fd_count()


def _run_generation(
    store, members: Dict[int, TCPCollective], *, timeout: float = 20.0
) -> Dict[str, object]:
    """One quorum transition: rendezvous every live member onto a fresh
    store prefix (ranked by sorted member id — the stable ordering the
    Manager derives from replica ids) and run one lockstep allreduce.

    Asserts the commit protocol's ground truth for the generation: every
    member's reduction is BITWISE identical, and — because the payloads
    are small integers, exact in f32 — equal to the true sum."""
    live = sorted(members)
    world = len(live)
    prefix = fresh_prefix()

    def worker(rank: int) -> Dict[str, object]:
        c = members[live[rank]]
        c.configure(f"{store.address()}/{prefix}", rank, world)
        x = np.full(257, float(rank + 1), dtype=np.float32)
        out = c.allreduce([x], op="sum").wait(timeout=timeout)[0]
        return {
            "member": live[rank],
            "mode": c.last_configure["mode"],
            "reused_lanes": c.last_configure["reused_lanes"],
            "topology": c._active_topology,
            "bits": out.tobytes(),
            "value": float(out[0]),
        }

    with ThreadPoolExecutor(max_workers=world) as pool:
        futures = [pool.submit(worker, r) for r in range(world)]
        results = [f.result(timeout=timeout + 30) for f in futures]

    digests = {r["bits"] for r in results}
    assert len(digests) == 1, f"replica divergence at world={world}"
    expected = float(world * (world + 1) // 2)
    assert results[0]["value"] == expected, (results[0]["value"], expected)
    topos = {r["topology"] for r in results}
    assert len(topos) == 1, f"topology disagreement: {topos}"
    return {
        "world": world,
        "topology": topos.pop(),
        "modes": [r["mode"] for r in results],
        "reused_lanes": sum(int(r["reused_lanes"]) for r in results),
    }


def _make_plan(rng: random.Random, n: int, start_world: int) -> List[str]:
    """Seeded membership walk bounded to [2, 6], prefixed with a scripted
    leg that guarantees both ring2d<->ring crossing directions (4->3, 3->4
    with ring2d_min=4) and a flat->flat leg (3->2) where the incremental
    path can engage."""
    plan = ["leave", "leave", "join", "join"]  # 4->3->2->3->4
    cur = start_world
    for _ in range(n - len(plan)):
        if cur <= 2:
            kind = "join"
        elif cur >= 6:
            kind = "leave"
        else:
            kind = rng.choice(["join", "leave"])
        plan.append(kind)
        cur += 1 if kind == "join" else -1
    return plan


def test_churn_soak_bitwise_and_no_leaks(store, monkeypatch) -> None:
    monkeypatch.setenv("TPUFT_RING_TOPOLOGY", "auto")
    monkeypatch.setenv("TPUFT_RING2D_MIN_GROUPS", "4")
    monkeypatch.setenv("TPUFT_INCREMENTAL_RECONF", "1")
    gc.collect()
    fd_before = _fd_count()
    shm_before = _shm_segments()

    rng = random.Random(20)
    members: Dict[int, TCPCollective] = {
        i: TCPCollective(timeout=15.0, topology="auto") for i in range(4)
    }
    next_id = 4
    plan = _make_plan(rng, 21, start_world=4)
    heal_at = next(
        i for i, k in enumerate(plan) if i > 4 and k == "join"
    )  # first post-scripted join doubles as the heal-racing-admit round

    try:
        gen0 = _run_generation(store, members)
        assert gen0["topology"] == "ring2d", gen0  # world 4, min 4
        prev_topology = gen0["topology"]
        transitions = 0
        modes_seen = set(gen0["modes"])
        crossings = set()
        reuse_total = 0

        for i, kind in enumerate(plan):
            if kind == "leave":
                victim = rng.choice(sorted(members))
                members.pop(victim).shutdown()
            else:
                if i == heal_at:
                    # Heal racing admit: one survivor comes back as a
                    # fresh incarnation (non-reusable edges, full path)
                    # in the SAME generation that hot-admits a member.
                    healed = rng.choice(sorted(members))
                    members[healed].shutdown()
                    members[healed] = TCPCollective(timeout=15.0, topology="auto")
                members[next_id] = TCPCollective(timeout=15.0, topology="auto")
                next_id += 1
            gen = _run_generation(store, members)
            transitions += 1
            modes_seen.update(gen["modes"])
            reuse_total += gen["reused_lanes"]
            if gen["topology"] != prev_topology:
                crossings.add((prev_topology, gen["topology"]))
            prev_topology = gen["topology"]

        assert transitions >= 20, transitions
        assert "incremental" in modes_seen, modes_seen
        assert "full" in modes_seen, modes_seen
        assert reuse_total > 0, "incremental path never reused a lane"
        assert ("ring2d", "ring") in crossings, crossings
        assert ("ring", "ring2d") in crossings, crossings
    finally:
        for c in members.values():
            c.shutdown()

    fd_after = _settle_fds(fd_before)
    assert fd_after <= fd_before, f"leaked fds: {fd_before} -> {fd_after}"
    assert _shm_segments() == shm_before, "leaked shm segments"


# Fixed walk for the parity matrix: worlds 4->3->2->3->4->5->4->3, covering
# ring2d<->ring both ways, the flat 2-world (next and prev collapse onto one
# peer), and the prime world 5 (grid cannot factor -> flat degrade).
_PARITY_EVENTS = [
    ("leave", 3, None),
    ("leave", 1, None),
    ("join", None, 4),
    ("join", None, 5),
    ("join", None, 6),
    ("leave", 5, None),
    ("leave", 0, None),
]


def _parity_walk(store, incremental: str, monkeypatch) -> List[List[bytes]]:
    """Runs the fixed membership walk and returns each generation's
    reductions as raw bytes, ordered by rank.  TPUFT_INCREMENTAL_RECONF is
    captured in TCPCollective.__init__, so it is set BEFORE any
    construction; all member incarnations are pre-created so later joins
    inherit the same setting."""
    monkeypatch.setenv("TPUFT_RING_TOPOLOGY", "auto")
    monkeypatch.setenv("TPUFT_RING2D_MIN_GROUPS", "4")
    monkeypatch.setenv("TPUFT_INCREMENTAL_RECONF", incremental)
    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    universe = {i: TCPCollective(timeout=15.0, topology="auto") for i in range(7)}
    live = {i: universe[i] for i in range(4)}
    out: List[List[bytes]] = []
    modes_seen = set()

    def run_gen() -> None:
        members = sorted(live)
        world = len(members)
        prefix = fresh_prefix()

        def worker(rank: int) -> bytes:
            c = live[members[rank]]
            c.configure(f"{store.address()}/{prefix}", rank, world)
            xs = [
                np.arange(96, dtype=np.float32) % 7.0 + float(rank + 1),
                np.full(33, float(rank + 1), dtype=bf16),
            ]
            res = c.allreduce(xs, op="sum").wait(timeout=20)
            modes_seen.add(c.last_configure["mode"])
            return res[0].tobytes() + res[1].tobytes()

        with ThreadPoolExecutor(max_workers=world) as pool:
            futures = [pool.submit(worker, r) for r in range(world)]
            out.append([f.result(timeout=45) for f in futures])

    try:
        run_gen()
        for kind, victim, joiner in _PARITY_EVENTS:
            if kind == "leave":
                live.pop(victim).shutdown()
            else:
                live[joiner] = universe[joiner]
            run_gen()
    finally:
        for c in universe.values():
            c.shutdown()

    if incremental == "1":
        assert "incremental" in modes_seen, modes_seen
    else:
        assert modes_seen == {"full"}, modes_seen
    return out


def test_incremental_vs_full_bitwise_parity(store, monkeypatch) -> None:
    fast = _parity_walk(store, "1", monkeypatch)
    full = _parity_walk(store, "0", monkeypatch)
    assert len(fast) == len(full) == len(_PARITY_EVENTS) + 1
    for gen, (a, b) in enumerate(zip(fast, full)):
        # Bitwise within each fleet (replica consistency)...
        assert len(set(a)) == 1, f"incremental fleet diverged at gen {gen}"
        assert len(set(b)) == 1, f"full fleet diverged at gen {gen}"
        # ...and bitwise ACROSS the reconfigure strategies: lane reuse must
        # be invisible to the math, f32 and bf16 alike.
        assert a[0] == b[0], f"incremental vs full mismatch at gen {gen}"


def test_world2_neighbor_replacement_no_stall(store, monkeypatch) -> None:
    """World-2 restart: the survivor's ONLY neighbor is replaced by a fresh
    incarnation, so no edge survives the transition.  The survivor must
    stay on the incremental path and rebuild both edges over its KEPT
    listener.  Regression: it used to publish its address, then fall back
    to the full path ("nothing survives") — closing the listener the fresh
    peer had already dialed, stranding the peer on dead sockets and burning
    the survivor's entire 60 s rendezvous timeout on a replacement listener
    nobody dials (the Manager-level symptom: test_ddp_recovery stalling a
    minute per restart)."""
    monkeypatch.setenv("TPUFT_INCREMENTAL_RECONF", "1")
    members: Dict[int, TCPCollective] = {
        0: TCPCollective(timeout=15.0, topology="ring"),
        1: TCPCollective(timeout=15.0, topology="ring"),
    }
    try:
        _run_generation(store, members)
        for _ in range(2):  # twice: the rebuilt edges must survive a rebuild
            members.pop(1).shutdown()
            members[1] = TCPCollective(timeout=15.0, topology="ring")
            t0 = time.monotonic()
            gen = _run_generation(store, members)
            elapsed = time.monotonic() - t0
            assert elapsed < 20.0, f"replacement transition stalled {elapsed:.1f}s"
            # modes are rank-ordered: rank 0 is the survivor, rank 1 fresh.
            assert gen["modes"][0] == "incremental", gen
            assert gen["modes"][1] == "full", gen
            assert gen["reused_lanes"] == 0, gen
    finally:
        for c in members.values():
            c.shutdown()


def test_shm_lane_churn_reuse_and_cleanup(store, monkeypatch) -> None:
    """Membership churn over same-host shm lanes: the incremental path must
    keep surviving segments (reuse>0), results stay bitwise consistent, and
    shutdown reclaims every segment."""
    monkeypatch.setenv("TPUFT_INCREMENTAL_RECONF", "1")
    shm_before = _shm_segments()

    def make() -> TCPCollective:
        return TCPCollective(
            timeout=15.0, lanes=2, transport="shm", chunk_bytes=4 << 10,
            topology="ring",
        )

    members: Dict[int, TCPCollective] = {i: make() for i in range(3)}
    modes_seen = set()
    reuse_total = 0
    try:
        for kind, mid in (
            (None, None), ("leave", 2), ("join", 3), ("leave", 0), ("join", 4),
        ):
            if kind == "leave":
                members.pop(mid).shutdown()
            elif kind == "join":
                members[mid] = make()
            gen = _run_generation(store, members)
            modes_seen.update(gen["modes"])
            reuse_total += gen["reused_lanes"]
            for c in members.values():
                assert c.ring_transport == "shm"
            assert _shm_segments() - shm_before, "no shm segments negotiated"
    finally:
        for c in members.values():
            c.shutdown()
    assert "incremental" in modes_seen, modes_seen
    assert reuse_total > 0, "shm lanes never reused across a transition"
    assert _shm_segments() == shm_before, "leaked shm segments"
