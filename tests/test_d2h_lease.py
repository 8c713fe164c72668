"""The host's device-to-host lease (``torchft_tpu/d2h_lease.py``) between
real processes, and ``futures.device_get_into``'s use of it.

Counts and orders only, never a duration: the holders' shared log is written
while the lease is held, so its order is the order of the turns.  The
processes are this file run as a script (``--worker``): they import the
lease and nothing of JAX.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import types
import warnings

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from torchft_tpu import d2h_lease  # noqa: E402
from torchft_tpu.d2h_lease import D2HLease, Held  # noqa: E402

PROCS = 4
TURNS = 6
WAIT_S = 60.0  # every wait of a test gives up then, and the test fails


# ---------------------------------------------------------------------------
# The worker: one process that takes turns at the lease.
# ---------------------------------------------------------------------------


def _log(path: str, **rec) -> None:
    """One line in one ``write`` to a file opened for append: whole, and in
    the order of the calls."""
    fd = os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
    try:
        os.write(fd, (json.dumps(rec) + "\n").encode())
    finally:
        os.close(fd)


def _mark(sync: str, name: str) -> None:
    open(os.path.join(sync, name), "w").close()


def _wait_for(sync: str, names, what: str) -> None:
    deadline = time.monotonic() + WAIT_S
    while not all(os.path.exists(os.path.join(sync, n)) for n in names):
        if time.monotonic() > deadline:
            raise TimeoutError(f"{what}: not everyone arrived")
        time.sleep(0.001)


def worker(mode: str, lease_path: str, sync: str, rank: int, nbytes: int) -> int:
    lease = D2HLease(lease_path)
    log = os.path.join(sync, "log.jsonl")
    everyone = range(PROCS)
    if mode == "hold":
        # Takes the lease, says so, and sits on it until it is killed.
        held = lease.acquire(nbytes, WAIT_S)
        _log(log, rank=rank, event="begin", outcome=held.outcome, start=held.segment[0])
        _mark(sync, f"holding.{rank}")
        time.sleep(10 * WAIT_S)
        return 1
    if mode == "wait":
        segment = lease.enqueue(nbytes)
        _mark(sync, f"queued.{rank}")
        outcome = lease.await_turn(segment, 10 * WAIT_S)
        _log(log, rank=rank, event="begin", outcome=outcome, start=segment[0])
        lease.release(Held(outcome, segment))
        return 0
    _mark(sync, f"ready.{rank}")
    _wait_for(sync, [f"ready.{r}" for r in everyone], "start")
    for turn in range(TURNS):
        segment = lease.enqueue(nbytes)
        _mark(sync, f"queued.{rank}.{turn}")
        outcome = lease.await_turn(segment, WAIT_S)
        _log(log, rank=rank, turn=turn, event="begin", outcome=outcome, start=segment[0])
        if mode == "rounds" or turn == 0:
            # Everyone who has not had this turn is in the queue before the
            # holder re-enters it: what co-located groups do by themselves,
            # fetching the same leaf at the same time.  (A race starts so
            # too, and is on its own from there.)
            _wait_for(sync, [f"queued.{r}.{turn}" for r in everyone], f"turn {turn}")
        if mode == "rounds" and turn + 1 < TURNS:
            # And whoever had this turn before the holder is back in the queue
            # before the holder joins it: a round's order is then the queue's
            # doing, not that of the process the host ran first after a release
            # (six workers' load held one back past its successor's whole turn).
            had = [r for r in everyone if os.path.exists(os.path.join(sync, f"ended.{r}.{turn}"))]
            _wait_for(sync, [f"queued.{r}.{turn + 1}" for r in had], f"turn {turn}'s earlier holders")
        _log(log, rank=rank, turn=turn, event="end")
        _mark(sync, f"ended.{rank}.{turn}")
        lease.release(Held(outcome, segment))
    return 0


def start(mode: str, lease_path, sync, rank: int, nbytes: int = 1000) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker", mode, str(lease_path), str(sync), str(rank), str(nbytes)]
    )


def reap(procs) -> None:
    for p in procs:
        p.kill()
        p.wait()


def log_of(sync) -> list:
    with open(os.path.join(sync, "log.jsonl"), encoding="utf-8") as f:
        return [json.loads(line) for line in f]


def run_turns(mode: str, tmp_path) -> list:
    procs = [start(mode, tmp_path / "lease", tmp_path, r) for r in range(PROCS)]
    try:
        assert [p.wait(timeout=2 * WAIT_S) for p in procs] == [0] * PROCS
    finally:
        reap(procs)
    return log_of(tmp_path)


# ---------------------------------------------------------------------------
# Between four real processes.
# ---------------------------------------------------------------------------


def test_no_two_holders_at_a_time_across_four_processes(tmp_path) -> None:
    """Four processes race for the lease as fast as they can: in the shared
    log every ``begin`` is followed by the same holder's ``end``."""
    log = run_turns("race", tmp_path)
    assert len(log) == 2 * PROCS * TURNS
    for begin, end in zip(log[0::2], log[1::2]):
        assert (begin["event"], end["event"]) == ("begin", "end")
        assert (begin["rank"], begin["turn"]) == (end["rank"], end["turn"])
    assert {r["outcome"] for r in log[0::2]} <= {"free", "waited"}
    assert sum(r["outcome"] == "waited" for r in log[0::2]) >= PROCS - 1  # the first turn was contended


def test_turns_are_granted_in_the_order_of_arrival(tmp_path) -> None:
    """First come, first served: a place in the queue is a segment's start,
    and the turns come in ascending starts for as long as the queue is never
    empty (it restarts at the bottom when it is)."""
    begins = [r for r in run_turns("rounds", tmp_path) if r["event"] == "begin"]
    starts = [r["start"] for r in begins]
    # With the holder waiting for everyone to queue, the queue never empties.
    assert starts == sorted(starts) and len(set(starts)) == len(starts)


def test_nobody_has_turn_k_plus_1_before_everyone_had_turn_k(tmp_path) -> None:
    """Round robin: who has just fetched goes to the back of the queue."""
    begins = [r for r in run_turns("rounds", tmp_path) if r["event"] == "begin"]
    assert len(begins) == PROCS * TURNS
    for k in range(TURNS):
        batch = begins[k * PROCS : (k + 1) * PROCS]
        assert sorted(r["rank"] for r in batch) == list(range(PROCS)), (k, batch)
        assert {r["turn"] for r in batch} == {k}
    # And after the first round the order repeats.
    order = [r["rank"] for r in begins[:PROCS]]
    assert [r["rank"] for r in begins] == order * TURNS


def test_sigkill_of_the_holder_frees_the_next_waiter_at_once(tmp_path) -> None:
    """The holder's segment is ten terabytes long, so the waiter's bound is
    half a day: only the kernel dropping the dead holder's lock lets the
    waiter through, and it goes through as ``waited``, not ``timeout``."""
    lease = tmp_path / "lease"
    holder = start("hold", lease, tmp_path, 0, nbytes=10**13)
    waiters = []
    try:
        _wait_for(tmp_path, ["holding.0"], "holder")
        waiters = [start("wait", lease, tmp_path, r) for r in (1, 2)]
        _wait_for(tmp_path, ["queued.1", "queued.2"], "waiters")
        assert [w.poll() for w in waiters] == [None, None]  # both behind the holder
        holder.send_signal(signal.SIGKILL)
        assert [w.wait(timeout=WAIT_S) for w in waiters] == [0, 0]
    finally:
        reap([holder, *waiters])
    log = log_of(tmp_path)
    assert [(r["rank"], r["outcome"]) for r in log[:1]] == [(0, "free")]
    assert sorted(r["outcome"] for r in log[1:]) == ["waited", "waited"]
    assert [r["start"] for r in log] == sorted(r["start"] for r in log)  # in the order they came


def stopped_holder(tmp_path, nbytes: int = 1) -> subprocess.Popen:
    holder = start("hold", tmp_path / "lease", tmp_path, 0, nbytes=nbytes)
    _wait_for(tmp_path, ["holding.0"], "holder")
    holder.send_signal(signal.SIGSTOP)
    return holder


def test_a_stopped_holder_costs_each_waiter_one_bounded_wait(tmp_path) -> None:
    """SIGSTOP: alive, holding, not moving.  Each waiter's first turn ends in
    ``timeout``; it then looks past the wedged segment, so its later turns do
    not wait for it again — and they still take turns with each other."""
    holder = stopped_holder(tmp_path)
    outcomes = {1: [], 2: []}

    def waiter(rank: int) -> None:
        lease = D2HLease(str(tmp_path / "lease"))
        for _turn in range(4):
            held = lease.acquire(1, WAIT_S)
            outcomes[rank].append(held.outcome)
            lease.release(held)

    try:
        threads = [threading.Thread(target=waiter, args=(r,)) for r in outcomes]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=WAIT_S)
        assert not any(t.is_alive() for t in threads)
    finally:
        reap([holder])
    for got in outcomes.values():
        assert got[0] == "timeout" and got.count("timeout") == 1, outcomes
        assert set(got[1:]) <= {"free", "waited"}


def test_the_bound_is_the_bytes_ahead_over_the_floor_rate(tmp_path, monkeypatch) -> None:
    """What a waiter will wait is derived from what it waits behind: behind
    two hundred bytes at a floor of a hundred bytes a second it sleeps past
    one deadline, of two seconds and the slack; the clock is the test's."""
    monkeypatch.setattr(d2h_lease, "FLOOR_BYTES_PER_S", 100.0)
    now = [1000.0]
    slept = []

    def sleep(seconds: float) -> None:
        slept.append(seconds)
        now[0] += 0.25

    monkeypatch.setattr(d2h_lease, "time", types.SimpleNamespace(monotonic=lambda: now[0], sleep=sleep))
    path = str(tmp_path / "lease")
    first, second, third = D2HLease(path), D2HLease(path), D2HLease(path)
    a, b = first.enqueue(150), second.enqueue(50)
    assert (a, b) == ((1, 150), (151, 50))
    segment = third.enqueue(7)
    assert third.await_turn(segment, 1e9) == "timeout"
    assert len(slept) == round((d2h_lease.SLACK_S + 200 / 100.0) / 0.25)
    # The caller's cap (a share of the fetch's own deadline) is the shorter bound.
    slept.clear()
    fourth = D2HLease(path)
    last = fourth.enqueue(7)
    assert fourth.await_turn(last, 0.5) == "timeout" and len(slept) == 2
    # Gone segments are forgotten, and an empty queue restarts at the bottom.
    assert third._wedged == {a, b}
    for lease, seg in ((first, a), (second, b), (third, segment), (fourth, last)):
        lease.release(Held("free", seg))
    assert third.acquire(9, 1.0) == Held("free", (1, 9)) and third._wedged == set()


def test_a_forked_child_opens_a_lease_of_its_own(tmp_path) -> None:
    """A shared open file description would share the parent's locks: the
    child would walk through a lease its parent holds."""
    lease = D2HLease(str(tmp_path / "lease"))
    held = lease.acquire(10, 1.0)
    assert held.outcome == "free"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)  # fork under pytest's threads: the child only locks and exits
        pid = os.fork()
    if pid == 0:  # the child: behind its parent, so its short bound expires
        try:
            os._exit(0 if lease.acquire(10, 0.01).outcome == "timeout" else 1)
        finally:
            os._exit(2)
    assert os.waitpid(pid, 0)[1] == 0
    lease.release(held)
    assert D2HLease(str(tmp_path / "lease")).acquire(10, 1.0).outcome == "free"


# ---------------------------------------------------------------------------
# device_get_into: which fetches take the lease, and what it counts.
# ---------------------------------------------------------------------------


def pairs_of(n: int):
    import numpy as np

    srcs = [np.arange(100, dtype=np.float32) + i for i in range(n)]
    return srcs, [(s, np.zeros_like(s)) for s in srcs]


def landed(srcs, pairs) -> bool:
    return all((dst == src).all() for src, (_s, dst) in zip(srcs, pairs))


def test_a_stopped_holder_is_counted_and_never_fails_the_fetch(tmp_path, monkeypatch) -> None:
    from torchft_tpu import futures

    monkeypatch.setattr(d2h_lease, "_HOST_LEASE", D2HLease(str(tmp_path / "lease")))
    monkeypatch.setattr(futures, "_fetch_is_d2h", lambda src: True)
    holder = stopped_holder(tmp_path)
    srcs, pairs = pairs_of(5)
    counts: dict = {}
    waits = []

    def sub(name: str, **fields):
        span = types.SimpleNamespace(fields=fields)
        if name == "d2h_lease_wait":
            waits.append(span)
        return futures.nullcontext(span)

    try:
        futures.device_get_into(pairs, timeout=WAIT_S, lease_counts=counts, sub=sub)
    finally:
        reap([holder])
    assert landed(srcs, pairs)
    assert counts == {"lease_fetches": 5, "lease_contended": 1, "lease_timeouts": 1}
    assert [w.fields for w in waits] == [{"bytes": 400, "contended": True}] + [{"bytes": 400, "contended": False}] * 4


def test_an_unopenable_lease_path_fetches_anyway_and_is_counted(tmp_path, monkeypatch) -> None:
    from torchft_tpu import futures

    (tmp_path / "a-file").write_text("")
    monkeypatch.setattr(d2h_lease, "_HOST_LEASE", D2HLease(str(tmp_path / "a-file" / "lease")))
    monkeypatch.setattr(futures, "_fetch_is_d2h", lambda src: True)
    srcs, pairs = pairs_of(3)
    counts: dict = {}
    futures.device_get_into(pairs, timeout=WAIT_S, lease_counts=counts)
    assert landed(srcs, pairs) and counts == {"lease_unavailable": 3}


def test_host_resident_and_cpu_backend_sources_never_touch_the_lease(monkeypatch) -> None:
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu import futures

    def never():
        raise AssertionError("a source in host memory asked for the lease")

    monkeypatch.setattr(d2h_lease, "host_lease", never)
    srcs = [np.arange(50, dtype=np.float32), jnp.arange(50, dtype=jnp.float32), jnp.ones((5, 10))]
    pairs = [(s, np.zeros(s.shape, np.float32)) for s in srcs]
    counts: dict = {}
    names = []
    futures.device_get_into(pairs, timeout=WAIT_S, lease_counts=counts, sub=lambda name, **f: (names.append(name), futures.nullcontext())[1])
    assert all((dst == np.asarray(src)).all() for src, dst in pairs)
    assert counts == {} and names == ["d2h_ready", "d2h_fetch", "d2h_copy"] * 3


def test_only_a_leaf_in_an_accelerators_own_memory_is_a_d2h_fetch() -> None:
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.futures import _fetch_is_d2h

    class Leaf:
        def __init__(self, platform: str, memory_kind: str) -> None:
            self._devices = {type("Device", (), {"platform": platform})()}
            self.sharding = type("Sharding", (), {"memory_kind": memory_kind})()

        def devices(self):
            return self._devices

    assert _fetch_is_d2h(Leaf("tpu", "device")) and _fetch_is_d2h(Leaf("gpu", "device"))
    assert not _fetch_is_d2h(Leaf("tpu", "pinned_host"))
    assert not _fetch_is_d2h(Leaf("cpu", "device"))
    assert not _fetch_is_d2h(np.ones(3)) and not _fetch_is_d2h(jnp.ones(3)) and not _fetch_is_d2h(4.0)


def test_the_default_lease_is_one_file_in_the_temporary_directory(tmp_path, monkeypatch) -> None:
    """Found without configuration: every process that shares the machine's
    temporary directory meets at the same file, which is never written."""
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert d2h_lease.default_path() == str(tmp_path / "tpuft-d2h.lease")
    lease, other = D2HLease(), D2HLease()
    held = lease.acquire(4096, 1.0)
    assert held == Held("free", (1, 4096))
    assert other.enqueue(1) == (4097, 1)
    assert os.path.getsize(tmp_path / "tpuft-d2h.lease") == 0
    assert os.stat(tmp_path / "tpuft-d2h.lease").st_mode & 0o777 == 0o666
    assert d2h_lease.host_lease() is d2h_lease.host_lease()


if __name__ == "__main__":
    assert sys.argv[1] == "--worker"
    sys.exit(worker(sys.argv[2], sys.argv[3], sys.argv[4], int(sys.argv[5]), int(sys.argv[6])))
