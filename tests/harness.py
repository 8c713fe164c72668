"""Fault-injection integration harness.

Reference parity: torchft/manager_integ_test.py:55-155 — a FailureInjector
raises InjectedFailure inside the train loop at scripted steps, and a Runner
re-runs each replica group (as a thread) up to ``attempts`` times, simulating
a torchelastic restart.  Replica groups are threads in one process, each
thread stack being one full replica: real native Lighthouse + Manager
servers, real TCP collective over localhost.
"""

from __future__ import annotations

import logging
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set

logger = logging.getLogger(__name__)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def script_env(extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """This process's environment for a child that runs a file of this
    directory as a script (the cells' workers): its own ``sys.path[0]`` is
    ``tests/``, so the repo rides in PYTHONPATH."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH")) if p
    )
    env.update(extra or {})
    return env


def fd_count() -> int:
    """Open file descriptors of this process (-1 where /proc is absent)."""
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return -1


class InjectedFailure(Exception):
    pass


class FailureInjector:
    """Scripts failures at (rank, step) points
    (reference: torchft/manager_integ_test.py:55-73)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._failures: Set[tuple] = set()
        self.count = 0

    def fail_at(self, rank: int, step: int) -> "FailureInjector":
        with self._lock:
            self._failures.add((rank, step))
        return self

    def check(self, rank: int, step: int) -> None:
        with self._lock:
            key = (rank, step)
            if key in self._failures:
                self._failures.remove(key)
                self.count += 1
                logger.info("injecting failure at %s", key)
                raise InjectedFailure(f"injected failure rank={rank} step={step}")


@dataclass
class Runner:
    """Runs one replica group with restart-on-failure
    (reference: Runner, torchft/manager_integ_test.py:87-155).

    With ``world_size > 1`` each attempt runs all local ranks as threads
    sharing one rendezvous store (rank 0's Manager spawns the group's
    ManagerServer; the others dial it through the store), and a failure in
    any rank restarts the whole group — the torchelastic semantics the
    reference simulates (torchft/manager_integ_test.py:100-141)."""

    replica_id: int
    lighthouse_address: str
    failure_injector: FailureInjector
    train_loop: Callable[..., object]
    num_replicas: int = 2
    world_size: int = 1
    attempts: int = 3
    train_loop_args: Dict[str, Any] = field(default_factory=dict)

    def _attempt(self) -> List[object]:
        if self.world_size == 1:
            return [self.train_loop(self, rank=0)]

        from torchft_tpu._native import StoreServer

        # Fresh store per attempt: a restarted group must not see the dead
        # incarnation's manager_addr/replica_id keys.
        store = StoreServer(bind="127.0.0.1:0")
        try:
            with ThreadPoolExecutor(
                max_workers=self.world_size,
                thread_name_prefix=f"replica{self.replica_id}",
            ) as pool:
                futures = [
                    pool.submit(
                        self.train_loop,
                        self,
                        rank=rank,
                        store_addr=store.address(),
                    )
                    for rank in range(self.world_size)
                ]
                return [f.result(timeout=120) for f in futures]
        finally:
            store.shutdown()

    def run_replica(self) -> List[object]:
        for i in range(self.attempts):
            try:
                logger.info("starting replica %s attempt %s", self.replica_id, i)
                return self._attempt()
            except InjectedFailure:
                logger.info("replica %s died; restarting", self.replica_id)
                continue
        raise RuntimeError(f"replica {self.replica_id} exceeded {self.attempts} attempts")


def run_replicas(runners: List[Runner]) -> List[List[object]]:
    """Runs all replica groups concurrently, propagating the first error."""
    with ThreadPoolExecutor(max_workers=len(runners),
                            thread_name_prefix="replica") as pool:
        futures = [pool.submit(r.run_replica) for r in runners]
        return [f.result(timeout=120) for f in futures]
