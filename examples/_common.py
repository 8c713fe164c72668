"""Shared mechanics for the example trainers.

Only the non-instructive plumbing lives here (virtual devices, compile
cache, Manager wiring, the FINAL digest); each example keeps its own train
loop inline so it still reads as a tutorial for its parallelism style.
"""

from __future__ import annotations

import hashlib
import os
import time
from typing import Any, Callable, Optional


def prepare_jax_env(virtual_devices: Optional[int] = None) -> None:
    """Applies the environment contract every example shares.  Call it
    BEFORE the first ``import jax``: JAX reads both settings at import.

    - ``virtual_devices``: simulate one multi-device slice per process on
      the CPU backend (demo only; real hardware drops this).
    - compile cache: placed by ``torchft_tpu.launch.export_compile_cache``
      (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``) so
      a restarted replica re-JITs from disk, shrinking the recovery window.

    The platform is JAX's own ``JAX_PLATFORMS``.  A chip belongs to one
    process: anything that runs several processes on one host sets
    ``JAX_PLATFORMS=cpu`` in the child environment, or gives each child its
    own chip (``Launcher(group_env=...)``, see ``chip_smoke.py``).
    """
    if virtual_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={virtual_devices}"
            ).strip()

    from torchft_tpu.launch import export_compile_cache

    export_compile_cache()


def replica_env() -> tuple:
    """(replica_group, num_replica_groups) from the launcher's env.

    Hot-spare mode: when the supervisor started this process as a SPARE
    (``TPUFT_SPARE_FILE`` set, no ``REPLICA_GROUP_ID``), finish the
    expensive initialization NOW and block until the supervisor assigns a
    replica group by writing the go-file.  Adoption then skips the
    process-spawn + runtime-init floor that dominates a cold restart's dead
    window (measured ~7 s of the ~7.5 s downtime on the CPU kill bench).

    What "initialization" may cover depends on the platform.  A CPU backend
    is not exclusive, so under ``JAX_PLATFORMS=cpu`` the spare brings it up
    while idle.  A chip belongs to one process: anywhere else the spare
    stops at the imports and takes the chip only once it owns a group —
    on a one-chip host that is after the group it replaces has died."""
    gid = os.environ.get("REPLICA_GROUP_ID")
    spare = os.environ.get("TPUFT_SPARE_FILE")
    if gid is None and spare:
        import jax

        if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
            jax.devices()  # backend init happens while idling, not after a death
            ready = "backend up"
        else:
            ready = "imports done, no chip taken"
        print(f"[spare] ready ({ready}), waiting at {spare}", flush=True)
        while not os.path.exists(spare):
            time.sleep(0.05)
        with open(spare) as f:
            gid = f.read().strip()
        os.environ["REPLICA_GROUP_ID"] = gid
        print(f"[spare] adopted replica group {gid}", flush=True)
    return (
        int(gid or 0),
        int(os.environ.get("NUM_REPLICA_GROUPS", 2)),
    )


def maybe_straggle(replica_group: int) -> float:
    """Fault injection for the straggler bench scenario: when the driver
    wrote ``<TPUFT_STRAGGLE_DIR>/straggle_<group>.json`` this step sleeps
    ``sleep_s`` extra, simulating a degraded-but-alive host (the failure
    mode no heartbeat timeout ever catches).  The notice is PID-pinned: a
    replacement incarnation adopting the same group id models a healthy
    spare host and must not inherit the slowness.  Returns the seconds
    slept (0 = no injection)."""
    d = os.environ.get("TPUFT_STRAGGLE_DIR")
    if not d:
        return 0.0
    import json

    path = os.path.join(d, f"straggle_{replica_group}.json")
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return 0.0
    pid = data.get("pid")
    # A notice MUST name a pid: a pid-less file matching every incarnation
    # would pin the slowness to each replacement forever, turning one slow
    # host into an unrecoverable slow group.
    if pid is None or int(pid) != os.getpid():
        return 0.0
    sleep_s = float(data.get("sleep_s", 0.0))
    if sleep_s > 0.0:
        time.sleep(sleep_s)
    return sleep_s


def make_manager(
    save: Callable[[], Any],
    load: Callable[[Any], None],
    replica_group: int,
    *,
    min_replicas: int = 1,
    timeout_s: float = 30.0,
    restore_sharding: Any = None,
) -> Any:
    """One-replica-group Manager with the examples' standard wiring:
    TCPCollective data plane + HTTP checkpoint transport (optionally with a
    sharding restorer for sharded-state healing), plus the cooperative-drain
    watcher (SIGTERM / supervisor notice file / opt-in GCE metadata poll) so
    a planned departure hands off instead of dying."""
    from datetime import timedelta

    from torchft_tpu import Manager, TCPCollective
    from torchft_tpu.checkpointing.http_transport import HTTPTransport

    manager = Manager(
        collective=TCPCollective(timeout=timeout_s),
        load_state_dict=load,
        state_dict=save,
        min_replica_size=min_replicas,
        timeout=timedelta(seconds=timeout_s),
        rank=0,
        world_size=1,
        replica_id=str(replica_group),
        checkpoint_transport=HTTPTransport(
            timeout=timeout_s, restore_sharding=restore_sharding
        ),
    )
    manager.attach_drain_watcher()
    return manager


class TrainGate:
    """Decides when an example train loop is done.

    Three exits, in priority order:

    - **drain** — a cooperative-departure notice arrived (the Manager's
      DrainWatcher fired): finish the in-flight step and leave NOW; the
      supervisor already pre-warmed a replacement.
    - **merged final** (``require_merged`` > 0) — don't stop at the step
      budget until a committed step at-or-after it ran with at least that
      many participating groups.  This replaces the fixed-step-budget race
      in the kill tests with a deterministic criterion: a survivor keeps
      stepping (solo) until the healed replacement merges back, so both
      groups provably finish the same merged step with identical state.
    - **step budget** — plain ``current_step() >= steps`` otherwise, with
      ``steps_cap`` as a runaway bound when the merged criterion can never
      be met (e.g. the peer is gone for good).
    """

    def __init__(
        self, manager: Any, steps: int, *, require_merged: int = 0, steps_cap: int = 0
    ) -> None:
        self._manager = manager
        self._steps = steps
        self._require_merged = require_merged
        self._steps_cap = steps_cap
        self._last_merged = 0

    def should_continue(self) -> bool:
        if self._manager.drain_requested():
            return False
        step = self._manager.current_step()
        if self._steps_cap and step >= self._steps_cap:
            return False
        if step < self._steps:
            return True
        return self._require_merged > 0 and self._last_merged < self._require_merged

    def note_commit(self, committed: bool) -> None:
        """Record the last commit's participation (call once per step)."""
        self._last_merged = self._manager.num_participants() if committed else 0

    def drained(self) -> bool:
        return self._manager.drain_requested()

    def finish(self, replica_group: int) -> bool:
        """Drain epilogue: completes a requested drain and prints the exit
        marker.  Returns True when this was a drain exit (the caller skips
        its FINAL print — the departing params are donor state, not the
        run's converged result)."""
        if not self.drained():
            return False
        self._manager.complete_drain()
        print(
            f"[group {replica_group}] DRAIN exit step="
            f"{self._manager.current_step()}",
            flush=True,
        )
        return True


def params_digest(params: Any) -> str:
    """Order-stable sha256 over every parameter leaf — the cross-group
    convergence evidence each example prints at FINAL."""
    import jax
    import numpy as np

    digest = hashlib.sha256()
    leaves = sorted(
        jax.tree_util.tree_leaves_with_path(params),
        key=lambda kv: jax.tree_util.keystr(kv[0]),
    )
    for _, leaf in leaves:
        digest.update(np.asarray(leaf).tobytes())
    return digest.hexdigest()
