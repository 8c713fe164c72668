"""Replica-group launcher + restart supervisor.

Reference parity: torchft/torchx.py:11-80 — the reference ships a TorchX
component that launches N single-node replica groups with per-group env
(``REPLICA_GROUP_ID``, ``NUM_REPLICA_GROUPS``, ``TORCHFT_LIGHTHOUSE``) and
relies on torchelastic's ``--max_restarts`` to resurrect a killed group so it
can heal live from a peer.  TorchX/torchelastic don't exist here, so the
supervisor itself is part of the framework: ``Launcher`` owns the replica
group subprocesses, restarts the ones that die (each restart is a fresh
process that re-rendezvouses via the Lighthouse and heals from a healthy
peer), and optionally embeds the native Lighthouse server in-process.

CLI::

    python -m torchft_tpu.launch --groups 2 --max-restarts 3 -- \
        python examples/train_ddp.py --steps 20

Programmatic (this is what ``benchmark/jobs/steady.py`` drives)::

    with Launcher([sys.executable, "train.py"], num_groups=2,
                  lighthouse="embed", log_dir=workdir) as launcher:
        while launcher.running():
            time.sleep(0.25)
            launcher.supervise_once()
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, MutableMapping, Optional

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A group that exits in under this many seconds is treated as crash-looping
# and restarted with exponential backoff rather than immediately.
_MIN_UPTIME_S = 5.0

__all__ = ["Launcher", "export_compile_cache", "fetch_alerts", "main"]


def export_compile_cache(env: Optional[MutableMapping[str, str]] = None) -> str:
    """Places JAX's persistent compile cache from OUTSIDE the program and
    returns the directory: where ``JAX_COMPILATION_CACHE_DIR`` is already
    set, that holds; otherwise ``<repo>/.jax_cache`` (git-ignored) is
    exported into ``env`` (default: this process's environment) under the
    same standard variable, so every child inherits it.  The path is part of
    the cache's key — a directory that moves never hits — and a restarted
    group re-JITs from disk instead of recompiling.

    Beside the place go two settings of the key, each unless the environment
    already has it.  ``JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY=1``: a
    program's metadata — the ``jax.named_scope`` names of the model's parts,
    which a profile's device time is booked by (``obs/opmap.py``) — is part
    of what the key hashes; without it a program whose scopes changed is a
    hit on an executable that carries the old ones.
    ``JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0``: an operation's location holds its
    name stack and no source frame, so the key holds no file or line and a
    change that only moves lines recompiles nothing (a compiled program's
    metadata then names no source line either).

    JAX reads the variables when it is imported, so a process that wants the
    cache for ITSELF calls this before its first ``import jax``; no code of
    this repo sets them through ``jax.config``."""
    env = os.environ if env is None else env
    env.setdefault("JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY", "1")
    env.setdefault("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", "0")
    return env.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(_REPO_ROOT, ".jax_cache")
    )


def fetch_alerts(http_address: str, timeout: float = 2.0):
    """Fetches the lighthouse's straggler-sentinel alert feed
    (``GET /alerts.json``) from a ``host:port`` HTTP address.  Returns the
    parsed dict, or None on any failure — callers poll inside supervision
    or measurement loops and must treat a missed fetch as 'retry later',
    never as an error.  Dials 127.0.0.1 with the advertised port: embedded
    lighthouses bind loopback, and the advertised hostname may not resolve
    inside sandboxes."""
    import json
    import urllib.request

    if not http_address:
        return None
    port = http_address.rsplit(":", 1)[-1]
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/alerts.json", timeout=timeout
        ) as resp:
            return json.loads(resp.read().decode())
    except Exception:  # noqa: BLE001
        return None


@dataclass
class _Spare:
    """A hot-spare process: spawned and initialized as far as it may be
    while idle (see ``Launcher``'s ``spares``), blocked in the example
    harness's ``replica_env`` until the supervisor writes its go-file with
    a replica-group id."""

    proc: subprocess.Popen
    log: Optional[object]
    go_path: str
    sid: int
    spawned_at: float = 0.0


@dataclass
class _Draining:
    """A donor process finishing its cooperative departure: detached from
    its group slot (the replacement already owns it), reaped separately,
    escalated to SIGTERM/SIGKILL past its deadline."""

    proc: subprocess.Popen
    log: Optional[object]
    group: int
    deadline: float  # monotonic; escalate past this
    notice_path: str
    started: float = 0.0
    term_sent: bool = False


@dataclass
class _Group:
    proc: Optional[subprocess.Popen] = None
    log: Optional[object] = None
    restarts: int = 0
    held: bool = False  # killed on purpose; don't auto-restart until spawn()
    exited_clean: bool = False
    env: Dict[str, str] = field(default_factory=dict)
    spawned_at: float = 0.0
    # Crash-loop brake: a group that dies almost immediately (bad argv,
    # import error) is restarted with exponential backoff instead of at the
    # supervisor's poll rate (~4/s unbounded without this).
    backoff_until: float = 0.0
    backoff_s: float = 0.0
    # Set when the death was OUR kill() (fault injection): exempt from the
    # brake — the uptime check targets spontaneous fast-exits only.
    killed_by_us: bool = False
    # This incarnation's death was already reported to the lighthouse; the
    # supervisor polls dead groups every pass (backoff / exhausted budget)
    # and must not repeat the (possibly blocking, for external
    # lighthouses) evict RPC each tick.
    evicted: bool = False


class Launcher:
    """Launches and supervises ``num_groups`` replica-group processes.

    Args:
        cmd: argv of one replica group (e.g. ``[sys.executable, "train.py"]``).
        num_groups: number of replica groups (``NUM_REPLICA_GROUPS``).
        lighthouse: ``"embed"`` to run the native Lighthouse in-process,
            an ``"host:port"`` address to use an external one — or a
            comma-separated list of them (an HA lighthouse replica set,
            docs/wire.md "HA lighthouse"): the children's managers and
            this supervisor's evict/drain calls fail over across the list
            and follow leader redirects — or None to inherit
            ``TPUFT_LIGHTHOUSE`` from the environment.
        max_restarts: per-group restart budget (None = unlimited), the
            ``--max_restarts`` analogue (torchft/torchx.py:54).
        min_replicas: embedded Lighthouse quorum floor.
        join_timeout_ms: embedded Lighthouse straggler wait.
        log_dir: per-group logs land in ``<log_dir>/g<i>.log`` (append);
            None inherits this process's stdout/stderr.
        env: extra environment for every group (overrides inherited; a None
            value unsets the variable).  The groups also inherit the compile
            cache location (:func:`export_compile_cache`).
        group_env: extra environment per group id, applied over ``env`` on
            every (re)spawn of that group — the place for what one group
            owns alone, e.g. the TPU runtime's visibility settings that
            confine a child to its own chip (one process per chip).  A
            group carrying overrides never adopts a hot spare.
        cwd: working directory for the groups.
        spares: hot-spare pool size.  Spares are spawned WITHOUT a
            ``REPLICA_GROUP_ID`` and idle initialized (imports done; the JAX
            backend too where it is not exclusive, i.e. under
            ``JAX_PLATFORMS=cpu`` — a spare never takes a chip its live
            group needs) behind ``TPUFT_SPARE_FILE``; when a group dies,
            ``spawn`` hands the dead group's id to a ready spare by writing
            that file — adoption skips the process-spawn + runtime-init
            floor that dominates cold-restart downtime (kill-bench
            ``victim_restart_s``), and the pool is refilled in the
            background.  Requires the command to resolve its group id via
            the ``replica_env`` contract (``examples/_common.py``).
        straggler_auto_drain: act on the lighthouse's straggler-sentinel
            alerts — ``supervise_once`` polls ``GET /alerts.json`` (embedded
            lighthouse only) and rotates a confirmed straggler out through
            :meth:`drain`, i.e. the PR-1 cooperative handoff: a replacement
            is pre-warmed (hot spare when available) while the slow donor
            finishes its step and exits, so a degraded-but-alive host costs
            one handoff gap instead of dragging every synchronous step for
            the rest of the job.  Default: ``TPUFT_STRAGGLER_AUTO_DRAIN=1``
            in the environment.
    """

    def __init__(
        self,
        cmd: List[str],
        num_groups: int,
        *,
        lighthouse: Optional[str] = None,
        max_restarts: Optional[int] = None,
        min_replicas: int = 1,
        join_timeout_ms: int = 2000,
        log_dir: Optional[str] = None,
        env: Optional[Dict[str, Optional[str]]] = None,
        group_env: Optional[Dict[int, Dict[str, str]]] = None,
        cwd: Optional[str] = None,
        spares: int = 0,
        straggler_auto_drain: Optional[bool] = None,
        incident_watcher: bool = False,
        watcher_act: bool = False,
    ) -> None:
        self._cmd = list(cmd)
        self._num_groups = num_groups
        self._max_restarts = max_restarts
        self._log_dir = log_dir
        self._cwd = cwd
        self._groups: Dict[int, _Group] = {
            i: _Group(env=dict((group_env or {}).get(i, {})))
            for i in range(num_groups)
        }
        self._embedded = None
        self._spares_target = max(0, spares)
        self._spares: List[_Spare] = []
        self._spare_seq = 0
        self._spare_fast_deaths = 0
        self._spare_pool_disabled = False
        self._spare_dir: Optional[str] = None
        self._spare_dir_created = False
        self._evict_client = None  # lazy wire client for external lighthouses
        self._draining: List[_Draining] = []
        self._drain_dir: Optional[str] = None
        self._drain_dir_created = False
        if straggler_auto_drain is None:
            straggler_auto_drain = (
                os.environ.get("TPUFT_STRAGGLER_AUTO_DRAIN", "") == "1"
            )
        self._straggler_auto_drain = straggler_auto_drain
        self._sentinel_last_poll = 0.0
        self._handled_alerts: set = set()
        # IncidentWatcher (docs/observability.md "IncidentWatcher"): polls
        # the incident feed, captures bundles, journals flap-guarded
        # remediation recommendations.  Dry-run unless watcher_act, which
        # gates the cooperative-drain action.
        self._incident_watcher_enabled = incident_watcher
        self._watcher_act = watcher_act
        self._watcher = None  # built lazily on the first supervise pass

        lighthouse_http = ""
        if lighthouse == "embed":
            from torchft_tpu._native import LighthouseServer

            self._embedded = LighthouseServer(
                bind="127.0.0.1:0",
                min_replicas=min_replicas,
                join_timeout_ms=join_timeout_ms,
            )
            lighthouse_addr = self._embedded.address()
            lighthouse_http = self._embedded.http_address()
        elif lighthouse is not None:
            lighthouse_addr = lighthouse
        else:
            lighthouse_addr = os.environ.get("TPUFT_LIGHTHOUSE", "")

        base = dict(os.environ)
        for k, v in (env or {}).items():
            if v is None:
                base.pop(k, None)
            else:
                base[k] = v
        base.update(
            {
                "NUM_REPLICA_GROUPS": str(num_groups),
                "MASTER_ADDR": base.get("MASTER_ADDR", "localhost"),
            }
        )
        if lighthouse_addr:
            base["TPUFT_LIGHTHOUSE"] = lighthouse_addr
        export_compile_cache(base)
        # Cooperative-drain channel: every child (groups AND spares, whose
        # group id resolves at adoption) watches <drain_dir>/drain_<gid>.json
        # through its DrainWatcher; the supervisor's drain() writes it.
        if log_dir is not None:
            os.makedirs(log_dir, exist_ok=True)
            self._drain_dir = log_dir
        else:
            import tempfile

            self._drain_dir = tempfile.mkdtemp(prefix="tpuft_drain_")
            self._drain_dir_created = True
        base["TPUFT_DRAIN_DIR"] = self._drain_dir
        # Children only honor PID-PINNED notices (written by drain()); a
        # pid-less file is an OPERATOR request addressed to this
        # supervisor, which re-issues it through drain() so the departing
        # group gets a replacement (a child consuming it directly would
        # exit clean with nobody taking over).
        base["TPUFT_DRAIN_SUPERVISED"] = "1"
        self._base_env = base
        self.lighthouse_address = lighthouse_addr
        # Dashboard/metrics HTTP address of the embedded lighthouse (empty
        # for external ones): the sentinel poll and ops tooling read
        # /metrics and /alerts.json here.
        self.lighthouse_http_address = lighthouse_http
        from torchft_tpu.metrics import MetricsLogger

        self._metrics = MetricsLogger(base.get("TPUFT_METRICS_PATH"), "launcher")

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "Launcher":
        for i in range(self._num_groups):
            self.spawn(i)
        for _ in range(self._spares_target):
            self._spawn_spare()
        return self

    # -- hot spares ----------------------------------------------------------

    def _spawn_spare(self) -> None:
        if self._spare_pool_disabled:
            return
        if self._spare_dir is None:
            import tempfile

            if self._log_dir is not None:
                self._spare_dir = self._log_dir
                os.makedirs(self._spare_dir, exist_ok=True)
            else:
                self._spare_dir = tempfile.mkdtemp(prefix="tpuft_spares_")
                self._spare_dir_created = True
        sid = self._spare_seq
        self._spare_seq += 1
        go_path = os.path.join(self._spare_dir, f"spare_{sid}.go")
        env = dict(self._base_env)
        env.pop("REPLICA_GROUP_ID", None)
        env["TPUFT_SPARE_FILE"] = go_path
        stdout = stderr = None
        log = None
        if self._log_dir is not None:
            log = open(os.path.join(self._log_dir, f"spare_{sid}.log"), "ab")
            stdout, stderr = log, subprocess.STDOUT
        proc = subprocess.Popen(
            self._cmd, env=env, stdout=stdout, stderr=stderr, cwd=self._cwd
        )
        self._spares.append(
            _Spare(
                proc=proc, log=log, go_path=go_path, sid=sid,
                spawned_at=time.monotonic(),
            )
        )

    def _note_spare_death(self, spare: _Spare, refill: bool = True) -> None:
        """Bookkeeping for a dead spare: close its log, apply the
        crash-loop brake (same discipline as groups: only FAST deaths
        count, a healthy-uptime death resets the streak), refill."""
        if spare.log is not None:
            spare.log.close()
        if time.monotonic() - spare.spawned_at < _MIN_UPTIME_S:
            self._spare_fast_deaths += 1
        else:
            self._spare_fast_deaths = 0
        if self._spare_fast_deaths > 3:
            self._spare_pool_disabled = True
            logger.error(
                "spare %d died fast (exit %s); pool disabled after repeated "
                "immediate deaths", spare.sid, spare.proc.poll(),
            )
            return
        logger.warning(
            "spare %d died (exit %s); respawning", spare.sid, spare.proc.poll()
        )
        if refill:
            self._spawn_spare()

    def _take_ready_spare(self) -> Optional[_Spare]:
        while self._spares:
            spare = self._spares.pop(0)
            if spare.proc.poll() is None:
                return spare
            # A dead spare found here must still be replaced, or the pool
            # silently shrinks to zero and every later "hot" restart pays
            # full cold cost.
            self._note_spare_death(spare)
        return None

    def spare_count(self) -> int:
        """Live spares currently in the pool."""
        return sum(1 for s in self._spares if s.proc.poll() is None)

    def __enter__(self) -> "Launcher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def spawn(self, group: int) -> None:
        """(Re)starts one replica group; clears any kill-hold on it.

        With a hot-spare pool, a respawn ADOPTS a ready spare instead of
        forking a cold process: the spare already paid imports + JAX
        backend init and is blocked waiting for its group id."""
        g = self._groups[group]
        if g.proc is not None and g.proc.poll() is None:
            raise RuntimeError(f"group {group} is already running")
        g.held = False
        g.exited_clean = False
        g.backoff_until = 0.0  # explicit spawn overrides a pending backoff
        g.killed_by_us = False  # the new process's exits are its own
        g.evicted = False  # fresh incarnation: its death is unreported
        # Spares are spawned with the BASE env only — a group carrying
        # per-group overrides cannot adopt one (the drain handoff path
        # relies on the replacement seeing the same env as the donor), so
        # it falls through to a cold spawn that applies g.env.
        spare = (
            self._take_ready_spare() if self._spares_target and not g.env else None
        )
        if spare is not None:
            tmp = spare.go_path + ".tmp"
            with open(tmp, "w") as f:
                f.write(str(group))
            os.replace(tmp, spare.go_path)  # atomic: the spare reads whole ids
            if g.log is not None:
                g.log.close()
            g.proc = spare.proc
            g.log = spare.log  # the adopted process keeps its spare log file
            g.spawned_at = time.monotonic()
            logger.info(
                "group %d adopted hot spare %d (pid %d)", group, spare.sid,
                spare.proc.pid,
            )
            self._spawn_spare()  # refill the pool in the background
            return
        env = dict(self._base_env)
        env["REPLICA_GROUP_ID"] = str(group)
        env.update(g.env)
        stdout = stderr = None
        if self._log_dir is not None:
            if g.log is not None:
                g.log.close()  # respawns must not leak the old handle
            os.makedirs(self._log_dir, exist_ok=True)
            g.log = open(os.path.join(self._log_dir, f"g{group}.log"), "ab")
            stdout, stderr = g.log, subprocess.STDOUT
        g.proc = subprocess.Popen(
            self._cmd, env=env, stdout=stdout, stderr=stderr, cwd=self._cwd
        )
        g.spawned_at = time.monotonic()

    def _evict_from_lighthouse(self, group: int) -> None:
        """Supervisor-assisted failure notification: the lighthouse drops
        (and tombstones) the dead group's incarnations immediately, so the
        next quorum forms without spending join/heartbeat timeouts on a
        corpse whose heartbeat still looks fresh.  This is what makes
        hot-spare adoption fast — the spare rejoins within the old
        incarnation's heartbeat window.  Embedded lighthouses are called
        in-process; external ones over the wire (method 4, docs/wire.md)."""
        try:
            if self._embedded is not None:
                self._embedded.evict(str(group))
            elif self.lighthouse_address:
                from torchft_tpu._native import LighthouseClient

                if self._evict_client is None:
                    self._evict_client = LighthouseClient(self.lighthouse_address)
                self._evict_client.evict(str(group))
        except Exception:  # noqa: BLE001
            # Drop a possibly-broken cached connection so the next death
            # redials instead of failing forever on a stale client.
            self._evict_client = None
            logger.warning("lighthouse evict of group %d failed", group, exc_info=True)

    def _drain_at_lighthouse(self, group: int, deadline_ms: int) -> None:
        """Marks the group's EXISTING incarnations draining at the
        lighthouse, by family prefix.  Called from drain() BEFORE the
        replacement spawns (its fresh uuid must not be caught by the
        prefix), so quorum exclusion holds even when the child never
        integrated the drain contract (the cooperating Manager's own
        exact-id notice is then a harmless duplicate)."""
        try:
            if self._embedded is not None:
                self._embedded.drain(str(group), deadline_ms)
            elif self.lighthouse_address:
                from torchft_tpu._native import LighthouseClient

                if self._evict_client is None:
                    self._evict_client = LighthouseClient(self.lighthouse_address)
                self._evict_client.drain(str(group), deadline_ms)
        except Exception:  # noqa: BLE001
            self._evict_client = None
            logger.warning(
                "lighthouse drain of group %d failed", group, exc_info=True
            )

    def drain(self, group: int, deadline_s: float = 30.0) -> None:
        """Cooperative drain of one group: graceful handoff instead of a
        kill.  The moment the notice lands, a replacement is pre-warmed —
        a ready hot spare adopts the group id instantly, otherwise a cold
        replacement is spawned — so its initialization OVERLAPS the donor's
        final step; the donor (notified through its drain file) finishes
        the in-flight step, votes commit, tells the lighthouse it is
        leaving, and exits.  Past ``deadline_s`` a non-cooperative donor is
        escalated to SIGTERM, then SIGKILL (supervise_once drives the
        escalation and the reaping)."""
        g = self._groups[group]
        if g.proc is None or g.proc.poll() is not None:
            raise RuntimeError(f"group {group} is not running; nothing to drain")
        donor = g.proc
        donor_log = g.log
        # 1. The notice file the donor's DrainWatcher polls.  Pinned to the
        # donor's PID so the replacement (same group id, same file name)
        # cannot mistake the stale notice for its own.
        notice_path = os.path.join(self._drain_dir, f"drain_{group}.json")
        tmp = notice_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            import json

            json.dump(
                {
                    "deadline_ms": int(deadline_s * 1000),
                    "source": "supervisor",
                    "pid": donor.pid,
                },
                f,
            )
        os.replace(tmp, notice_path)  # atomic: the watcher reads whole notices
        # 1b. Lighthouse exclusion from the supervisor side too, BEFORE the
        # replacement exists: a donor that never wired a DrainWatcher would
        # otherwise keep joining quorums until the deadline escalation
        # kills it, stalling survivors on its stale heartbeat afterwards.
        self._drain_at_lighthouse(group, int(deadline_s * 1000))
        # 2. Detach the donor from the group slot and hand the id to a
        # replacement NOW — adoption overlaps the donor's last step.  The
        # lighthouse admits both briefly: the donor's incarnation is
        # marked draining (by its own Manager), the replacement's fresh
        # uuid joins normally.
        self._draining.append(
            _Draining(
                proc=donor,
                log=donor_log,
                group=group,
                deadline=time.monotonic() + deadline_s,
                notice_path=notice_path,
                started=time.monotonic(),
            )
        )
        g.proc = None
        g.log = None
        had_spare = self._spares_target > 0 and self.spare_count() > 0 and not g.env
        self.spawn(group)
        logger.info(
            "group %d draining (pid %d, deadline %.1fs); replacement %s",
            group, donor.pid, deadline_s,
            "adopted a hot spare" if had_spare else "cold-spawned",
        )
        self._metrics.emit(
            "drain_handoff",
            group=str(group),
            donor_pid=donor.pid,
            hot_spare=had_spare,
            deadline_ms=int(deadline_s * 1000),
        )

    def draining(self) -> List[int]:
        """Groups with a donor still finishing a cooperative departure."""
        return sorted({d.group for d in self._draining if d.proc.poll() is None})

    def kill(self, group: int, sig: int = signal.SIGKILL, hold: bool = True) -> None:
        """Kills one group (default SIGKILL — the fault-injection path).  With
        ``hold``, the supervisor won't restart it until ``spawn`` is called,
        so callers control the dead window."""
        g = self._groups[group]
        if g.proc is not None and g.proc.poll() is None:
            g.proc.send_signal(sig)
            g.proc.wait()
            # Only a death WE caused is exempt from the crash-loop brake; a
            # process found already dead crashed on its own.  Reset the
            # doubled delay too — the next incarnation's exits start fresh.
            g.killed_by_us = True
            g.backoff_s = 0.0
            g.evicted = True
            self._evict_from_lighthouse(group)
        g.held = hold

    def supervise_once(self) -> List[int]:
        """One supervision pass: restarts groups that died (non-held), unless
        they exited cleanly or exhausted max_restarts.  Returns the groups
        restarted this pass."""
        restarted: List[int] = []
        for i, g in self._groups.items():
            if g.proc is None or g.held or g.exited_clean:
                continue
            code = g.proc.poll()
            if code is None:
                continue
            if code == 0:
                g.exited_clean = True
                if not g.evicted:
                    g.evicted = True
                    self._evict_from_lighthouse(i)
                continue
            # Evict BEFORE the budget check: a group that exhausted
            # max_restarts is the most permanently dead of all — leaving
            # its heartbeat fresh would stall the survivors' quorum on it.
            # Once per incarnation: dead groups are re-polled every pass.
            if not g.evicted:
                g.evicted = True
                self._evict_from_lighthouse(i)
            if self._max_restarts is not None and g.restarts >= self._max_restarts:
                continue
            now = time.monotonic()
            if g.killed_by_us:
                g.killed_by_us = False
                g.backoff_until = 0.0
            elif g.backoff_until:
                if now < g.backoff_until:
                    continue
                g.backoff_until = 0.0  # backoff served; fall through to restart
            else:
                uptime = now - g.spawned_at
                if uptime < _MIN_UPTIME_S:
                    # Died almost immediately: double the delay before the
                    # next attempt (0.5s -> ... -> 30s cap) instead of
                    # crash-looping at the caller's poll rate.
                    g.backoff_s = min(30.0, max(0.5, g.backoff_s * 2))
                    g.backoff_until = now + g.backoff_s
                    logger.warning(
                        "group %d exited with code %s after %.2fs; backing off "
                        "%.1fs before restart %d",
                        i, code, uptime, g.backoff_s, g.restarts + 1,
                    )
                    continue
                g.backoff_s = 0.0  # healthy uptime resets the brake
            logger.info("group %d exited with code %s; restarting (restart %d)",
                        i, code, g.restarts + 1)
            g.restarts += 1
            self.spawn(i)
            restarted.append(i)
        # Operator drain requests: a pid-less drain_<g>.json in the drain
        # dir (e.g. `echo '{}' > <log-dir>/drain_1.json` against the CLI
        # launcher) is addressed to the SUPERVISOR — re-issue it through
        # drain(), which pre-warms the replacement and rewrites the file
        # pid-pinned for the donor.  Children skip pid-less files in
        # supervised mode, so there is no consume race.
        if self._drain_dir is not None:
            for i, g in self._groups.items():
                if g.proc is None or g.proc.poll() is not None:
                    continue
                path = os.path.join(self._drain_dir, f"drain_{i}.json")
                import json

                try:
                    with open(path, "rb") as f:
                        raw = f.read()
                except OSError:
                    # Absent — or consumed by its donor between any
                    # existence check and the open; draining the
                    # replacement over that race would be a spurious
                    # second handoff.
                    continue
                deadline_s = 30.0
                try:
                    data = json.loads(raw)
                    if data.get("pid") is not None:
                        continue  # already pid-pinned: in flight to its donor
                    deadline_s = float(data.get("deadline_ms", 30000)) / 1000.0
                except (ValueError, AttributeError):
                    pass  # a bare `touch` is a valid operator request
                logger.info("group %d: operator drain request via %s", i, path)
                self.drain(i, deadline_s=deadline_s)
        # Draining donors: reap the ones that finished their cooperative
        # exit; escalate SIGTERM -> SIGKILL past the drain deadline for a
        # child that never integrated the drain contract.
        for d in list(self._draining):
            code = d.proc.poll()
            now = time.monotonic()
            if code is not None:
                self._draining.remove(d)
                if d.log is not None:
                    d.log.close()
                try:
                    os.remove(d.notice_path)
                except OSError:
                    pass
                logger.info(
                    "group %d donor (pid %d) exited %s after %.2fs of drain",
                    d.group, d.proc.pid, code, now - d.started,
                )
                self._metrics.emit(
                    "drain_donor_exit",
                    group=str(d.group),
                    exit_code=code,
                    drain_s=round(now - d.started, 3),
                )
            elif now > d.deadline:
                if not d.term_sent:
                    logger.warning(
                        "group %d donor (pid %d) still alive past its drain "
                        "deadline; sending SIGTERM", d.group, d.proc.pid,
                    )
                    d.proc.send_signal(signal.SIGTERM)
                    d.term_sent = True
                    d.deadline = now + 5.0
                else:
                    logger.warning(
                        "group %d donor (pid %d) ignored SIGTERM; SIGKILL",
                        d.group, d.proc.pid,
                    )
                    d.proc.kill()
        # Spare pool upkeep: replace dead spares (repeated IMMEDIATE deaths
        # mean the command itself is broken — _note_spare_death's brake
        # disables the pool instead of crash-looping).
        for spare in list(self._spares):
            if spare.proc.poll() is None:
                continue
            self._spares.remove(spare)
            self._note_spare_death(spare)
        # Straggler sentinel: rotate confirmed-slow hosts out (throttled,
        # no-op unless straggler_auto_drain and an embedded lighthouse).
        self._sentinel_once()
        # IncidentWatcher: capture + journal (throttled internally; no-op
        # unless --incident-watcher and an embedded lighthouse).
        self._watcher_once()
        return restarted

    def pid(self, group: int) -> Optional[int]:
        """PID of the group's current process (None while dead) — lets fault
        injectors pin per-incarnation state (e.g. the straggler bench's
        pid-pinned slow-step file, which must not follow the group id onto
        the replacement)."""
        g = self._groups[group]
        if g.proc is not None and g.proc.poll() is None:
            return g.proc.pid
        return None

    def _sentinel_once(self) -> None:
        """Acts on the lighthouse's straggler alerts (``/alerts.json``,
        polled at most once a second): an ACTIVE, unhandled straggler alert
        for a group this supervisor owns triggers the cooperative-drain
        rotation — exactly what an operator clicking "drain" on the slow
        host would do, automated.  The lighthouse detects (it sees every
        replica's pace); the supervisor acts (it owns the spare pool).
        When the pool is configured but momentarily empty the alert is left
        unhandled and retried next poll — rotating without a warm
        replacement would trade a slow step for a cold-start gap."""
        if not self._straggler_auto_drain or not self.lighthouse_http_address:
            return
        now = time.monotonic()
        if now - self._sentinel_last_poll < 1.0:
            return
        self._sentinel_last_poll = now
        alerts = fetch_alerts(self.lighthouse_http_address)
        if alerts is None:
            return  # missed poll; retried in a second
        for alert in alerts.get("alerts", []):
            if not alert.get("active") or alert.get("kind") != "straggler":
                continue
            if alert.get("id") in self._handled_alerts:
                continue
            group_s = str(alert.get("replica_id", "")).split(":", 1)[0]
            try:
                group = int(group_s)
            except ValueError:
                continue
            if group not in self._groups:
                continue
            g = self._groups[group]
            # The alert names an INCARNATION; the group slot may already
            # hold a different process (the alerted one crashed and was
            # restarted before the graveyard prune resolved its alert).
            # Draining the fresh replacement over a stale alert would be a
            # spurious handoff — skip when the slot's process is younger
            # than the alert.  Clock bases differ (alert: epoch ms; spawn:
            # monotonic), so compare AGES, with a 1 s slack for the skew
            # between time.time() and the lighthouse's stamp.
            alert_age = time.time() - float(alert.get("raised_ms", 0)) / 1e3
            proc_age = (
                now - g.spawned_at if g.proc is not None else float("inf")
            )
            if proc_age + 1.0 < alert_age:
                self._handled_alerts.add(alert.get("id"))  # stale: never act
                continue
            if self._spares_target > 0 and self.spare_count() == 0:
                continue  # pool refilling; retry next poll
            self._handled_alerts.add(alert.get("id"))
            logger.warning(
                "group %d (%s) confirmed straggler (%.2fx median, step time "
                "%.0f ms); rotating out via cooperative drain",
                group, alert.get("replica_id"),
                float(alert.get("ratio", 0.0)),
                float(alert.get("step_time_ms", 0.0)),
            )
            self._metrics.emit(
                "straggler_drain",
                group=str(group),
                replica_id=alert.get("replica_id"),
                alert_id=alert.get("id"),
                ratio=alert.get("ratio"),
                step_time_ms=alert.get("step_time_ms"),
            )
            try:
                self.drain(group, deadline_s=30.0)
            except RuntimeError:
                # The donor already exited (the lighthouse's own auto-drain
                # mark aborts its quorum joins, and a cooperative Manager
                # exits cleanly on that) — just make sure a replacement
                # owns the slot.
                if g.proc is None or g.proc.poll() is not None:
                    self.spawn(group)

    def _watcher_once(self) -> None:
        """One IncidentWatcher pass (built lazily, throttled internally):
        the watcher polls the incident feed, captures evidence bundles
        into the drain/log dir, and journals flap-guarded remediation
        recommendations to ``watcher_journal.jsonl`` there.  Acting is
        gated separately (watcher_act) and limited to the cooperative
        drain, routed through this supervisor's own :meth:`drain` so the
        departing group gets a replacement."""
        if not self._incident_watcher_enabled or not self.lighthouse_http_address:
            return
        if self._watcher is None:
            from torchft_tpu.obs.watcher import IncidentWatcher

            def _drain_group(target: str) -> None:
                group = int(target)
                if group not in self._groups:
                    raise ValueError(f"unknown group {target}")
                try:
                    self.drain(group, deadline_s=30.0)
                except RuntimeError:
                    # Donor already gone (the lighthouse-side drain mark
                    # aborted its joins); just refill the slot.
                    g = self._groups[group]
                    if g.proc is None or g.proc.poll() is not None:
                        self.spawn(group)

            metrics_path = self._base_env.get("TPUFT_METRICS_PATH")
            self._watcher = IncidentWatcher(
                [self.lighthouse_http_address],
                self._drain_dir or ".",
                act=self._watcher_act,
                metrics_paths=[metrics_path] if metrics_path else [],
                drain_cb=_drain_group,
            )
        try:
            self._watcher.poll_once()
        except Exception:  # noqa: BLE001
            # The watcher observes the run; it must never take it down.
            logger.exception("incident watcher poll failed")

    def running(self) -> bool:
        """True while any group process is alive."""
        return any(
            g.proc is not None and g.proc.poll() is None for g in self._groups.values()
        )

    def all_exited_clean(self) -> bool:
        return all(g.exited_clean for g in self._groups.values())

    def exhausted(self) -> List[int]:
        """Groups that died and have no restart budget left."""
        out = []
        for i, g in self._groups.items():
            if g.exited_clean or g.held or g.proc is None:
                continue
            code = g.proc.poll()
            if (
                code is not None
                and code != 0
                and self._max_restarts is not None
                and g.restarts >= self._max_restarts
            ):
                out.append(i)
        return out

    def restarts(self, group: int) -> int:
        return self._groups[group].restarts

    def stop(self) -> None:
        """SIGTERM every group (and spare), escalate to SIGKILL, close logs
        and the embedded Lighthouse."""
        for g in self._groups.values():
            if g.proc is not None and g.proc.poll() is None:
                g.proc.send_signal(signal.SIGTERM)
        for d in self._draining:
            if d.proc.poll() is None:
                d.proc.kill()  # a donor mid-drain at stop() gets no grace
        for spare in self._spares:
            if spare.proc.poll() is None:
                spare.proc.kill()  # spares hold no state worth a grace period
        for g in self._groups.values():
            if g.proc is not None:
                try:
                    g.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    g.proc.kill()
                    g.proc.wait(timeout=5)
        for spare in self._spares:
            try:
                spare.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
            if spare.log is not None:
                spare.log.close()
        self._spares.clear()
        # Go-file cleanup: remove the mkdtemp directory outright, or the
        # stray .go files when they lived in the caller's log_dir.
        if self._spare_dir is not None:
            import glob
            import shutil

            if self._spare_dir_created:
                shutil.rmtree(self._spare_dir, ignore_errors=True)
            else:
                for path in glob.glob(os.path.join(self._spare_dir, "spare_*.go")):
                    try:
                        os.remove(path)
                    except OSError:
                        pass
            self._spare_dir = None
        for d in self._draining:
            try:
                d.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
            if d.log is not None:
                d.log.close()
            try:
                os.remove(d.notice_path)
            except OSError:
                pass
        self._draining.clear()
        if self._drain_dir is not None:
            import glob
            import shutil

            if self._drain_dir_created:
                shutil.rmtree(self._drain_dir, ignore_errors=True)
            else:
                for path in glob.glob(os.path.join(self._drain_dir, "drain_*.json")):
                    try:
                        os.remove(path)
                    except OSError:
                        pass
            self._drain_dir = None
        for g in self._groups.values():
            if g.log is not None:
                g.log.close()
                g.log = None
        self._metrics.close()
        if self._embedded is not None:
            self._embedded.shutdown()
            self._embedded = None


def main(argv: Optional[List[str]] = None) -> int:
    """CLI entry: ``python -m torchft_tpu.launch --groups N -- <cmd>``."""
    parser = argparse.ArgumentParser(
        prog="python -m torchft_tpu.launch",
        description="Launch N fault-tolerant replica groups with a restart "
        "supervisor (the torchx.hsdp component analogue).",
    )
    parser.add_argument("--groups", type=int, default=2, help="replica groups")
    parser.add_argument(
        "--max-restarts", type=int, default=None, help="per-group restart budget"
    )
    parser.add_argument(
        "--lighthouse",
        default="embed",
        help='"embed" (default: in-process native Lighthouse), or host:port '
        "of an external one",
    )
    parser.add_argument("--min-replicas", type=int, default=1)
    parser.add_argument("--join-timeout-ms", type=int, default=2000)
    parser.add_argument(
        "--spares", type=int, default=0,
        help="hot-spare pool: pre-initialized processes that adopt a dead "
        "group's id instantly (skips the respawn + runtime-init floor)",
    )
    parser.add_argument("--log-dir", default=None)
    parser.add_argument(
        "--incident-watcher", action="store_true",
        help="run the IncidentWatcher against the embedded lighthouse: "
        "auto-capture incident bundles + journal flap-guarded remediation "
        "recommendations (watcher_journal.jsonl in the log dir); dry-run "
        "unless --watcher-act",
    )
    parser.add_argument(
        "--watcher-act", action="store_true",
        help="let the IncidentWatcher execute its one actionable policy "
        "(cooperative drain); all other recommendations stay dry-run",
    )
    spec = parser.add_argument_group(
        "scheduler spec generation",
        "--dump-spec renders the same env contract as a GKE JobSet manifest "
        "(one TPU-slice Job per replica group + a lighthouse) instead of "
        "launching locally — the torchx-component analogue "
        "(torchft/torchx.py:11-80).",
    )
    spec.add_argument(
        "--dump-spec", action="store_true",
        help="print a JobSet YAML manifest for this job and exit",
    )
    spec.add_argument("--name", default="tpuft", help="JobSet name")
    spec.add_argument(
        "--hosts-per-group", type=int, default=1,
        help="hosts per replica-group slice (TPUFT_NUM_HOSTS)",
    )
    spec.add_argument("--image", default="REPLACE_ME_IMAGE")
    spec.add_argument("--tpu-accelerator", default="tpu-v5-lite-podslice")
    spec.add_argument("--tpu-topology", default="2x4")
    spec.add_argument("--chips-per-host", type=int, default=4)
    parser.add_argument(
        "cmd", nargs=argparse.REMAINDER, help="-- <command for one replica group>"
    )
    args = parser.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        parser.error("missing replica-group command (after --)")

    if args.dump_spec:
        from torchft_tpu.spec import dump_yaml, jobset_spec

        print(
            dump_yaml(
                jobset_spec(
                    cmd,
                    name=args.name,
                    num_groups=args.groups,
                    hosts_per_group=args.hosts_per_group,
                    image=args.image,
                    tpu_accelerator=args.tpu_accelerator,
                    tpu_topology=args.tpu_topology,
                    chips_per_host=args.chips_per_host,
                    max_restarts=args.max_restarts if args.max_restarts is not None else 10,
                    min_replicas=args.min_replicas,
                )
            ),
            end="",
        )
        return 0

    launcher = Launcher(
        cmd,
        args.groups,
        lighthouse=args.lighthouse,
        max_restarts=args.max_restarts,
        min_replicas=args.min_replicas,
        join_timeout_ms=args.join_timeout_ms,
        log_dir=args.log_dir,
        spares=args.spares,
        incident_watcher=args.incident_watcher,
        watcher_act=args.watcher_act,
    )
    with launcher:
        print(
            f"[tpuft_launch] {args.groups} groups, lighthouse="
            f"{launcher.lighthouse_address or '(inherited)'}",
            flush=True,
        )
        try:
            while launcher.running() or not (
                launcher.all_exited_clean() or launcher.exhausted()
            ):
                time.sleep(0.25)
                launcher.supervise_once()
                if launcher.all_exited_clean():
                    return 0
                if launcher.exhausted():
                    print(
                        f"[tpuft_launch] groups {launcher.exhausted()} exhausted "
                        "their restart budget",
                        file=sys.stderr,
                        flush=True,
                    )
                    return 1
        except KeyboardInterrupt:
            return 130
    return 0


if __name__ == "__main__":
    sys.exit(main())
