"""The HA lighthouse failover cell, driven by
tests/test_integration_smokes.py::test_ha_quick_smoke: SIGKILL the leader
mid-run and count what the takeover cost.

- N lighthouse replica processes (``python -m torchft_tpu.lighthouse_cli
  --lease-file ...``) share a lease file; one wins the election and serves,
  the rest are warm standbys receiving continuous state replication;
- G replica-group worker processes run the REAL Manager control loop
  (quorum -> step -> two-phase commit vote) against the full
  comma-separated ``TPUFT_LIGHTHOUSE`` address list.  Workers are
  JAX-free: the cell exercises the CONTROL plane, so each "step" is a
  short sleep -- hundreds of commits per window instead of a handful;
- mid-window the driver SIGKILLs the current leader (found via the lease
  file) and records: the lease-file epoch bump and the
  ``lighthouse_failover`` event the winning standby writes into the obs
  stream, commits of every group after the kill, failed commits on the
  healthy groups (must be ZERO -- the managers' failover clients retry
  inside the quorum deadline instead of failing the step), and state
  continuity on the new leader (/metrics still shows every replica's step
  AND the straggler-sentinel step-time gauges that only exist if the
  health state was replicated, at an epoch exactly one higher).

The workers are this file run as a script (``--worker``, see the end):
nothing a person would run.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

from harness import REPO, script_env


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# Worker: one replica group's Manager control loop (re-entered subprocess)
# ---------------------------------------------------------------------------


def _worker_main(cfg: Dict) -> None:
    """One replica group: real Manager + lighthouse quorum + commit votes,
    no JAX and no gradient traffic — a control-plane treadmill.  Prints a
    one-line JSON summary on exit; per-event truth rides in the shared
    metrics stream (TPUFT_METRICS_PATH)."""
    import numpy as np

    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.manager import Manager
    from datetime import timedelta

    state = {"w": np.zeros(8, dtype=np.float32)}
    manager = Manager(
        collective=TCPCollective(timeout=20.0),
        load_state_dict=lambda sd: state.update(sd),
        state_dict=lambda: dict(state),
        min_replica_size=1,
        rank=0,
        world_size=1,
        replica_id=str(cfg["group"]),
        lighthouse_addr=cfg["lighthouse"],
        # The failover budget: a quorum call must be allowed to ride out a
        # full leader election (lease expiry + takeover) inside its own
        # deadline, or the step fails and the zero-failed-commits contract
        # breaks on a fault that lost no worker.
        quorum_timeout=timedelta(seconds=cfg.get("quorum_timeout_s", 20.0)),
        timeout=timedelta(seconds=20.0),
        connect_timeout=timedelta(seconds=10.0),
        checkpoint_transport=HTTPTransport(timeout=20.0),
        init_sync=False,
    )
    # ALL groups share one absolute end_ts (driver wall clock): a per-process
    # now+run_s deadline lets the earliest starter exit while a sibling still
    # counts steps, and a counted quorum with an absent sibling blocks on
    # the split-brain guard until timeout — a failed commit the CONTROL
    # plane never caused.
    end_ts = float(cfg["end_ts"])
    step_s = float(cfg.get("step_s", 0.05))
    groups = int(cfg["groups"])
    workdir = cfg["workdir"]
    commits = 0
    failed = 0
    try:
        while time.time() < end_ts:
            manager.start_quorum()
            time.sleep(step_s)  # the "train step"
            if manager.should_commit():
                commits += 1
            else:
                failed += 1
        # Linger: keep feeding the quorum machine (uncounted) until every
        # sibling has finished its counted window, so a sibling's LAST
        # counted quorum — started a tick before ours ended — still forms
        # instead of stalling against our missing join.
        with open(os.path.join(workdir, f"done_{cfg['group']}"), "w"):
            pass
        linger_deadline = time.time() + 20.0
        while time.time() < linger_deadline:
            if all(
                os.path.exists(os.path.join(workdir, f"done_{g}"))
                for g in range(groups)
            ):
                break
            manager.start_quorum()
            time.sleep(step_s)
            manager.should_commit()
    finally:
        summary = {"group": cfg["group"], "commits": commits, "failed": failed}
        print("HA_WORKER " + json.dumps(summary), flush=True)
        manager.shutdown()


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _spawn_lighthouse(
    bind_port: int,
    http_port: int,
    lease_path: str,
    peer_ports: List[int],
    lease_ms: int,
    log_path: str,
    metrics_path: str,
    min_replicas: int,
) -> subprocess.Popen:
    env = dict(os.environ)
    env["TPUFT_METRICS_PATH"] = metrics_path
    # The child inherits the fd via Popen; close the parent's handle right
    # away so repeated trials (and the tier-1 smoke inside pytest) do not
    # leak one fd per spawned process.
    with open(log_path, "ab") as log:
        return subprocess.Popen(
            [
                sys.executable, "-m", "torchft_tpu.lighthouse_cli",
                "--bind", f"127.0.0.1:{bind_port}",
                "--http_bind", f"127.0.0.1:{http_port}",
                # min_replicas = the full group count: the FIRST quorum
                # contains every group, so nobody sprints ahead solo and
                # forces the late joiner through a heal it cannot win a
                # split-brain vote for.
                "--min_replicas", str(min_replicas),
                "--join_timeout_ms", "2000",
                "--lease-file", lease_path,
                "--lease-ms", str(lease_ms),
                "--peers", ",".join(f"127.0.0.1:{p}" for p in peer_ports),
            ],
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
            cwd=REPO,
        )


def _scrape(http_port: int, path: str, timeout: float = 2.0) -> Optional[str]:
    import urllib.request

    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{http_port}{path}", timeout=timeout
        ) as resp:
            return resp.read().decode()
    except Exception:  # noqa: BLE001 — poller; absence is an answer
        return None


def _metric_value(text: str, name: str) -> Optional[float]:
    for line in text.splitlines():
        if line.startswith(name) and " " in line and "{" not in line:
            try:
                return float(line.rsplit(" ", 1)[1])
            except ValueError:
                return None
    return None


def _metric_replicas(text: str, name: str) -> List[str]:
    out = []
    for line in text.splitlines():
        if line.startswith(name + "{"):
            try:
                out.append(line.split('replica="', 1)[1].split('"', 1)[0])
            except IndexError:
                pass
    return out


def run_failover(
    workdir: str,
    lighthouses: int = 3,
    groups: int = 2,
    lease_ms: int = 1500,
    window_s: float = 30.0,
) -> Dict:
    """One failover trial (see the module docstring for what each field
    backs)."""
    from torchft_tpu.ha.lease import FileLease
    from torchft_tpu.metrics import MetricsLogger
    from torchft_tpu.obs import report as obs_report

    os.makedirs(workdir, exist_ok=True)
    metrics_path = os.path.join(workdir, "metrics.jsonl")
    lease_path = os.path.join(workdir, "lease")
    lease_view = FileLease(lease_path, lease_ms, owner_id="bench-driver")
    fault_log = MetricsLogger(metrics_path, replica_id="bench-driver")

    ports = [_free_port() for _ in range(lighthouses)]
    http_ports = [_free_port() for _ in range(lighthouses)]
    procs: List[subprocess.Popen] = []
    workers: List[subprocess.Popen] = []
    lease_s = lease_ms / 1000.0  # sizes the takeover wait's timeout
    result: Dict = {
        "lighthouses": lighthouses,
        "groups": groups,
        "lease_ms": lease_ms,
        "window_s": window_s,
        "ok": False,
    }
    try:
        for i in range(lighthouses):
            peer_ports = [p for j, p in enumerate(ports) if j != i]
            procs.append(
                _spawn_lighthouse(
                    ports[i], http_ports[i], lease_path, peer_ports, lease_ms,
                    os.path.join(workdir, f"lighthouse_{i}.log"), metrics_path,
                    min_replicas=groups,
                )
            )
        # Wait for the initial election.
        t0 = time.time()
        rec = None
        while time.time() - t0 < 30.0:
            rec = lease_view.read()
            if rec is not None and not rec.expired(int(time.time() * 1000)):
                break
            time.sleep(0.05)
        assert rec is not None, "no lighthouse won the initial election"
        epoch_before = rec.epoch
        leader_idx = ports.index(int(rec.rpc_address.rsplit(":", 1)[1]))
        result["leader_epoch_before"] = epoch_before

        # Workers against the FULL address list (leader not first, so the
        # normal path already exercises rotation/redirect).
        addr_list = ",".join(f"127.0.0.1:{p}" for p in ports)
        worker_env = script_env({"TPUFT_METRICS_PATH": metrics_path})
        end_ts = time.time() + window_s
        for g in range(groups):
            cfg = {
                "group": g,
                "groups": groups,
                "lighthouse": addr_list,
                "end_ts": end_ts,
                "workdir": workdir,
                "step_s": 0.05,
            }
            with open(os.path.join(workdir, f"g{g}.log"), "ab") as log:
                workers.append(
                    subprocess.Popen(
                        [sys.executable, os.path.abspath(__file__), "--worker",
                         json.dumps(cfg)],
                        env=worker_env,
                        stdout=log,
                        stderr=subprocess.STDOUT,
                        cwd=REPO,
                    )
                )

        # Hold the kill until every group has a commit timeline (and the
        # step-time EWMA had a chance to ride a heartbeat).
        def commits_per_group() -> Dict[str, List[float]]:
            return obs_report.commit_timelines(
                obs_report.read_events([metrics_path])
            )

        # The kill must land while the workers still have most of their
        # window left (post-kill commits are the resume evidence), so the
        # warm-up wait gives up at mid-window instead of outliving it.
        kill_by = end_ts - window_s * 0.5
        while time.time() < kill_by:
            cs = commits_per_group()
            if all(len(cs.get(str(g), [])) >= 5 for g in range(groups)):
                break
            time.sleep(0.25)

        # Pre-kill continuity baseline from the live leader.
        pre = _scrape(http_ports[leader_idx], "/metrics") or ""
        result["replicas_tracked_before"] = sorted(
            {r.split(":", 1)[0] for r in _metric_replicas(pre, "tpuft_replica_step")}
        )
        result["step_time_tracked_before"] = sorted(
            {r.split(":", 1)[0]
             for r in _metric_replicas(pre, "tpuft_replica_step_time_seconds")}
        )

        # THE FAULT: SIGKILL the active leader.
        kill_ts = time.time()
        fault_log.emit("fault", ts=kill_ts, kind="lighthouse", group="lighthouse",
                       plan="leader_sigkill")
        procs[leader_idx].kill()
        procs[leader_idx].wait()
        result["kill_ts"] = kill_ts

        # Takeover: lease epoch bump by a different owner.
        took_over = False
        t0 = time.time()
        while time.time() - t0 < max(15.0, 6 * lease_s):
            rec2 = lease_view.read()
            if (
                rec2 is not None
                and rec2.epoch > epoch_before
                and not rec2.expired(int(time.time() * 1000))
            ):
                took_over = True
                result["leader_epoch_after"] = rec2.epoch
                new_leader_idx = ports.index(int(rec2.rpc_address.rsplit(":", 1)[1]))
                break
            time.sleep(0.05)
        assert took_over, "no standby took over the lease"

        # The lease record is written a settle-delay BEFORE the winner
        # confirms the race and flips its native role (and emits the
        # failover event) — wait for the role gauge so the continuity
        # scrape below cannot race the takeover it is trying to verify.
        poll_deadline = time.time() + 10.0
        while time.time() < poll_deadline:
            m = _scrape(http_ports[new_leader_idx], "/metrics")
            if m is not None and _metric_value(m, "tpuft_lighthouse_role") == 1.0:
                break
            time.sleep(0.05)

        # Let the workers run out their window, then collect summaries.
        for w in workers:
            w.wait(timeout=window_s + 60.0)
        summaries = []
        for g in range(groups):
            with open(os.path.join(workdir, f"g{g}.log"), "rb") as f:
                for line in f:
                    if line.startswith(b"HA_WORKER "):
                        summaries.append(json.loads(line[len(b"HA_WORKER "):]))
        result["worker_summaries"] = summaries

        # Post-failover continuity, evaluated against whoever leads NOW.
        # Re-resolve from the lease file at scrape time: on a heavily
        # loaded host a renewal stall can lapse the new leader's lease and
        # move leadership again (epoch 3+) — correct behavior (the
        # serve-time guard is doing its job and replication carries the
        # state onward), so the continuity contract follows the current
        # leader, and the split-brain check is "every OTHER instance reads
        # role 0 while the current leader reads 1", settled with a bounded
        # retry instead of one instantaneous snapshot (a single scrape
        # landing inside a renewal stall reads a conservative 0).
        post = ""
        cur_idx = new_leader_idx
        standby_roles: List[float] = []
        settle_deadline = time.time() + 15.0
        while time.time() < settle_deadline:
            cur = lease_view.read()
            if cur is not None and not cur.expired(int(time.time() * 1000)):
                try:
                    cur_idx = ports.index(int(cur.rpc_address.rsplit(":", 1)[1]))
                except ValueError:
                    pass
                result["leader_epoch_final"] = cur.epoch
            m = _scrape(http_ports[cur_idx], "/metrics")
            if m is None or _metric_value(m, "tpuft_lighthouse_role") != 1.0:
                time.sleep(0.2)
                continue
            roles = []
            for i in range(lighthouses):
                if i in (leader_idx, cur_idx):
                    continue
                s = _scrape(http_ports[i], "/metrics")
                if s is not None:
                    with open(
                        os.path.join(workdir, f"scrape_standby_{i}.metrics"), "w"
                    ) as f:
                        f.write(s)
                    roles.append(_metric_value(s, "tpuft_lighthouse_role"))
            if any(r == 1.0 for r in roles):
                # Leadership is mid-move (the "standby" just took the
                # lease); re-resolve and re-check rather than reading a
                # handoff as a split brain.
                time.sleep(0.2)
                continue
            post = m
            standby_roles = roles
            break
        with open(os.path.join(workdir, "scrape_new_leader.metrics"), "w") as f:
            f.write(post)
        result["role_new_leader"] = _metric_value(post, "tpuft_lighthouse_role")
        result["epoch_gauge_new_leader"] = _metric_value(
            post, "tpuft_lighthouse_leader_epoch"
        )
        result["replicas_tracked_after"] = sorted(
            {r.split(":", 1)[0] for r in _metric_replicas(post, "tpuft_replica_step")}
        )
        result["step_time_tracked_after"] = sorted(
            {r.split(":", 1)[0]
             for r in _metric_replicas(post, "tpuft_replica_step_time_seconds")}
        )
        result["standby_roles_after"] = standby_roles

        # Commit accounting from the stream.
        events = obs_report.read_events([metrics_path])
        commits = obs_report.commit_timelines(events)
        failed_after: Dict[str, int] = {}
        for ev in events:
            if ev.get("event") == "commit" and not ev.get("committed"):
                # Scope to the COUNTED window [kill, end_ts]: after end_ts
                # the workers are in the uncounted linger phase, where the
                # last group standing legitimately fails a quorum once its
                # siblings exit (min_replicas = all groups) — harness
                # teardown, not a control-plane failure.
                if kill_ts <= float(ev.get("ts", 0.0)) <= end_ts:
                    g = str(ev.get("replica_id", "")).split(":", 1)[0]
                    failed_after[g] = failed_after.get(g, 0) + 1
        result["failed_commits_after_kill"] = failed_after
        result["failed_commits_healthy_groups"] = sum(failed_after.values())

        result["per_group_commits"] = {
            g: len(ts) for g, ts in sorted(commits.items())
        }
        # The headline criterion: quorum formation resumed under the new
        # leader -- evidenced by committed steps after the kill (a commit
        # REQUIRES a formed quorum) in every group.
        result["commits_after_kill"] = {
            str(g): sum(1 for t in commits.get(str(g), []) if t > kill_ts)
            for g in range(groups)
        }
        result["resumed_after_kill"] = all(
            n > 0 for n in result["commits_after_kill"].values()
        )

        # The failover must be visible in the obs stream (the standby's
        # takeover event), and the report must charge it as quorum-ish
        # time, not a worker fault.
        failover_events = [
            ev for ev in events if ev.get("event") == "lighthouse_failover"
        ]
        result["failover_event_seen"] = bool(failover_events)
        result["failover_event_epoch"] = (
            failover_events[0].get("leader_epoch") if failover_events else None
        )
        attribution = obs_report.attribute(events)
        result["election_s_attributed"] = attribution["totals"].get("election_s")
        result["lighthouse_elections_in_report"] = attribution["goodput"].get(
            "lighthouse_elections"
        )
        result["victims_recovered_in_report"] = attribution["goodput"].get(
            "victims_recovered"
        )

        # The epoch gauge must match the CURRENT lease epoch (>= the
        # takeover epoch: under load leadership may have moved again, and
        # continuity must hold across every hop, not just the first).
        final_epoch = result.get("leader_epoch_final", result["leader_epoch_after"])
        result["metrics_continuity_ok"] = (
            result["role_new_leader"] == 1.0
            and result["epoch_gauge_new_leader"] == float(final_epoch)
            and final_epoch >= result["leader_epoch_after"]
            and result["replicas_tracked_after"] == result["replicas_tracked_before"]
            and result["step_time_tracked_after"] == result["step_time_tracked_before"]
            and len(result["replicas_tracked_after"]) == groups
        )
        result["ok"] = bool(
            result["resumed_after_kill"]
            and result["failed_commits_healthy_groups"] == 0
            and result["metrics_continuity_ok"]
            and result["failover_event_seen"]
            and all(r == 0.0 for r in standby_roles)
            and all(s["commits"] > 0 and s["failed"] == 0 for s in summaries)
        )
        return result
    finally:
        fault_log.close()
        for w in workers:
            if w.poll() is None:
                w.kill()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def run_quick() -> Dict:
    """The smoke's shape: 2 lighthouses, 2 groups, one leader SIGKILL,
    short window.  Workdir is kept under a tempdir for post-mortem."""
    workdir = tempfile.mkdtemp(prefix="tpuft_ha_quick_")
    return run_failover(
        workdir, lighthouses=2, groups=2, lease_ms=1200, window_s=18.0
    )


if __name__ == "__main__":
    # Worker entry only: the cell above starts this file as a script.
    _worker_main(json.loads(sys.argv[2]))
