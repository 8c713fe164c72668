"""The elastic-quorum cell, driven by
tests/test_integration_smokes.py::test_elastic_quick_smoke: constant global
batch across membership churn.

A SEEDED arrival/departure trace drives a live cluster of real Manager
subprocess groups through membership churn while the elastic batch engine
(`TPUFT_ELASTIC_GLOBAL_BATCH`, ddp.ElasticBatchScaler) holds the global
batch constant: survivors take larger per-group shares when the quorum
shrinks, joiners hot-admit and the share relaxes back.  What the cell
returns is counts from the metrics stream: failed commits, step records
that carry the plan, reconfigure modes, EC re-shard pushes, leaked fds.

Departures take the COOPERATIVE drain path (`lighthouse.drain`): spot
reclaim gives notice, the lighthouse excludes the leaver from the next
quorum immediately, the leaver finishes its in-flight step and exits via
`Manager.complete_drain()` -- which is what makes "zero failed survivor
commits across every transition" honest rather than aspirational (SIGKILL
mid-allreduce necessarily fails one survivor round; that is the churn
soak's job, not this trace's).  Arrivals are freshly spawned groups that
hot-admit at the next step boundary.

The workers are this file run as a script (``--worker``, see the end):
nothing a person would run.
"""

from __future__ import annotations

import gc
import json
import os
import random
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from harness import REPO, fd_count, script_env


# ---------------------------------------------------------------------------
# Worker: one replica group riding the elastic plan (re-entered subprocess)
# ---------------------------------------------------------------------------


def _worker_main(cfg: Dict) -> None:
    """One replica group: real Manager + lighthouse quorum + elastic batch
    plan + commit votes.  The "train step" sleeps proportional to THIS
    group's share of the constant global batch (the accumulation loop a
    real trainer would run), so wall-clock throughput honestly reflects
    the rescale: fewer groups -> bigger shares -> longer steps -> the same
    committed samples per step.  ``use_averager`` routes gradient traffic
    through a real multi-bucket GradientAverager (bucket plans keyed by
    participant count); otherwise a flat numpy payload rides
    Manager.allreduce directly (the JAX-free quick path)."""
    from datetime import timedelta

    import numpy as np

    use_averager = bool(cfg.get("use_averager"))
    if use_averager:
        # Pre-warm the runtime BEFORE dialing the lighthouse: a spare that
        # pays its JAX import inside its first lockstep step stalls every
        # survivor for the import time.  launch.py's spare pool pre-warms
        # for exactly this reason.
        import jax

        jax.numpy.zeros(1).block_until_ready()

    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.manager import Manager

    state = {"w": np.zeros(16, dtype=np.float32)}
    manager = Manager(
        collective=TCPCollective(timeout=30.0),
        load_state_dict=lambda sd: state.update(sd),
        state_dict=lambda: dict(state),
        min_replica_size=1,
        rank=0,
        world_size=1,
        replica_id=str(cfg["group"]),
        lighthouse_addr=cfg["lighthouse"],
        quorum_timeout=timedelta(seconds=30.0),
        timeout=timedelta(seconds=30.0),
        connect_timeout=timedelta(seconds=15.0),
        checkpoint_transport=HTTPTransport(timeout=30.0),
        init_sync=False,
    )
    averager = None
    grads = None
    if use_averager:
        from torchft_tpu.ddp import GradientAverager

        # Small bucket size over a few-leaf tree -> multiple buckets, so
        # the participant-keyed plan cache is exercised for real.
        averager = GradientAverager(manager, bucket_bytes=8 << 10)
        grads = [
            np.ones(4096, dtype=np.float32),
            np.ones(2048, dtype=np.float32),
            np.ones(1024, dtype=np.float32),
        ]
    payload = np.ones(2048, dtype=np.float32)

    workdir = cfg["workdir"]
    stop_path = os.path.join(workdir, "stop")
    done_all_path = os.path.join(workdir, "done_all")
    end_cap = float(cfg["end_cap_ts"])  # hard ceiling, stop file is the norm
    per_sample_s = float(cfg.get("per_sample_s", 0.02))
    global_batch = int(os.environ.get("TPUFT_ELASTIC_GLOBAL_BATCH", "32"))
    commits = 0
    failed = 0
    samples = 0
    drained = False
    participants_seen: set = set()
    try:
        with open(os.path.join(workdir, f"ready_{cfg['group']}"), "w"):
            pass
        # Initial workers barrier on the driver's go file so the FIRST
        # quorum contains the whole starting set; arrivals see it already
        # present and proceed straight to their hot-admit join.
        go_deadline = time.time() + 180.0
        go_path = os.path.join(workdir, "go")
        while time.time() < go_deadline and not os.path.exists(go_path):
            time.sleep(0.05)
        while time.time() < end_cap and not os.path.exists(stop_path):
            try:
                manager.start_quorum()
                manager.wait_quorum()
                if manager.drain_requested():
                    # Cooperative departure: the lighthouse already
                    # excluded us from the next quorum — finish cleanly,
                    # never vote a failed commit into the stream.
                    drained = True
                    break
                plan = manager.elastic_plan() or {
                    "group_batch": max(1, global_batch // 2),
                    "global_batch": global_batch,
                }
                participants_seen.add(int(plan.get("participants", 0)))
                # The accumulation loop: this group's share of the fixed
                # global batch at a fixed per-sample cost.
                time.sleep(per_sample_s * int(plan["group_batch"]))
                if averager is not None:
                    grads = averager.allreduce(grads)
                else:
                    manager.allreduce(payload.copy())
                if manager.should_commit():
                    commits += 1
                    samples += int(plan["global_batch"])
                else:
                    failed += 1
            except Exception:  # noqa: BLE001 — count and retry, never die
                if manager.drain_requested():
                    drained = True
                    break
                failed += 1
                time.sleep(0.2)
        if not drained:
            # Uncounted linger: siblings' final counted quorums — started a
            # tick before ours ended — need our join to form.  Bounded;
            # the driver writes done_all once every live group checked in.
            with open(os.path.join(workdir, f"done_{cfg['group']}"), "w"):
                pass
            linger_deadline = time.time() + 12.0
            while (
                time.time() < linger_deadline
                and not os.path.exists(done_all_path)
            ):
                try:
                    manager.start_quorum()
                    time.sleep(0.1)
                    manager.should_commit()
                except Exception:  # noqa: BLE001 — teardown races are benign
                    break
    finally:
        if drained:
            manager.complete_drain()
        summary = {
            "group": cfg["group"],
            "commits": commits,
            "failed": failed,
            "samples": samples,
            "drained": drained,
            "participants_seen": sorted(participants_seen),
        }
        if averager is not None:
            # Evidence the bucket-plan cache is participant-keyed: one
            # plan per membership size this group trained through.
            summary["bucket_plan_participants"] = sorted(
                {key[3] for key in averager._plans}
            )
        print("ELASTIC_WORKER " + json.dumps(summary), flush=True)
        manager.shutdown()


# ---------------------------------------------------------------------------
# Trace construction
# ---------------------------------------------------------------------------


def make_trace(
    seed: int, kinds: List[str], start_groups: int, gap_range=(4.0, 7.0)
) -> List[Dict[str, Any]]:
    """The seeded spot-market trace: for each event kind in ``kinds``
    (``"leave"``/``"join"``), the rng picks WHICH live non-anchor group
    departs and the inter-event gap.  Group 0 is the anchor (never leaves)
    so the cell always has one continuous commit timeline to measure
    steady-state cadence from.  Join ids are fresh (monotonic) — drained
    incarnations are tombstoned by the lighthouse and never reused."""
    rng = random.Random(seed)
    live = list(range(start_groups))
    next_id = start_groups
    trace: List[Dict[str, Any]] = []
    for kind in kinds:
        gap = round(rng.uniform(*gap_range), 2)
        if kind == "leave":
            candidates = [g for g in live if g != 0]
            if not candidates:
                raise ValueError("trace would drain the anchor group")
            victim = rng.choice(candidates)
            live.remove(victim)
            trace.append(
                {"kind": "leave", "group": victim, "gap_s": gap,
                 "n_after": len(live)}
            )
        elif kind == "join":
            trace.append(
                {"kind": "join", "group": next_id, "gap_s": gap,
                 "n_after": len(live) + 1}
            )
            live.append(next_id)
            next_id += 1
        else:
            raise ValueError(f"unknown trace event kind {kind!r}")
    return trace


# ---------------------------------------------------------------------------
# Cell driver
# ---------------------------------------------------------------------------


def _spawn_worker(
    workdir: str,
    group: int,
    lighthouse_addr: str,
    end_cap: float,
    per_sample_s: float,
    use_averager: bool,
    env: Dict[str, str],
    log_paths: List[str],
    workers: Dict[int, subprocess.Popen],
) -> None:
    cfg = {
        "group": group,
        "lighthouse": lighthouse_addr,
        "workdir": workdir,
        "end_cap_ts": end_cap,
        "per_sample_s": per_sample_s,
        "use_averager": use_averager,
    }
    log_path = os.path.join(workdir, f"g{group}.log")
    log_paths.append(log_path)
    with open(log_path, "ab") as log:
        workers[group] = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             json.dumps(cfg)],
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
            cwd=REPO,
        )


def run_trace_cell(
    workdir: str,
    start_groups: int,
    trace: List[Dict[str, Any]],
    *,
    global_batch: int = 32,
    per_sample_s: float = 0.02,
    use_averager: bool = True,
    tail_s: float = 6.0,
    min_groups: int = 2,
    ring2d_min: Optional[int] = None,
    worker_env: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    """One churn cell: spawn ``start_groups`` workers, run the trace's
    cooperative leaves (lighthouse drain) and hot-admit joins (fresh
    spawns), then count what the metrics stream recorded."""
    from torchft_tpu._native import LighthouseServer
    from torchft_tpu.obs import report as obs_report

    os.makedirs(workdir, exist_ok=True)
    metrics_path = os.path.join(workdir, "metrics.jsonl")
    gc.collect()
    fd_before = fd_count()
    result: Dict[str, Any] = {
        "groups_start": start_groups,
        "global_batch": global_batch,
        "per_sample_s": per_sample_s,
        "use_averager": use_averager,
        "trace": [dict(e) for e in trace],
        "ok": False,
    }
    workers: Dict[int, subprocess.Popen] = {}
    log_paths: List[str] = []
    lighthouse = None
    drained_groups: List[int] = []
    try:
        lighthouse = LighthouseServer(
            bind="127.0.0.1:0",
            http_bind="127.0.0.1:0",
            # The floor must stay satisfiable at the trace's smallest
            # membership; the ready/go barrier (not the floor) is what
            # makes the FIRST quorum contain the whole starting set.
            min_replicas=max(1, min_groups),
            join_timeout_ms=10000 + 500 * start_groups,
            quorum_tick_ms=50,
            # Every departure of a trace is by notice (a drain), so a stale
            # heartbeat detects nothing here; at 3 s a group that six workers'
            # load kept off the CPU for 3.4 s was taken for dead, and a
            # survivor's commit failed that no transition had caused.
            heartbeat_timeout_ms=15000,
        )
        env = script_env()
        env["TPUFT_METRICS_PATH"] = metrics_path
        env["TPUFT_ELASTIC_GLOBAL_BATCH"] = str(global_batch)
        # EC plane on: shards of each committed step's state spread across
        # the groups, so every membership change has coverage to re-form.
        env.setdefault("TPUFT_EC_K", "2")
        env.setdefault("TPUFT_EC_M", "1")
        env.setdefault("TPUFT_EC_INTERVAL", "1")
        env.setdefault("TPUFT_RING_TOPOLOGY", "auto")
        if ring2d_min is not None:
            env["TPUFT_RING2D_MIN_GROUPS"] = str(ring2d_min)
        if use_averager:
            env.setdefault("JAX_PLATFORMS", "cpu")
        if worker_env:
            env.update(worker_env)
        # Hard ceiling: warmup + every trace gap + per-event stabilization
        # budget + the tail.
        end_cap = (
            time.time() + 120.0
            + sum(float(e["gap_s"]) for e in trace)
            + 45.0 * max(1, len(trace)) + tail_s
        )
        for g in range(start_groups):
            _spawn_worker(
                workdir, g, lighthouse.address(), end_cap, per_sample_s,
                use_averager, env, log_paths, workers,
            )

        def commits_per_group() -> Dict[str, List[float]]:
            return obs_report.commit_timelines(
                obs_report.read_events([metrics_path])
            )

        # Ready/go barrier (fleet_cells' lesson): release together so the
        # first quorum holds the full starting set.
        ready_deadline = time.time() + 90.0 + 2.0 * start_groups
        while time.time() < ready_deadline:
            if all(
                os.path.exists(os.path.join(workdir, f"ready_{g}"))
                for g in range(start_groups)
            ):
                break
            time.sleep(0.1)
        with open(os.path.join(workdir, "go"), "w"):
            pass

        # Warmup: every starting group commits before the trace begins.
        warm_deadline = time.time() + 90.0
        while time.time() < warm_deadline:
            cs = commits_per_group()
            if all(len(cs.get(str(g), [])) >= 2 for g in range(start_groups)):
                break
            time.sleep(0.25)
        cs = commits_per_group()
        result["warmed_groups"] = sum(
            1 for g in range(start_groups) if len(cs.get(str(g), [])) >= 2
        )

        live = list(range(start_groups))
        transitions: List[Dict[str, Any]] = []
        for event in trace:
            time.sleep(float(event["gap_s"]))
            t_e = time.time()
            g = int(event["group"])
            survivors = list(live)
            if event["kind"] == "leave":
                # Cooperative drain: excluded from the next quorum
                # immediately, in-flight step finishes undisturbed.
                lighthouse.drain(str(g), deadline_ms=20000)
                survivors.remove(g)
                drained_groups.append(g)
                live.remove(g)
                try:
                    workers[g].wait(timeout=45.0)
                except subprocess.TimeoutExpired:
                    workers[g].kill()
                    workers[g].wait()
            else:
                _spawn_worker(
                    workdir, g, lighthouse.address(), end_cap, per_sample_s,
                    use_averager, env, log_paths, workers,
                )
                live.append(g)
            # Stabilization: every survivor commits >= 2 steps past the
            # event (and a joiner lands its first commit) before the next
            # event fires — each transition is measured in isolation.
            stab_deadline = time.time() + 60.0
            stable = False
            while time.time() < stab_deadline and not stable:
                cs = commits_per_group()
                stable = all(
                    len([t for t in cs.get(str(s), []) if t > t_e]) >= 2
                    for s in survivors
                ) and (
                    event["kind"] == "leave"
                    or len(cs.get(str(g), [])) >= 1
                )
                time.sleep(0.2)
            transitions.append(
                {
                    "kind": event["kind"],
                    "group": g,
                    "ts": t_e,
                    "n_after": len(live),
                    "survivors": survivors,
                    "stabilized": stable,
                }
            )
        time.sleep(tail_s)
        with open(os.path.join(workdir, "stop"), "w"):
            pass
        # Linger protocol: every live group checks in, then done_all
        # releases them together.
        done_deadline = time.time() + 30.0
        while time.time() < done_deadline:
            if all(
                os.path.exists(os.path.join(workdir, f"done_{g}"))
                for g in live
            ):
                break
            time.sleep(0.1)
        with open(os.path.join(workdir, "done_all"), "w"):
            pass
        for g in live:
            try:
                workers[g].wait(timeout=60.0)
            except subprocess.TimeoutExpired:
                workers[g].kill()
                workers[g].wait()

        # ----- scoring -----------------------------------------------------
        events = obs_report.read_events([metrics_path])
        cs = commits_per_group()
        result["per_group_commits"] = {g: len(ts) for g, ts in sorted(cs.items())}
        result["transitions_stabilized"] = sum(
            1 for t in transitions if t["stabilized"]
        )

        # Committed steps are cluster-lockstep, so distinct step numbers
        # count the cell's work without double-counting per group.
        result["committed_steps"] = len({
            int(ev["step"])
            for ev in events
            if ev.get("event") == "commit" and ev.get("committed")
        })
        result["transitions"] = [
            {k: t[k] for k in ("kind", "group", "n_after", "stabilized")}
            for t in transitions
        ]

        # Failed commits, from the stream (authoritative even if a worker
        # summary line is lost): every group in this cell is either a
        # survivor or a cooperative leaver/joiner, so the gate is zero
        # across ALL of them.
        failed_by_group: Dict[str, int] = {}
        for ev in events:
            if ev.get("event") == "commit" and not ev.get("committed"):
                grp = str(ev.get("replica_id", "")).split(":", 1)[0]
                failed_by_group[grp] = failed_by_group.get(grp, 0) + 1
        result["failed_commits_by_group"] = failed_by_group
        result["survivor_failed_commits"] = sum(failed_by_group.values())

        # Elastic invariant: every committed step record carries the
        # constant global batch; participants move with the trace.
        elastic_committed = 0
        bad_global = 0
        participants_seen: set = set()
        for ev in events:
            if ev.get("event") != "step_summary" or not ev.get("committed"):
                continue
            if "elastic_global_batch" not in ev:
                continue
            elastic_committed += 1
            if int(ev["elastic_global_batch"]) != global_batch:
                bad_global += 1
            participants_seen.add(int(ev.get("elastic_participants", 0)))
        total_committed_summaries = sum(
            1 for ev in events
            if ev.get("event") == "step_summary" and ev.get("committed")
        )
        result["elastic_records"] = {
            "committed_with_plan": elastic_committed,
            "committed_total": total_committed_summaries,
            "constant_global_batch": (
                elastic_committed == total_committed_summaries
                and elastic_committed > 0
                and bad_global == 0
            ),
            "participants_seen": sorted(participants_seen),
        }

        # Reconfiguration + membership + EC evidence.
        modes: Dict[str, int] = {}
        reused = opened = 0
        for ev in events:
            if ev.get("event") == "reconfigure":
                mode = str(ev.get("mode", "unknown"))
                modes[mode] = modes.get(mode, 0) + 1
                reused += int(ev.get("reused_lanes") or 0)
                opened += int(ev.get("opened_lanes") or 0)
        result["reconfigure_modes"] = modes
        result["reused_lanes_total"] = reused
        result["opened_lanes_total"] = opened
        result["membership_changes"] = sum(
            1 for ev in events if ev.get("event") == "membership_change"
        )
        result["ec_reshard_pushes"] = sum(
            1 for ev in events
            if ev.get("event") == "ec_push" and ev.get("reshard")
        )

        summaries = []
        for path in log_paths:
            try:
                with open(path, "rb") as f:
                    for line in f:
                        if line.startswith(b"ELASTIC_WORKER "):
                            summaries.append(
                                json.loads(line[len(b"ELASTIC_WORKER "):])
                            )
            except OSError:
                pass
        result["worker_summaries"] = sorted(summaries, key=lambda s: s["group"])
        result["drained_groups"] = drained_groups
    finally:
        for w in workers.values():
            if w.poll() is None:
                w.kill()
                w.wait()
        if lighthouse is not None:
            lighthouse.shutdown()

    # fd hygiene: everything the cell opened must be closed.
    fd_after = fd_count()
    settle = time.time() + 5.0
    while fd_after > fd_before and time.time() < settle:
        gc.collect()
        time.sleep(0.2)
        fd_after = fd_count()
    result["fd_leaked"] = max(0, fd_after - fd_before) if fd_before >= 0 else None

    result["ok"] = bool(
        result.get("warmed_groups") == start_groups
        and result.get("transitions_stabilized") == len(trace)
        and result.get("committed_steps", 0) > 0
        and result.get("survivor_failed_commits") == 0
        and result.get("elastic_records", {}).get("constant_global_batch")
        and (result.get("fd_leaked") in (0, None))
    )
    return result


# ---------------------------------------------------------------------------
# The smoke's cell
# ---------------------------------------------------------------------------


def run_quick(workdir: Optional[str] = None, seed: int = 7) -> Dict[str, Any]:
    """The 3-transition cell: 3 JAX-free groups, cooperative
    leave/join/leave on the flat-ring incremental path."""
    workdir = workdir or tempfile.mkdtemp(prefix="tpuft_elastic_q_")
    trace = make_trace(
        seed, ["leave", "join", "leave"], start_groups=3, gap_range=(1.5, 3.0)
    )
    cell = run_trace_cell(
        os.path.join(workdir, "elastic"),
        start_groups=3,
        trace=trace,
        global_batch=24,
        per_sample_s=0.01,
        use_averager=False,
        tail_s=3.0,
        min_groups=2,
    )
    return {"seed": seed, "global_batch": 24, "workdir": workdir,
            "elastic": cell, "ok": cell["ok"]}


if __name__ == "__main__":
    # Worker entry only: the cell above starts this file as a script.
    _worker_main(json.loads(sys.argv[2]))
