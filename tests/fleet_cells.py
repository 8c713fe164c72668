"""Live control-plane cells with replica groups as JAX-free worker
subprocesses, driven by tests/test_integration_smokes.py and
tests/test_federation.py.  What a cell returns is counts and booleans
(commits per group, quorum transitions reconstructed from the flight
recorder, observations in the lighthouse's histograms, leaked fds).

  control    -- ONE in-process native lighthouse + N worker subprocesses
                running the REAL Manager control loop (quorum -> sleep-step
                -> two-phase commit vote).  A cell can inject a CORRELATED
                PREEMPTION WAVE: several groups SIGKILLed inside one tight
                window (spot reclaim); the surviving groups must reform a
                quorum and keep committing, the driver must leak zero fds,
                and the lighthouse's flight-recorder dump must reconstruct
                the wave's quorum transitions.
  federated  -- the two-tier control plane (docs/wire.md "Federation"):
                child-lighthouse SUBPROCESSES own their region's heartbeats
                and push digests to an in-driver root, which forms the
                global quorum from digests alone (the root sees ZERO
                heartbeats).
  parity     -- the flat ring and ring2d on the same inputs at 4 in-process
                ranks.

The workers and the child lighthouses are this file run as a script
(``--worker`` / ``--child``, see the end): nothing a person would run.
"""

from __future__ import annotations

import gc
import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from harness import REPO, fd_count, script_env


# ---------------------------------------------------------------------------
# Worker: one replica group's Manager control loop (re-entered subprocess)
# ---------------------------------------------------------------------------


def _worker_main(cfg: Dict) -> None:
    """One replica group: real Manager + lighthouse quorum + commit votes,
    no JAX and no gradient traffic.  The cross-group collective still
    rendezvouses per quorum change, so at N >= the ring2d crossover the
    workers build (and, across the preemption wave, REBUILD at the new
    group count) the hierarchical topology's tier sockets.  Counted window
    ends when the driver's stop file appears; a bounded linger keeps
    feeding the quorum machine so siblings' last counted quorums can form
    (see failover_cells.py for the lesson this encodes)."""
    from datetime import timedelta

    import numpy as np

    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.manager import Manager

    state = {"w": np.zeros(8, dtype=np.float32)}
    manager = Manager(
        collective=TCPCollective(timeout=30.0),
        load_state_dict=lambda sd: state.update(sd),
        state_dict=lambda: dict(state),
        min_replica_size=1,
        rank=0,
        world_size=1,
        replica_id=str(cfg["group"]),
        lighthouse_addr=cfg["lighthouse"],
        # Budget for a full post-wave reformation (heartbeat staling +
        # rejoin fan-in) inside one quorum call on a loaded 1-2 core host.
        quorum_timeout=timedelta(seconds=cfg.get("quorum_timeout_s", 60.0)),
        timeout=timedelta(seconds=30.0),
        connect_timeout=timedelta(seconds=15.0),
        checkpoint_transport=HTTPTransport(timeout=30.0),
        init_sync=False,
    )
    workdir = cfg["workdir"]
    stop_path = os.path.join(workdir, "stop")
    end_cap = float(cfg["end_cap_ts"])  # hard ceiling, stop file is the norm
    step_s = float(cfg.get("step_s", 0.1))
    groups = int(cfg["groups"])
    commits = 0
    failed = 0
    try:
        # Ready/go barrier: interpreter startup at N=32 on a small host
        # spreads worker launch over tens of seconds; without the barrier
        # the earliest min_replicas workers form a quorum alone and every
        # late joiner enters through a heal-against-a-moving-cluster (the
        # failover_cells lesson).  The driver writes "go" once every group is
        # constructed, so the FIRST quorum contains all N.
        with open(os.path.join(workdir, f"ready_{cfg['group']}"), "w"):
            pass
        go_deadline = time.time() + 180.0
        go_path = os.path.join(workdir, "go")
        while time.time() < go_deadline and not os.path.exists(go_path):
            time.sleep(0.05)
        while time.time() < end_cap and not os.path.exists(stop_path):
            # A transient control-plane fault (quorum RPC timeout riding a
            # CPU-starved tick, a busy donor window mid-heal) must count as
            # a failed step and RETRY, not kill the worker — worker death
            # on recoverable faults is exactly what this harness exists to
            # flush out.
            try:
                manager.start_quorum()
                time.sleep(step_s)  # the "train step"
                if manager.should_commit():
                    commits += 1
                else:
                    failed += 1
            except Exception:  # noqa: BLE001
                failed += 1
                time.sleep(step_s)
        # Uncounted linger: siblings' final counted quorums — started a
        # tick before ours ended — need our join to form.  Bounded because
        # a preemption wave's victims never write their done files.
        with open(os.path.join(workdir, f"done_{cfg['group']}"), "w"):
            pass
        linger_deadline = time.time() + 12.0
        while time.time() < linger_deadline:
            if all(
                os.path.exists(os.path.join(workdir, f"done_{g}"))
                for g in range(groups)
            ):
                break
            try:
                manager.start_quorum()
                time.sleep(step_s)
                manager.should_commit()
            except Exception:  # noqa: BLE001 — teardown races are benign
                break
    finally:
        summary = {"group": cfg["group"], "commits": commits, "failed": failed}
        print("SCALE_WORKER " + json.dumps(summary), flush=True)
        manager.shutdown()


# ---------------------------------------------------------------------------
# Child: one regional lighthouse (re-entered subprocess, federated sweep)
# ---------------------------------------------------------------------------


def _child_main(cfg: Dict) -> None:
    """One regional CHILD lighthouse as its own OS process — the federated
    sweep's region tier (docs/wire.md "Federation").  Owns its region's
    heartbeats/sentinels/ledger and pushes digests to the in-driver root;
    publishes its addresses through an atomically-renamed info file, then
    idles until the cell's stop file (or SIGKILL, for the region-wave
    victim: the root must detect the silence, not a clean goodbye)."""
    from torchft_tpu._native import LighthouseServer

    server = LighthouseServer(
        bind="127.0.0.1:0",
        http_bind="127.0.0.1:0",
        # Advisory: a child never forms the quorum — the ROOT's floor gates.
        min_replicas=1,
        join_timeout_ms=int(cfg.get("join_timeout_ms", 10000)),
        quorum_tick_ms=int(cfg.get("quorum_tick_ms", 50)),
        heartbeat_timeout_ms=int(cfg.get("heartbeat_timeout_ms", 3000)),
    )
    server.set_federation(
        cfg["region"], cfg["root"], int(cfg.get("push_ms", 100))
    )
    info = {
        "region": cfg["region"],
        "addr": server.address(),
        "http": server.http_address(),
    }
    path = os.path.join(cfg["workdir"], f"child_{cfg['region']}.json")
    with open(path + ".tmp", "w", encoding="utf-8") as f:
        json.dump(info, f)
    os.replace(path + ".tmp", path)
    # Children wait for their OWN stop file, written only after every
    # worker exited: a child dying at the workers' stop signal would fail
    # the in-flight quorum calls of workers mid-step — phantom "failed
    # commits" charged to teardown, not the control plane.
    stop_path = os.path.join(cfg["workdir"], "stop_children")
    end_cap = float(cfg["end_cap_ts"])
    while time.time() < end_cap and not os.path.exists(stop_path):
        time.sleep(0.1)
    server.shutdown()


# ---------------------------------------------------------------------------
# Scrape parsing
# ---------------------------------------------------------------------------


def _scrape(http_address: str, path: str, timeout: float = 5.0) -> Optional[str]:
    import urllib.request

    url = http_address if http_address.startswith("http") else f"http://{http_address}"
    try:
        with urllib.request.urlopen(url + path, timeout=timeout) as resp:
            return resp.read().decode()
    except Exception:  # noqa: BLE001 — poller; absence is an answer
        return None


def _hist_stats(text: str, name: str, label: str = "") -> Dict[str, Any]:
    """``{count, mean_ms}`` for one Prometheus histogram family (``label``
    filters a labelled series, e.g. ``method="Quorum"``)."""
    total: Optional[float] = None
    count: Optional[float] = None
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        rest = line[len(name):]
        if rest.startswith("_sum") and (not label or label in rest):
            total = float(line.rsplit(" ", 1)[1])
        elif rest.startswith("_count") and (not label or label in rest):
            count = float(line.rsplit(" ", 1)[1])
    if not count:
        return {"count": 0, "mean_ms": None}
    return {"count": int(count), "mean_ms": round(1e3 * (total or 0.0) / count, 3)}


# ---------------------------------------------------------------------------
# Control-plane cell
# ---------------------------------------------------------------------------


def run_control_cell(
    workdir: str,
    groups: int,
    window_s: float = 10.0,
    step_s: float = 0.1,
    wave: int = 0,
    # Generous vs the 100 ms-cadence default: on a saturated small host a
    # worker process can be scheduler-starved for seconds, and a spuriously
    # staled heartbeat lets a subset quorum form that drags the starved
    # group through a heal the cell never meant to measure.
    heartbeat_timeout_ms: int = 3000,
    quorum_tick_ms: int = 50,
    worker_env: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    """One N-group control-plane cell.  ``wave`` > 0 SIGKILLs that many
    groups (the highest-numbered ones) inside one tight window mid-run and
    requires the survivors to reform a quorum and keep committing, the
    flight-recorder dump to reconstruct the transition, and the driver to
    leak zero fds across the whole cell."""
    from torchft_tpu._native import LighthouseServer
    from torchft_tpu.obs import flight as obs_flight
    from torchft_tpu.obs import report as obs_report

    os.makedirs(workdir, exist_ok=True)
    metrics_path = os.path.join(workdir, "metrics.jsonl")
    gc.collect()
    fd_before = fd_count()
    prior_flight = os.environ.get("TPUFT_FLIGHT_DIR")
    os.environ["TPUFT_FLIGHT_DIR"] = workdir
    survivors = list(range(groups - wave))
    victims = list(range(groups - wave, groups))
    result: Dict[str, Any] = {
        "section": "scale_control",
        "groups": groups,
        "window_s": window_s,
        "step_s": step_s,
        "wave": wave,
        "min_replicas": max(1, groups - wave),
        "ok": False,
    }
    workers: List[subprocess.Popen] = []
    lighthouse = None
    try:
        lighthouse = LighthouseServer(
            bind="127.0.0.1:0",
            http_bind="127.0.0.1:0",
            # A wave cell's floor must be satisfiable by the survivors or
            # the post-wave quorum can never form; clean cells pin the full
            # count so the first quorum contains everyone.
            min_replicas=max(1, groups - wave),
            # Generous: every worker heartbeats from construction (before
            # the go barrier), so a long join wait only delays formation
            # while a LIVE member's join is still in flight — on a
            # saturated host the unluckiest first join can lag seconds,
            # and a quorum formed without it drags that group through a
            # heal this cell never meant to measure.  Post-wave
            # reformation is unaffected: SIGKILLed victims stop
            # heartbeating, and once they stale out the all-joined check
            # forms the survivor quorum without waiting out this timeout.
            join_timeout_ms=10000 + 500 * groups,
            quorum_tick_ms=quorum_tick_ms,
            heartbeat_timeout_ms=heartbeat_timeout_ms,
        )
        http = lighthouse.http_address()
        env = script_env({"TPUFT_METRICS_PATH": metrics_path, **(worker_env or {})})
        # Hard ceiling well past the window: worker startup at N=32 on a
        # small host serializes ~0.5 s of interpreter+numpy import each,
        # and a wave cell's counted phase additionally spans the driver's
        # reformation wait (which can include a straggler-recovery cycle).
        end_cap = time.time() + window_s + 60.0 + 1.2 * groups + (
            240.0 if wave > 0 else 0.0
        )
        log_paths = []
        for g in range(groups):
            cfg = {
                "group": g,
                "groups": groups,
                "lighthouse": lighthouse.address(),
                "end_cap_ts": end_cap,
                "workdir": workdir,
                "step_s": step_s,
                # Steady-state quorums are sub-second; the budget only has
                # to ride out a post-wave reformation.  Shorter than the
                # worker default so ONE unlucky blocked join (a re-register
                # racing the formed round) costs the lockstep cluster 30 s,
                # not 60, before the abort-and-retry recovers it.
                "quorum_timeout_s": 30.0,
            }
            log_path = os.path.join(workdir, f"g{g}.log")
            log_paths.append(log_path)
            with open(log_path, "ab") as log:
                workers.append(
                    subprocess.Popen(
                        [sys.executable, os.path.abspath(__file__), "--worker",
                         json.dumps(cfg)],
                        env=env,
                        stdout=log,
                        stderr=subprocess.STDOUT,
                        cwd=REPO,
                    )
                )

        def commits_per_group() -> Dict[str, List[float]]:
            return obs_report.commit_timelines(
                obs_report.read_events([metrics_path])
            )

        # Barrier: wait for every worker's ready file AND for the
        # lighthouse to have all N heartbeats on file, then release the
        # workers together.  The heartbeat half is what makes this sound:
        # the lighthouse's straggler wait and split-brain guard only cover
        # replicas it can SEE — a constructed-but-not-yet-heartbeating
        # group is invisible, the all-joined check short-circuits without
        # it, and the resulting subset quorum drags it through a heal at
        # step 0.  With all N heartbeats pre-registered, formation
        # provably waits for every live join (up to join_timeout).
        ready_deadline = time.time() + 60.0 + 1.5 * groups
        while time.time() < ready_deadline:
            if all(
                os.path.exists(os.path.join(workdir, f"ready_{g}"))
                for g in range(groups)
            ):
                status = _scrape(http, "/status.json") or "{}"
                try:
                    seen = json.loads(status).get("heartbeat_age_ms", {})
                except ValueError:
                    seen = {}
                if len({str(k).split(":", 1)[0] for k in seen}) >= groups:
                    break
            time.sleep(0.1)
        with open(os.path.join(workdir, "go"), "w"):
            pass

        # Warm-up: every group must have a commit timeline before the
        # counted phenomena (wave, histogram reads) mean anything.
        t0 = time.time()
        warm_deadline = t0 + 60.0 + 1.2 * groups
        while time.time() < warm_deadline:
            cs = commits_per_group()
            if all(len(cs.get(str(g), [])) >= 3 for g in range(groups)):
                break
            time.sleep(0.25)
        cs = commits_per_group()
        result["warmed_groups"] = sum(
            1 for g in range(groups) if len(cs.get(str(g), [])) >= 3
        )
        result["warmup_s"] = round(time.time() - t0, 2)

        # Prime the scrape-cost histogram (self-observed AFTER render: the
        # cost of scrape k is visible from scrape k+1).
        for _ in range(3):
            _scrape(http, "/metrics")

        wave_ts = None
        if wave > 0:
            # THE FAULT: a correlated preemption wave — SIGKILL `wave`
            # groups back-to-back, the spot-reclaim shape where one
            # maintenance event takes out a whole capacity block.
            wave_ts = time.time()
            for g in victims:
                try:
                    workers[g].send_signal(signal.SIGKILL)
                except OSError:
                    pass
            for g in victims:
                workers[g].wait()
            result["wave_ts"] = wave_ts
            result["wave_kill_span_s"] = round(time.time() - wave_ts, 3)
            # Reformation evidence: every survivor commits >= 2 more steps
            # AFTER the wave (requires a formed post-wave quorum).
            base = {
                g: len(commits_per_group().get(str(g), [])) for g in survivors
            }
            # Generous: covers heartbeat staling + rejoin fan-in, PLUS one
            # full straggler-recovery cycle — a survivor whose rejoin races
            # the formed round blocks for its quorum timeout, and the
            # lockstep cluster (correctly) waits for it before committing
            # again.  The cell's evidence for "reformed" is every survivor
            # committing post-wave, which includes riding out that cycle.
            reform_deadline = time.time() + 90.0 + 2 * 30.0
            reformed = False
            while time.time() < reform_deadline and not reformed:
                cs = commits_per_group()
                reformed = all(
                    len([t for t in cs.get(str(g), []) if t > wave_ts]) >= 2
                    for g in survivors
                )
                time.sleep(0.25)
            result["quorum_reformed"] = reformed
            if reformed:
                cs = commits_per_group()
                # First commit every survivor lands after the wave — an
                # upper bound on disruption, but it can ride the PRE-wave
                # quorum; the honest reformation latency comes from the
                # flight recorder's shrunken-quorum transition below.
                first_post = max(
                    min(t for t in cs[str(g)] if t > wave_ts) for g in survivors
                )
                result["first_commit_after_wave_s"] = round(first_post - wave_ts, 3)
            del base

        # Let the counted window run out, then stop everyone together.
        time.sleep(max(0.0, (t0 + result["warmup_s"] + window_s) - time.time()))
        with open(os.path.join(workdir, "stop"), "w"):
            pass
        for g, w in enumerate(workers):
            if g in victims:
                continue
            try:
                # Budget for the worst exit path: the LAST lingering worker
                # can block a full quorum_timeout (60 s) in its final
                # start_quorum once its siblings exited (min_replicas can
                # no longer be met), plus the 12 s linger bound.
                w.wait(timeout=110.0)
            except subprocess.TimeoutExpired:
                w.kill()
                w.wait()

        summaries = []
        for path in log_paths:
            with open(path, "rb") as f:
                for line in f:
                    if line.startswith(b"SCALE_WORKER "):
                        summaries.append(json.loads(line[len(b"SCALE_WORKER "):]))
        result["worker_summaries"] = sorted(summaries, key=lambda s: s["group"])
        result["survivor_failed_commits"] = sum(
            s["failed"] for s in summaries if s["group"] in survivors
        )

        cs = commits_per_group()
        result["per_group_commits"] = {g: len(ts) for g, ts in sorted(cs.items())}
        if wave > 0 and wave_ts is not None:
            result["post_wave_commits"] = {
                str(g): len([t for t in cs.get(str(g), []) if t > wave_ts])
                for g in survivors
            }

        # Control-plane cost vs N, from the PR 7 native histograms.
        final = _scrape(http, "/metrics") or ""
        with open(os.path.join(workdir, "final.metrics"), "w") as f:
            f.write(final)
        result["quorum_formation"] = _hist_stats(
            final, "tpuft_quorum_formation_seconds"
        )
        result["heartbeat_fanin"] = _hist_stats(
            final, "tpuft_heartbeat_fanin_seconds"
        )
        result["scrape"] = _hist_stats(final, "tpuft_metrics_scrape_seconds")
        result["rpc"] = {
            m: _hist_stats(final, "tpuft_rpc_latency_seconds", f'method="{m}"')
            for m in ("Quorum", "Heartbeat")
        }
        result["scrape_bytes"] = len(final)
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
                w.wait()
        if lighthouse is not None:
            lighthouse.shutdown()  # writes the flight dump into workdir
        if prior_flight is None:
            os.environ.pop("TPUFT_FLIGHT_DIR", None)
        else:
            os.environ["TPUFT_FLIGHT_DIR"] = prior_flight

    # Flight-recorder post-mortem: the dump must exist, parse, and (for a
    # wave cell) reconstruct the wave's quorum transitions.
    dumps = [
        os.path.join(workdir, f)
        for f in os.listdir(workdir)
        if f.startswith("flight_lighthouse_") and f.endswith(".json")
    ]
    result["flight_dump_found"] = bool(dumps)
    if dumps:
        dump = obs_flight.load_flight_dump(dumps[0])
        transitions = obs_flight.quorum_transitions(obs_flight.flight_events(dump))
        result["flight_transitions"] = len(transitions)
        if wave > 0 and wave_ts is not None:
            post = [
                t for t in transitions
                if t["ts_ms"] >= int(wave_ts * 1000) - 500
            ]
            # Replica ids carry per-incarnation uuid suffixes
            # ("<group>:<uuid>"); the reconstruction compares group prefixes.
            group_of = lambda m: str(m).split(":", 1)[0]  # noqa: E731
            left_union: set = set()
            for t in post:
                left_union.update(group_of(m) for m in t["left"])
            victim_ids = {str(g) for g in victims}
            survivor_ids = {str(g) for g in survivors}
            shrunk_ts = next(
                (t["ts_ms"] for t in post
                 if {group_of(m) for m in t["members"]} == survivor_ids),
                None,
            )
            result["wave_reconstructed"] = bool(
                victim_ids <= left_union and shrunk_ts is not None
            )
            if shrunk_ts is not None:
                # Quorum-reformation latency from the server's own record:
                # wave start to the formation of the survivors-only quorum.
                result["wave_reform_s"] = round(shrunk_ts / 1000.0 - wave_ts, 3)
            result["wave_transitions"] = [
                {k: t[k] for k in ("quorum_id", "members", "joined", "left")}
                for t in post[:8]
            ]

    # fd hygiene: everything the cell opened (lighthouse, scrape sockets,
    # worker pipes, log handles) must be closed.  Settle loop because
    # socket close under load is not instantaneous.
    fd_after = fd_count()
    settle = time.time() + 5.0
    while fd_after > fd_before and time.time() < settle:
        gc.collect()
        time.sleep(0.2)
        fd_after = fd_count()
    result["fd_before"] = fd_before
    result["fd_after"] = fd_after
    result["fd_leaked"] = max(0, fd_after - fd_before) if fd_before >= 0 else None

    # Commit evidence from the METRICS STREAM, not the worker summary
    # lines: a lingering worker killed at the driver's wait deadline loses
    # its stdout summary, but its commits are already durably in the
    # stream.
    stream_commits = result.get("per_group_commits", {})
    all_committed = all(
        stream_commits.get(str(g), 0) > 0 for g in survivors
    )
    result["ok"] = bool(
        result.get("warmed_groups") == groups
        and all_committed
        and result.get("flight_dump_found")
        and (wave == 0 or (result.get("quorum_reformed")
                           and result.get("wave_reconstructed")))
        and (result.get("fd_leaked") in (0, None))
    )
    return result


# ---------------------------------------------------------------------------
# Federated control-plane cell (two-tier: regional children + one root)
# ---------------------------------------------------------------------------


def run_federated_cell(
    workdir: str,
    groups: int,
    regions: int,
    window_s: float = 8.0,
    step_s: float = 0.1,
    kill: int = 0,
    push_ms: int = 100,
    heartbeat_timeout_ms: int = 3000,
    quorum_tick_ms: int = 50,
) -> Dict[str, Any]:
    """One federated control-plane cell: ``regions`` child-lighthouse
    SUBPROCESSES (wire-method-8 digest pushers), one in-driver root, and
    ``groups`` worker subprocesses running the unchanged flat Manager
    loop against their region's child — the managers never learn the
    root exists.  Measures per-instance heartbeat fan-in (children see
    only their region; the root sees ZERO heartbeats) and scrape cost vs
    N.  ``kill`` SIGKILLs that many individual workers (the highest
    numbered) mid-window and requires: survivors reform the global quorum
    with ZERO failed commits, and the root/child digest views stay
    consistent.  Group g lives in region g // (groups // regions)."""
    from torchft_tpu._native import LighthouseServer
    from torchft_tpu.obs import flight as obs_flight
    from torchft_tpu.obs import report as obs_report

    assert groups % regions == 0, "groups must divide evenly across regions"
    # Barrier files from a previous run in the same workdir would trip
    # this cell (a leftover ``stop`` ends workers instantly; stale
    # child_*.json points at dead lighthouses) — scrub them up front.
    for leftover in (
        glob.glob(os.path.join(workdir, "child_*.json"))
        + glob.glob(os.path.join(workdir, "ready_*"))
        + glob.glob(os.path.join(workdir, "done_*"))
        + [os.path.join(workdir, n) for n in ("stop", "stop_children", "go")]
    ):
        try:
            os.unlink(leftover)
        except OSError:
            pass
    per_region = groups // regions
    region_names = [f"r{i}" for i in range(regions)]
    region_of = lambda g: region_names[g // per_region]  # noqa: E731
    victims = list(range(groups - kill, groups)) if kill else []
    survivors = [g for g in range(groups) if g not in victims]
    surviving_regions = sorted({region_of(g) for g in survivors})

    os.makedirs(workdir, exist_ok=True)
    childdir = os.path.join(workdir, "children")
    os.makedirs(childdir, exist_ok=True)
    metrics_path = os.path.join(workdir, "metrics.jsonl")
    gc.collect()
    fd_before = fd_count()
    prior_flight = os.environ.get("TPUFT_FLIGHT_DIR")
    os.environ["TPUFT_FLIGHT_DIR"] = workdir
    result: Dict[str, Any] = {
        "section": "scale_federated",
        "groups": groups,
        "regions": regions,
        "per_region": per_region,
        "window_s": window_s,
        "step_s": step_s,
        "kill": len(victims),
        "min_replicas": max(1, len(survivors)),
        "ok": False,
    }
    workers: List[subprocess.Popen] = []
    children: Dict[str, subprocess.Popen] = {}
    child_info: Dict[str, Dict[str, str]] = {}
    root = None
    try:
        root = LighthouseServer(
            bind="127.0.0.1:0",
            http_bind="127.0.0.1:0",
            # Satisfiable by the survivors (wave) / everyone (clean); the
            # ready barrier below is what makes the FIRST quorum global.
            min_replicas=max(1, len(survivors)),
            join_timeout_ms=10000 + 500 * groups,
            quorum_tick_ms=quorum_tick_ms,
            heartbeat_timeout_ms=heartbeat_timeout_ms,
        )
        root_http = root.http_address()
        end_cap = time.time() + window_s + 90.0 + 1.5 * groups + (
            240.0 if victims else 0.0
        )
        child_env = script_env({"TPUFT_FLIGHT_DIR": childdir})  # keep root's dump unambiguous
        for name in region_names:
            ccfg = {
                "region": name,
                "root": root.address(),
                "workdir": workdir,
                "push_ms": push_ms,
                "end_cap_ts": end_cap,
                "heartbeat_timeout_ms": heartbeat_timeout_ms,
                "quorum_tick_ms": quorum_tick_ms,
            }
            log = open(os.path.join(workdir, f"child_{name}.log"), "ab")
            try:
                children[name] = subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__), "--child",
                     json.dumps(ccfg)],
                    env=child_env, stdout=log, stderr=subprocess.STDOUT,
                    cwd=REPO,
                )
            finally:
                log.close()
        info_deadline = time.time() + 60.0
        while time.time() < info_deadline and len(child_info) < regions:
            for name in region_names:
                if name in child_info:
                    continue
                path = os.path.join(workdir, f"child_{name}.json")
                if os.path.exists(path):
                    with open(path, "r", encoding="utf-8") as f:
                        child_info[name] = json.load(f)
            time.sleep(0.05)
        if len(child_info) < regions:
            raise RuntimeError(
                f"only {len(child_info)}/{regions} child lighthouses came up"
            )

        env = script_env({"TPUFT_METRICS_PATH": metrics_path})
        log_paths = []
        for g in range(groups):
            cfg = {
                "group": g,
                "groups": groups,
                "lighthouse": child_info[region_of(g)]["addr"],
                "end_cap_ts": end_cap,
                "workdir": workdir,
                "step_s": step_s,
                "quorum_timeout_s": 30.0,
            }
            log_path = os.path.join(workdir, f"g{g}.log")
            log_paths.append(log_path)
            with open(log_path, "ab") as log:
                workers.append(
                    subprocess.Popen(
                        [sys.executable, os.path.abspath(__file__), "--worker",
                         json.dumps(cfg)],
                        env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
                    )
                )

        def commits_per_group() -> Dict[str, List[float]]:
            return obs_report.commit_timelines(
                obs_report.read_events([metrics_path])
            )

        def root_rollup() -> Dict[str, Dict[str, Any]]:
            doc = _scrape(root_http, "/regions.json") or "{}"
            try:
                rows = json.loads(doc).get("regions", [])
            except ValueError:
                rows = []
            return {r.get("region"): r for r in rows}

        # Barrier: every worker constructed AND every heartbeat visible at
        # the ROOT — which, federated, means it already rode a digest up:
        # the rollup's replicas_total is the root's own count, so the
        # first global quorum provably waits for all N (same soundness
        # argument as the flat cell, one tier removed).
        ready_deadline = time.time() + 90.0 + 1.5 * groups
        while time.time() < ready_deadline:
            if all(
                os.path.exists(os.path.join(workdir, f"ready_{g}"))
                for g in range(groups)
            ):
                rollup = root_rollup()
                if sum(
                    int(r.get("replicas_total", 0)) for r in rollup.values()
                ) >= groups:
                    break
            time.sleep(0.1)
        with open(os.path.join(workdir, "go"), "w"):
            pass

        t0 = time.time()
        warm_deadline = t0 + 90.0 + 1.5 * groups
        while time.time() < warm_deadline:
            cs = commits_per_group()
            if all(len(cs.get(str(g), [])) >= 3 for g in range(groups)):
                break
            time.sleep(0.25)
        cs = commits_per_group()
        result["warmed_groups"] = sum(
            1 for g in range(groups) if len(cs.get(str(g), [])) >= 3
        )
        result["warmup_s"] = round(time.time() - t0, 2)

        # Prime every instance's scrape-cost histogram.
        for _ in range(3):
            _scrape(root_http, "/metrics")
            for info in child_info.values():
                _scrape(info["http"], "/metrics")

        def digest_consistent() -> Dict[str, Any]:
            """Root's per-region digest view vs each surviving child's own
            rollup.  Retries briefly: totals legitimately diverge for one
            push interval after membership changes."""
            deadline = time.time() + 10.0
            last: Dict[str, Any] = {"ok": False}
            while time.time() < deadline:
                rollup = root_rollup()
                rows = []
                ok = True
                for name in surviving_regions:
                    cdoc = json.loads(
                        _scrape(child_info[name]["http"], "/regions.json")
                        or "{}"
                    )
                    crows = cdoc.get("regions") or [{}]
                    self_row = crows[0]
                    rrow = rollup.get(name) or {}
                    match = (
                        cdoc.get("role") == "child"
                        and int(self_row.get("replicas_total", -1))
                        == int(rrow.get("replicas_total", -2))
                        and not rrow.get("stale", True)
                    )
                    ok = ok and match
                    rows.append({
                        "region": name,
                        "child_total": self_row.get("replicas_total"),
                        "root_total": rrow.get("replicas_total"),
                        "root_stale": rrow.get("stale"),
                        "match": match,
                    })
                last = {"ok": ok, "rows": rows}
                if ok:
                    break
                time.sleep(0.5)
            return last

        result["digest_consistency_pre"] = digest_consistent()

        wave_ts = None
        if victims:
            # THE FAULT: SIGKILL the victims' worker processes.
            wave_ts = time.time()
            for g in victims:
                try:
                    workers[g].send_signal(signal.SIGKILL)
                except OSError:
                    pass
            for g in victims:
                workers[g].wait()
            result["wave_ts"] = wave_ts

            # Reformation: every survivor commits >= 2 AFTER the fault.
            reform_deadline = time.time() + 90.0 + 2 * 30.0
            reformed = False
            while time.time() < reform_deadline and not reformed:
                cs = commits_per_group()
                reformed = all(
                    len([t for t in cs.get(str(g), []) if t > wave_ts]) >= 2
                    for g in survivors
                )
                time.sleep(0.25)
            result["quorum_reformed"] = reformed
            if reformed:
                cs = commits_per_group()
                first_post = max(
                    min(t for t in cs[str(g)] if t > wave_ts)
                    for g in survivors
                )
                result["first_commit_after_wave_s"] = round(
                    first_post - wave_ts, 3
                )
            result["digest_consistency_post"] = digest_consistent()

        time.sleep(max(0.0, (t0 + result["warmup_s"] + window_s) - time.time()))

        # Per-instance control-plane cost BEFORE teardown: the federated
        # claim is that no instance's load scales with N — children see
        # only their region's heartbeat fan-in, the root sees none at all
        # (digests only), and every scrape payload is bounded by the
        # instance's own region.
        per_instance: Dict[str, Any] = {}
        final_root = _scrape(root_http, "/metrics") or ""
        per_instance["root"] = {
            "heartbeat_fanin": _hist_stats(
                final_root, "tpuft_heartbeat_fanin_seconds"
            ),
            "scrape": _hist_stats(final_root, "tpuft_metrics_scrape_seconds"),
            "scrape_bytes": len(final_root),
            "rpc_region_digest": _hist_stats(
                final_root, "tpuft_rpc_latency_seconds", 'method="RegionDigest"'
            ),
            "rpc_heartbeat": _hist_stats(
                final_root, "tpuft_rpc_latency_seconds", 'method="Heartbeat"'
            ),
        }
        per_instance["children"] = {}
        for name in surviving_regions:
            text = _scrape(child_info[name]["http"], "/metrics") or ""
            per_instance["children"][name] = {
                "heartbeat_fanin": _hist_stats(
                    text, "tpuft_heartbeat_fanin_seconds"
                ),
                "scrape": _hist_stats(text, "tpuft_metrics_scrape_seconds"),
                "scrape_bytes": len(text),
            }
        result["per_instance"] = per_instance
        fanins = [
            c["heartbeat_fanin"]["count"]
            for c in per_instance["children"].values()
        ]
        result["root_heartbeat_rpcs"] = per_instance["root"]["rpc_heartbeat"][
            "count"
        ]
        result["max_child_fanin_count"] = max(fanins) if fanins else 0

        with open(os.path.join(workdir, "stop"), "w"):
            pass
        for g, w in enumerate(workers):
            if g in victims:
                continue
            try:
                w.wait(timeout=110.0)
            except subprocess.TimeoutExpired:
                w.kill()
                w.wait()
        with open(os.path.join(workdir, "stop_children"), "w"):
            pass
        for proc in children.values():
            try:
                proc.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()

        summaries = []
        for path in log_paths:
            with open(path, "rb") as f:
                for line in f:
                    if line.startswith(b"SCALE_WORKER "):
                        summaries.append(
                            json.loads(line[len(b"SCALE_WORKER "):])
                        )
        result["worker_summaries"] = sorted(summaries, key=lambda s: s["group"])
        result["survivor_failed_commits"] = sum(
            s["failed"] for s in summaries if s["group"] in survivors
        )
        cs = commits_per_group()
        result["per_group_commits"] = {
            g: len(ts) for g, ts in sorted(cs.items())
        }
        if victims and wave_ts is not None:
            result["post_wave_commits"] = {
                str(g): len([t for t in cs.get(str(g), []) if t > wave_ts])
                for g in survivors
            }
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
                w.wait()
        for proc in children.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if root is not None:
            root.shutdown()  # writes the flight dump into workdir
        if prior_flight is None:
            os.environ.pop("TPUFT_FLIGHT_DIR", None)
        else:
            os.environ["TPUFT_FLIGHT_DIR"] = prior_flight

    # Flight-recorder post-mortem on the ROOT's dump (children dump into
    # their own subdir): the global quorum transitions must reconstruct
    # the fault — members N -> survivors with the victims in `left`.
    dumps = [
        os.path.join(workdir, f)
        for f in os.listdir(workdir)
        if f.startswith("flight_lighthouse_") and f.endswith(".json")
    ]
    result["flight_dump_found"] = bool(dumps)
    if dumps and victims and wave_ts is not None:
        dump = obs_flight.load_flight_dump(dumps[0])
        transitions = obs_flight.quorum_transitions(
            obs_flight.flight_events(dump)
        )
        result["flight_transitions"] = len(transitions)
        group_of = lambda m: str(m).split(":", 1)[0]  # noqa: E731
        post = [
            t for t in transitions if t["ts_ms"] >= int(wave_ts * 1000) - 500
        ]
        left_union: set = set()
        for t in post:
            left_union.update(group_of(m) for m in t["left"])
        victim_ids = {str(g) for g in victims}
        survivor_ids = {str(g) for g in survivors}
        shrunk = next(
            (t for t in post
             if {group_of(m) for m in t["members"]} == survivor_ids),
            None,
        )
        result["wave_reconstructed"] = bool(
            victim_ids <= left_union and shrunk is not None
        )
        if shrunk is not None:
            result["wave_reform_s"] = round(
                shrunk["ts_ms"] / 1000.0 - wave_ts, 3
            )

    fd_after = fd_count()
    settle = time.time() + 5.0
    while fd_after > fd_before and time.time() < settle:
        gc.collect()
        time.sleep(0.2)
        fd_after = fd_count()
    result["fd_before"] = fd_before
    result["fd_after"] = fd_after
    result["fd_leaked"] = (
        max(0, fd_after - fd_before) if fd_before >= 0 else None
    )

    stream_commits = result.get("per_group_commits", {})
    all_committed = all(
        stream_commits.get(str(g), 0) > 0 for g in survivors
    )
    fault_ok = True
    if victims:
        fault_ok = bool(
            result.get("quorum_reformed")
            and result.get("survivor_failed_commits") == 0
            and result.get("digest_consistency_post", {}).get("ok")
        )
    result["ok"] = bool(
        result.get("warmed_groups") == groups
        and all_committed
        and result.get("digest_consistency_pre", {}).get("ok")
        and result.get("flight_dump_found")
        and result.get("root_heartbeat_rpcs") == 0
        and fault_ok
        and (result.get("fd_leaked") in (0, None))
    )
    return result


def run_federated_quick() -> Dict[str, Any]:
    """The federation smoke (tests/test_federation.py::
    test_federation_quick_smoke): 2 regions x 2 groups through real
    child subprocesses, one worker SIGKILLed mid-window; gates on digest
    consistency across the kill, the survivors' reformed global quorum,
    and ZERO failed survivor commits."""
    workdir = tempfile.mkdtemp(prefix="tpuft_fed_quick_")
    cell = run_federated_cell(
        workdir, groups=4, regions=2, window_s=4.0, step_s=0.1, kill=1,
        push_ms=100,
    )
    return {"workdir": workdir, "cells": [cell], "ok": cell["ok"]}


# ---------------------------------------------------------------------------
# Topology parity (in-process, cheap — the quick smoke's correctness gate)
# ---------------------------------------------------------------------------


def topology_parity_check(world: int = 4) -> Dict[str, Any]:
    """Same inputs through the flat ring and ring2d at ``world`` in-process
    thread ranks: results must agree within f32 reassociation tolerance,
    each topology must be replica-consistent (bitwise across ranks), and
    int payloads must bypass wire compression on both."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from torchft_tpu._native import StoreServer
    from torchft_tpu.collectives import TCPCollective

    rng = np.random.default_rng(29)
    fdata = [rng.standard_normal(4096).astype(np.float32) for _ in range(world)]
    idata = [np.arange(512, dtype=np.int64) * (r + 1) for r in range(world)]
    store = StoreServer(bind="127.0.0.1:0")
    out: Dict[str, Any] = {"world": world}
    try:
        def run(topology: str, tag: str):
            prefix = f"{store.address()}/parity_{tag}"
            results: Dict[int, Any] = {}

            def worker(rank: int) -> None:
                c = TCPCollective(timeout=20.0, lanes=2, topology=topology,
                                  wire_dtype="bf16", chunk_bytes=4 << 10)
                try:
                    c.configure(prefix, rank, world)
                    f = c.allreduce([fdata[rank].copy()], op="sum").wait(timeout=30)[0]
                    i = c.allreduce([idata[rank].copy()], op="sum").wait(timeout=30)[0]
                    results[rank] = (f, i, c.topology)
                finally:
                    c.shutdown()

            with ThreadPoolExecutor(max_workers=world) as pool:
                for fut in [pool.submit(worker, r) for r in range(world)]:
                    fut.result(timeout=60)
            return results

        ring = run("ring", "ring")
        r2d = run("ring2d", "ring2d")
        out["ring2d_active"] = r2d[0][2] == "ring2d"
        import numpy as np

        int_exact = all(
            np.array_equal(r2d[r][1], np.arange(512, dtype=np.int64)
                           * sum(range(1, world + 1)))
            for r in range(world)
        )
        replica_consistent = all(
            np.array_equal(r2d[r][0], r2d[0][0]) for r in range(world)
        ) and all(np.array_equal(ring[r][0], ring[0][0]) for r in range(world))
        # bf16 per-hop re-quantization envelope between topologies.
        close = np.allclose(
            np.asarray(r2d[0][0], np.float32), np.asarray(ring[0][0], np.float32),
            rtol=0.02, atol=0.05 * world,
        )
        out["int_bypass_ok"] = bool(int_exact)
        out["replica_consistent"] = bool(replica_consistent)
        out["topologies_close"] = bool(close)
        out["ok"] = bool(out["ring2d_active"] and int_exact
                         and replica_consistent and close)
    finally:
        store.shutdown()
    return out


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


def run_quick() -> Dict[str, Any]:
    """The smoke's shape: topology parity at 4 in-process ranks, then a
    4-group control cell with a 2-victim preemption wave under a PINNED
    ring2d topology — the post-wave 2-group world crosses the auto
    crossover back to the flat ring, so the smoke exercises the
    reconfigure-across-topologies path end to end."""
    workdir = tempfile.mkdtemp(prefix="tpuft_scale_quick_")
    fd_before = fd_count()
    parity = topology_parity_check(world=4)
    cell = run_control_cell(
        workdir,
        groups=4,
        window_s=5.0,
        step_s=0.1,
        wave=2,
        worker_env={"TPUFT_RING_TOPOLOGY": "ring2d"},
    )
    gc.collect()
    fd_after = fd_count()
    return {
        "parity": parity,
        "cells": [cell],
        "workdir": workdir,
        "fd_leaked_total": (
            max(0, fd_after - fd_before) if fd_before >= 0 else None
        ),
        "ok": bool(parity["ok"] and cell["ok"]),
    }


if __name__ == "__main__":
    # Worker / child entry only: the cells above start this file as a script.
    role, cfg = sys.argv[1], json.loads(sys.argv[2])
    {"--worker": _worker_main, "--child": _child_main}[role](cfg)
