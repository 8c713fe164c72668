"""Live cells of the heal path, driven by
tests/test_integration_smokes.py: how a recovering replica gets its state
when donors are healthy, when one dies mid-fetch, and when all of them are
gone.  What a cell returns is counts and booleans (bytes fetched, bitwise
equality, reconstructions, commits), never a time or a rate.

  http/donors=N  -- striped multi-donor fetch: N donor transports each serve
                    the full snapshot, the receiver pulls disjoint
                    byte-balanced stripes from all of them in parallel,
                    plus a failover trial whose first donor is dead.
  ec_*           -- erasure-coded peer state (torchft_tpu/ec): the encode
                    runs on the snapshotter and not the train thread,
                    any-k-of-(k+m) reconstruction is bitwise, a SIGKILLed
                    donor set is survived from the shard holders, and a
                    manager-level prefer-mode wave heals with the survivors
                    committing.

The wave cells' donors, holders and managers are this file run as a script
(``--worker``, see the end): nothing a person would run.
"""

from __future__ import annotations

import types
import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from harness import script_env


def make_state_dict(total_bytes: int, n_buffers: int) -> Dict[str, np.ndarray]:
    """n_buffers float32 arrays summing to ~total_bytes (the reference uses a
    dict of equal CUDA tensors; host numpy is the TPU-side unit of transfer)."""
    per = max(1, total_bytes // n_buffers // 4)
    return {
        f"layer_{i}.weight": np.full((per,), float(i), dtype=np.float32)
        for i in range(n_buffers)
    }


def bench_http_multi_donor(
    state: Dict[str, np.ndarray], n_donors: int, kill_donor: bool = False
) -> Dict[str, Any]:
    """Striped multi-donor heal: n_donors transports each serve the full
    snapshot, one receiver pulls disjoint byte-balanced stripes from all of
    them.  With ``kill_donor`` donor 0 is shut down before the fetch begins
    (its metadata still listed) -- the stripe-failover path must finish the
    heal on the survivors."""
    from torchft_tpu.checkpointing.http_transport import HTTPTransport

    donors = [HTTPTransport(timeout=120.0) for _ in range(n_donors)]
    dst = HTTPTransport(timeout=120.0)
    try:
        for d in donors:
            d.send_checkpoint([1], step=0, state_dict=state, timeout=120.0)
        for d in donors:
            assert d.wait_snapshot(120.0)
        metas = [d.metadata() for d in donors]
        if kill_donor:
            donors[0].shutdown()
        out = dst.recv_checkpoint(1, metas, step=0, timeout=120.0)
        assert set(out) == set(state)
        for k, v in state.items():
            np.testing.assert_array_equal(np.asarray(out[k]), v)
        return {
            "donors": n_donors,
            "donor_killed": kill_donor,
            "fetched_bytes": sum(np.asarray(a).nbytes for a in out.values()),
        }
    finally:
        for d in donors:
            d.shutdown()
        dst.shutdown()


# ---------------------------------------------------------------------------
# Erasure-coded peer state (torchft_tpu/ec): donor-free healing cells
# ---------------------------------------------------------------------------


def bench_ec_encode(
    state: Dict[str, np.ndarray], k: int, m: int, steps: int = 8
) -> Dict[str, Any]:
    """Donor-side encode, off the train thread: a loop hands every step's
    state to the transport (``enqueue_snapshot``, what the Manager does
    after a commit) with the erasure plane hooked on.  The hook is wrapped
    here to note the thread each encode ran on: all of them must be the
    transport's snapshotter, none the caller's."""
    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.ec.store import ECConfig, ECPlane, ShardStore

    src = HTTPTransport(timeout=120.0)
    peer = HTTPTransport(timeout=120.0)
    plane = ECPlane(ECConfig(k=k, m=m), push_timeout=120.0)
    encode_threads: List[str] = []

    def hook(step: int, meta, buffers) -> None:
        encode_threads.append(threading.current_thread().name)
        plane.on_snapshot(step, meta, buffers)

    try:
        src.attach_shard_store(plane.store)
        src.set_snapshot_hook(hook)
        peer.attach_shard_store(ShardStore(retain=2))
        plane.set_peers([0, 1], ["self", peer.metadata()], 0)
        for i in range(1, steps + 1):
            src.enqueue_snapshot(i, state, serve=False)
        drained = src.wait_snapshot(300.0)
        latest = plane.store.latest_step()
        return {
            "op": "ec_encode",
            "k": k,
            "m": m,
            "enqueued": steps,
            "drained": bool(drained),
            "train_thread": threading.current_thread().name,
            "encode_threads": sorted(set(encode_threads)),
            "encode_calls": len(encode_threads),
            "latest_encoded_step": latest,
            "shards_held": len(plane.store.have(latest)) if latest >= 0 else 0,
        }
    finally:
        src.shutdown()
        peer.shutdown()


def bench_ec_reconstruct(
    state: Dict[str, np.ndarray], k: int, m: int
) -> Dict[str, Any]:
    """Any-k-of-(k+m) shard fetch + decode from k+m holders; ``bitwise``
    pins that the reconstructed buffers equal the donor stream
    byte-for-byte."""
    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.checkpointing.serialization import flatten_state_dict
    from torchft_tpu.ec.encoder import encode_stream
    from torchft_tpu.ec.placement import shard_holder
    from torchft_tpu.ec.store import ShardStore, reconstruct

    step = 1
    meta, bufs = flatten_state_dict(state, step=step)
    shards = encode_stream(meta, bufs, k, m, step=step)
    holders = [HTTPTransport(timeout=300.0) for _ in range(k + m)]
    try:
        ranks = list(range(k + m))
        stores = [ShardStore(retain=2) for _ in holders]
        for h, s in zip(holders, stores):
            h.attach_shard_store(s)
        for shard in shards:
            stores[shard_holder(step, shard.idx, ranks)].put(shard)
        urls = [h.metadata() for h in holders]
        _, bufs2, stats = reconstruct(urls, step, timeout=600.0)
        bitwise = len(bufs) == len(bufs2) and all(
            x.tobytes() == y.tobytes() for x, y in zip(bufs, bufs2)
        )
        return {
            "op": "ec_reconstruct",
            "k": k,
            "m": m,
            "holders": k + m,
            "shards_used": stats.get("shards_used"),
            "bitwise": bool(bitwise),
        }
    finally:
        for h in holders:
            h.shutdown()


def _spawn_worker(cfg: Dict[str, Any], env: Optional[Dict[str, str]] = None) -> subprocess.Popen:
    """This file as a script with one JSON configuration (see the end)."""
    env = script_env(env)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker", json.dumps(cfg)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def _wave_role_main(args) -> None:
    """Subprocess body for the donor-dead-wave cell: serve a checkpoint
    (donor) or a shard-store slice (holder) until killed."""
    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.checkpointing.serialization import flatten_state_dict
    from torchft_tpu.ec.encoder import encode_shards
    from torchft_tpu.ec.store import ShardStore

    state = make_state_dict(int(args.gb * 1e9), args.buffers)
    transport = HTTPTransport(timeout=300.0)
    if args.wave_role == "donor":
        transport.send_checkpoint([1], step=args.wstep, state_dict=state,
                                  timeout=300.0)
        transport.wait_snapshot(300.0)
    else:
        meta, bufs = flatten_state_dict(state, step=args.wstep)
        shards = encode_shards(
            meta, bufs, args.wk, args.wm, args.wstep, list(args.shards)
        )
        store = ShardStore(retain=2)
        for s in shards.values():
            store.put(s)
        transport.attach_shard_store(store)
    with open(args.out + ".tmp", "w") as f:
        f.write(transport.metadata())
    os.replace(args.out + ".tmp", args.out)
    while True:  # parent SIGKILLs us
        time.sleep(1.0)


def bench_ec_wave(
    gb: float,
    buffers: int,
    k: int,
    m: int,
    n_donors: int = 2,
    workdir: Optional[str] = None,
) -> Dict[str, Any]:
    """The donor-dead wave: REAL subprocess donors serving the max-step
    checkpoint are all SIGKILLed; the recovering side's striped donor
    fetch fails, and reconstruction completes from the k+m surviving
    shard-holder processes — bitwise-equal to the donor stream."""
    import tempfile

    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.checkpointing.serialization import flatten_state_dict
    from torchft_tpu.ec.placement import shards_for_holder
    from torchft_tpu.ec.store import reconstruct

    step = 1
    workdir = workdir or tempfile.mkdtemp(prefix="tpuft_ec_wave_")
    procs: List[subprocess.Popen] = []
    donor_procs: List[subprocess.Popen] = []
    try:
        paths: List[str] = []
        common = {"gb": gb, "buffers": buffers, "wk": k, "wm": m, "wstep": step}
        for d in range(n_donors):
            path = os.path.join(workdir, f"donor_{d}.url")
            paths.append(path)
            p = _spawn_worker(dict(common, wave_role="donor", out=path))
            procs.append(p)
            donor_procs.append(p)
        holder_ranks = list(range(k + m))
        for h in holder_ranks:
            own = shards_for_holder(step, h, holder_ranks, k + m)
            path = os.path.join(workdir, f"holder_{h}.url")
            paths.append(path)
            procs.append(
                _spawn_worker(
                    dict(common, wave_role="holder", out=path, shards=own)
                )
            )

        def await_url(path: str, timeout: float = 120.0) -> str:
            deadline = time.time() + timeout
            while time.time() < deadline:
                if os.path.exists(path):
                    with open(path) as f:
                        return f.read().strip()
                time.sleep(0.1)
            raise RuntimeError(f"worker never published {path}")

        donor_urls = [await_url(p) for p in paths[:n_donors]]
        holder_urls = [await_url(p) for p in paths[n_donors:]]

        # The wave: every donor SIGKILLed, then the heal is attempted.
        for p in donor_procs:
            p.send_signal(signal.SIGKILL)
        for p in donor_procs:
            p.wait(timeout=30)
        receiver = HTTPTransport(timeout=10.0)
        donor_fetch_failed = False
        try:
            receiver.recv_checkpoint(0, donor_urls, step=step, timeout=5.0)
        except Exception:  # noqa: BLE001 — the expected outcome
            donor_fetch_failed = True

        _, bufs2, stats = reconstruct(holder_urls, step, timeout=600.0)
        receiver.shutdown()
        state = make_state_dict(int(gb * 1e9), buffers)
        _, bufs = flatten_state_dict(state, step=step)
        bitwise = len(bufs) == len(bufs2) and all(
            x.tobytes() == y.tobytes() for x, y in zip(bufs, bufs2)
        )
        return {
            "op": "ec_wave",
            "state_dict_bytes": sum(a.nbytes for a in state.values()),
            "k": k,
            "m": m,
            "donors_sigkilled": n_donors,
            "donor_fetch_failed": donor_fetch_failed,
            "holders": k + m,
            "shards_used": stats.get("shards_used"),
            "bitwise": bool(bitwise),
            "ok": bool(donor_fetch_failed and bitwise),
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass


def _ec_manager_worker_main(args) -> None:
    """Subprocess body for the manager-level wave: one real Manager in a
    JAX-light control loop committing steps until the shared absolute
    deadline, erasure plane on (mode from env)."""
    import hashlib
    from datetime import timedelta

    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.manager import Manager

    state = {"w": np.zeros(256, np.float32)}

    def save():
        return {"w": state["w"]}

    def load(sd):
        state["w"] = np.asarray(sd["w"]).copy()

    manager = Manager(
        collective=TCPCollective(timeout=15.0),
        load_state_dict=load,
        state_dict=save,
        # 1, not groups: step 0 only commits with participant 0 alone (the
        # init-sync collapse makes every other group non-participating).
        min_replica_size=1,
        use_async_quorum=True,
        timeout=timedelta(seconds=15),
        quorum_timeout=timedelta(seconds=30),
        rank=0,
        world_size=1,
        replica_id=args.replica,
        checkpoint_transport=HTTPTransport(timeout=15.0),
    )
    commits = failed = 0
    healed_step = None
    while time.time() < args.end_ts and not os.path.exists(args.stop):
        manager.start_quorum()
        fut = manager.allreduce(np.ones(64, np.float32))
        fut.result()
        if manager._healing and healed_step is None:
            healed_step = manager.current_step()
        if manager.should_commit():
            commits += 1
            state["w"] = state["w"] + 1.0
        else:
            failed += 1
        time.sleep(args.step_s)
    payload = {
        "replica": args.replica,
        "commits": commits,
        "failed_commits": failed,
        "final_step": manager.current_step(),
        "healed_step": healed_step,
        "sha": hashlib.sha256(state["w"].tobytes()).hexdigest(),
    }
    with open(args.out + ".tmp", "w") as f:
        json.dump(payload, f)
    os.replace(args.out + ".tmp", args.out)
    manager.shutdown()


def _stream_count(path: str, event: str, **fields: Any) -> int:
    """How many records of one kind, with these fields, a worker's metrics
    stream holds so far (it is read while the worker writes it)."""
    if not os.path.exists(path):
        return 0
    n = 0
    with open(path, "rb") as f:
        for line in f:
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            n += ev.get("event") == event and all(ev.get(key) == v for key, v in fields.items())
    return n


def _wait_for(done, until_ts: float, poll_s: float = 0.1) -> bool:
    """Poll `done()` until it holds or the wall clock passes `until_ts`."""
    while not done():
        if time.time() > until_ts:
            return False
        time.sleep(poll_s)
    return True


def bench_ec_manager_wave(
    groups: int = 4,
    k: int = 2,
    m: int = 1,
    run_s: float = 90.0,
    kill_at_s: float = 2.0,
    respawn_after_s: float = 1.5,
    step_s: float = 0.05,
    workdir: Optional[str] = None,
    survivor_failed_budget: int = 0,
) -> Dict[str, Any]:
    """Manager-level donor-free wave: G real-Manager worker subprocesses
    with TPUFT_EC_MODE=prefer (heals NEVER touch the donor path — no
    serving window ever opens on a survivor).  One group is SIGKILLed and
    respawned; its heal must complete via erasure reconstruction from the
    surviving shard holders while every survivor keeps committing with
    ZERO failed commits.

    The wave is paced by what the groups have done, not by the clock: the
    victim is killed once it has committed steps and every survivor has
    encoded a generation (a kill before the first commit leaves nothing to
    heal, which is how a loaded machine failed a wave paced by time), and the
    wave ends once the respawned group has committed after its heal.
    `kill_at_s` is the earliest kill and `run_s` the longest the wave may
    take; an idle machine needs about 12 s."""
    import tempfile

    from torchft_tpu._native import LighthouseServer

    workdir = workdir or tempfile.mkdtemp(prefix="tpuft_ec_mwave_")
    lighthouse = LighthouseServer(
        bind="[::]:0",
        min_replicas=groups,
        join_timeout_ms=2000,
        heartbeat_timeout_ms=1500,
    )
    end_ts = time.time() + run_s
    stop = os.path.join(workdir, "stop")
    procs: Dict[str, subprocess.Popen] = {}
    metrics_paths: Dict[str, str] = {}

    def spawn(idx: int, incarnation: int) -> None:
        replica = f"ecw{idx}"
        out = os.path.join(workdir, f"{replica}_{incarnation}.json")
        metrics = os.path.join(workdir, f"{replica}_{incarnation}.jsonl")
        metrics_paths[f"{replica}_{incarnation}"] = metrics
        procs[f"{replica}_{incarnation}"] = _spawn_worker(
            {"wave_role": "manager", "out": out, "replica": replica,
             "end_ts": end_ts, "stop": stop, "step_s": step_s},
            env={
                "TPUFT_LIGHTHOUSE": lighthouse.address(),
                "TPUFT_METRICS_PATH": metrics,
                "TPUFT_EC_K": str(k),
                "TPUFT_EC_M": str(m),
                "TPUFT_EC_MODE": "prefer",
                "TPUFT_HEAL_BACKOFF_BASE_S": "0.1",
                "TPUFT_HEAL_BACKOFF_CAP_S": "0.5",
            },
        )

    try:
        for i in range(groups):
            spawn(i, 0)
        time.sleep(kill_at_s)
        victim = f"ecw{groups - 1}"
        _wait_for(
            lambda: _stream_count(metrics_paths[f"{victim}_0"], "commit", committed=True) >= 3
            and all(_stream_count(metrics_paths[f"ecw{i}_0"], "ec_push") for i in range(groups - 1)),
            end_ts - run_s / 2,
        )
        procs[f"{victim}_0"].send_signal(signal.SIGKILL)
        procs[f"{victim}_0"].wait(timeout=30)
        time.sleep(respawn_after_s)
        spawn(groups - 1, 1)
        _wait_for(
            lambda: _stream_count(metrics_paths[f"{victim}_1"], "commit", committed=True) >= 3,
            end_ts,
        )
        with open(stop, "w"):
            pass
        deadline = end_ts + 60
        for key, p in procs.items():
            timeout = max(1.0, deadline - time.time())
            try:
                p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()

        results: Dict[str, Any] = {}
        for key in procs:
            out = os.path.join(workdir, f"{key}.json")
            if os.path.exists(out):
                with open(out) as f:
                    results[key] = json.load(f)
        survivors = [
            r for key, r in results.items()
            if not key.startswith(victim)
        ]
        victim_2 = results.get(f"{victim}_1")
        recon_events = 0
        for key, path in metrics_paths.items():
            if not key.startswith(victim) or not os.path.exists(path):
                continue
            with open(path, "rb") as f:
                for line in f:
                    try:
                        ev = json.loads(line)
                    except ValueError:
                        continue
                    if ev.get("event") == "ec_reconstruct":
                        recon_events += 1
        survivor_failed = sum(r["failed_commits"] for r in survivors)
        # survivor_failed_budget: the HEAL path never touches survivors in
        # prefer mode, but the SIGKILL itself can land mid-allreduce and
        # fail one survivor round — CI smokes pass a budget of 1 for that
        # independent race; the pinned artifact keeps the strict 0.
        ok = (
            len(survivors) == groups - 1
            and victim_2 is not None
            and victim_2["commits"] > 0
            and recon_events > 0
            and survivor_failed <= survivor_failed_budget
        )
        return {
            "op": "ec_manager_wave",
            "groups": groups,
            "k": k,
            "m": m,
            "mode": "prefer",
            "survivor_failed_commits": survivor_failed,
            "survivor_commits": [r["commits"] for r in survivors],
            "victim_post_heal_commits": (
                victim_2["commits"] if victim_2 else None
            ),
            "victim_healed_step": victim_2.get("healed_step") if victim_2 else None,
            "ec_reconstructions": recon_events,
            "ok": bool(ok),
        }
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        lighthouse.shutdown()


def run_ec_quick(gb: float = 0.008, buffers: int = 8, k: int = 2, m: int = 1) -> Dict[str, Any]:
    """The EC cells at a small state size: the encode off the train thread,
    reconstruction (bitwise-pinned), the subprocess donor-dead wave, and
    the manager-level prefer-mode wave."""
    state = make_state_dict(int(gb * 1e9), buffers)
    return {
        "state_dict_bytes": sum(a.nbytes for a in state.values()),
        "ec": [
            bench_ec_encode(state, k, m),
            bench_ec_reconstruct(state, k, m),
            bench_ec_wave(gb, buffers, k, m, n_donors=2),
            bench_ec_manager_wave(groups=3, k=k, m=m, step_s=0.05, survivor_failed_budget=1),
        ],
    }


def run_quick(gb: float = 0.064, buffers: int = 16) -> Dict[str, Any]:
    """Small dict, 1 vs 2 donors plus a dead first donor: transfer-path
    regressions (stripe arithmetic, failover, async snapshot) fail here."""
    state = make_state_dict(int(gb * 1e9), buffers)
    return {
        "state_dict_bytes": sum(a.nbytes for a in state.values()),
        "results": [
            bench_http_multi_donor(state, n_donors=1),
            bench_http_multi_donor(state, n_donors=2),
            # Donor 0 is dead before the header fetch, so a completed,
            # correctness-asserted fetch here IS the failover proof.
            bench_http_multi_donor(state, n_donors=2, kill_donor=True),
        ],
    }


if __name__ == "__main__":
    # Worker entry only: the wave cells above start this file as a script.
    _args = types.SimpleNamespace(**json.loads(sys.argv[2]))
    if _args.wave_role == "manager":
        _ec_manager_worker_main(_args)
    else:
        _wave_role_main(_args)
