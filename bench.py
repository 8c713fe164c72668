"""Headline benchmark: fault-tolerant training goodput, measured honestly.

Three configurations:

  raw   — the compiled train step alone on the local chip (no FT machinery).
  ft    — the full per-step fault-tolerance loop (native Lighthouse + Manager,
          async quorum, cross-group allreduce path, two-phase commit vote,
          checkpoint-transport gating) on the same chip, one replica group.
  kill  — the north-star scenario (BASELINE.md): two replica-group processes
          with restart supervisors on the CPU platform, one killed with
          SIGKILL mid-run and healed live from its peer; goodput is committed
          work over a fixed wall-clock window relative to an identical run
          without the kill.

Timing discipline: every measurement ends with a host materialization of a
value data-dependent on the whole step chain, and the raw/ft numbers carry an
MFU plausibility gate: if measured MFU exceeds 100% of the chip's peak the
benchmark fails loudly instead of reporting garbage.  (``chip_smoke.py``'s
train phase times the same step both ways — ``jax.block_until_ready`` and a
host materialization — on the chip this repo runs on; CHANGES.md PR 21
records what it found.)

One process per chip: ``main`` never touches JAX itself.  The chip
measurements run one after another, each in a child of its own that owns the
TPU (``--chip flagship|large``), and the kill scenario's children run with
``JAX_PLATFORMS=cpu``.  A measurement path that finds no TPU fails.

Prints ONE JSON line:
  value        = FT training goodput on the chip (tokens/sec)
  vs_baseline  = goodput-under-kill fraction (committed work with one
                 SIGKILL + heal vs the same window undisturbed).  The
                 reference publishes no absolute numbers (BASELINE.md); its
                 design target is <5% goodput loss => vs_baseline >= 0.95.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

# (device_kind substring, bf16 peak FLOP/s) — checked in order.
# NOTE: v5e's widely-quoted 394 TFLOP/s is the INT8 figure; bf16 peak is
# 197 TFLOP/s.  Rounds 1-3 used 394 here, which understated MFU by 2x and
# manufactured the "4x off roofline" mystery — per-op profiling (round 4)
# shows the big bf16 matmul fusions sustaining ~187 TFLOP/s, i.e. ~95% of
# the real peak, which is what pinned the error to this table.
_PEAKS = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e reports "TPU v5 lite"
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]


def _peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for sub, peak in _PEAKS:
        if sub in kind:
            return peak
    raise RuntimeError(
        f"no bf16 peak recorded for device kind {device.device_kind!r}: add "
        "it to bench._PEAKS with its source — an MFU is never computed "
        "against a guessed peak"
    )


def tpu_device():
    """The chip the measurement runs on.  Raises where JAX's first device is
    not a TPU: a chip path never measures the CPU under a device's name."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(
            f"this path measures the chip and JAX found {device.platform!r} "
            f"({device.device_kind}); run it through the chip tool"
        )
    return device


# ---------------------------------------------------------------------------
# On-chip: raw vs FT per-step goodput.
# ---------------------------------------------------------------------------


def flagship_config():
    """The headline benchmark model: (TransformerConfig, batch_size, seq).

    Shared with tools/profile_step.py so the per-op profile always
    corresponds to the shape the recorded numbers describe."""
    from torchft_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=32000,
        d_model=768,
        n_layers=12,
        # head_dim 128 = TPU lane width: the pallas flash-attention kernel
        # engages (d_head 64 falls back to XLA S^2 attention) and MXU tiles
        # are full.  Measured on v5e: 12 heads x 64 -> 273 ms/step, 6 x 128
        # -> 213 ms at identical param count (rounds 1-3; MFU percentages
        # from those rounds were computed against the wrong 394 TF/s peak —
        # see _PEAKS — the wall times stand).
        n_heads=6,
        n_kv_heads=6,
        d_ff=2048,
        max_seq=1024,
        # 134M params at batch 16 fits HBM without rematerialization; remat
        # would recompute every layer in backward (~4/3 the FLOPs) to save
        # memory this config doesn't need.
        remat=False,
        # Full unroll of the layer stack: XLA fuses/pipelines across layer
        # boundaries, and >= n_layers takes the static-Python-loop path
        # (constant-folded layer indexing — kills ~17 ms/step of
        # dynamic-update-slice grad writes the scan form leaves behind).
        # Measured on v5e at this config: scan 158 ms/step -> scan-unroll
        # 141 ms -> static loop 131 ms (round 3; now 108 ms with the
        # round-4 pallas backward + fused CE).  Partial unroll (4) was
        # slower than any of these.
        scan_unroll=12,
    )
    return cfg, 16, 1024


def chip_benchmark() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.models import init_params, loss_fn
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    cfg, batch_size, seq = flagship_config()
    tokens_per_step = batch_size * seq

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(batch_size, seq)), dtype=jnp.int32
    )
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    # 6N per token for the dense path + causal attention term (6*L*s*d).
    flops_per_step = (6 * n_params + 6 * cfg.n_layers * seq * cfg.d_model) * tokens_per_step

    device = tpu_device()
    peak = _peak_flops(device)

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    tx = optax.adamw(3e-4)
    step = TrainStep(ftmesh, tx, lambda p, b: loss_fn(p, b, cfg))

    def fetch(x) -> float:
        # Host materialization as the completion barrier (module docstring).
        return float(np.asarray(x))

    # -- raw --------------------------------------------------------------
    state = {"params": params, "opt": step.init_opt_state(params)}

    def raw_step():
        state["params"], state["opt"], loss = step.full_step(
            state["params"], state["opt"], batch
        )
        return loss

    for _ in range(3):  # compile + warmup
        loss = raw_step()
    fetch(loss)

    # Estimate step time to size the measured run (>= ~6 s of device time,
    # and never fewer than 20 steps: at ~240 ms/step an 8-step window showed
    # ±1% run-to-run noise — larger than the FT overhead being measured).
    t0 = time.perf_counter()
    fetch(raw_step())
    est = max(1e-3, time.perf_counter() - t0)
    steps = max(20, min(200, int(6.0 / est)))

    # -- ft (one replica group, full stack) -------------------------------
    from torchft_tpu._native import LighthouseServer
    from torchft_tpu.checkpointing.http_transport import HTTPTransport
    from torchft_tpu.collectives import TCPCollective
    from torchft_tpu.manager import Manager

    lighthouse = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=100
    )
    params2 = init_params(jax.random.PRNGKey(0), cfg)
    state2 = {"params": params2, "opt": step.init_opt_state(params2)}
    manager = Manager(
        collective=TCPCollective(timeout=30.0),
        load_state_dict=lambda sd: state2.update(sd),
        state_dict=lambda: dict(state2),
        min_replica_size=1,
        rank=0,
        world_size=1,
        replica_id="bench",
        lighthouse_addr=lighthouse.address(),
        checkpoint_transport=HTTPTransport(timeout=30.0),
    )
    ftmesh.manager = manager

    def ft_one_step():
        manager.start_quorum()
        state2["params"], state2["opt"], loss, committed = step.ft_step(
            state2["params"], state2["opt"], batch
        )
        assert committed, "bench step failed to commit"
        return loss

    # INTERLEAVED measurement: raw and FT blocks alternate (R,F,R,F,...) so
    # slow host-load drift hits both paths equally; the FT overhead is then
    # judged against the raw blocks' own spread rather than stated as a
    # point estimate (round-4 lesson: ft measured *faster* than raw — the
    # difference is below run variance, and the honest claim is exactly
    # that).
    reps = 3
    block = max(7, steps // reps)
    raw_block_tps: list[float] = []
    ft_block_tps: list[float] = []
    try:
        for _ in range(3):  # FT warmup (compile path is shared with raw)
            loss = ft_one_step()
        fetch(loss)
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(block):
                loss = raw_step()
            fetch(loss)  # loss depends on params_{k-1}: forces the chain
            raw_block_tps.append(tokens_per_step * block / (time.perf_counter() - t0))

            t0 = time.perf_counter()
            for _ in range(block):
                loss = ft_one_step()
            fetch(loss)
            ft_block_tps.append(tokens_per_step * block / (time.perf_counter() - t0))
    finally:
        manager.shutdown()
        lighthouse.shutdown()

    raw_tps = sum(raw_block_tps) / reps
    ft_tps = sum(ft_block_tps) / reps
    raw_dt = tokens_per_step * block * reps / raw_tps
    ft_dt = tokens_per_step * block * reps / ft_tps
    steps = block * reps
    raw_mfu = flops_per_step * steps / raw_dt / peak
    ft_mfu = flops_per_step * steps / ft_dt / peak

    if raw_mfu > 1.0:
        print(
            json.dumps(
                {
                    "metric": "ft_train_goodput",
                    "value": 0,
                    "unit": "tokens/sec",
                    "vs_baseline": 0,
                    "error": f"implausible measurement: raw MFU {raw_mfu:.2f} "
                    f"exceeds 100% of {device.device_kind} peak — timing is "
                    "not capturing real device execution",
                }
            )
        )
        sys.exit(1)

    # Run-to-run noise floor: the raw path's own block-to-block spread.
    raw_noise = (max(raw_block_tps) - min(raw_block_tps)) / raw_tps
    overhead = 1 - ft_tps / raw_tps

    return {
        "device": str(device.device_kind),
        "model": f"transformer-lm 12L d768 bf16 seq{seq} batch{batch_size} "
        f"({n_params/1e6:.0f}M params)",
        "steps_timed": steps,
        "interleaved_blocks": reps,
        "raw_tokens_per_sec": round(raw_tps, 1),
        "ft_tokens_per_sec": round(ft_tps, 1),
        "raw_block_tokens_per_sec": [round(x, 1) for x in raw_block_tps],
        "ft_block_tokens_per_sec": [round(x, 1) for x in ft_block_tps],
        "ft_step_ms": round(ft_dt / steps * 1000, 2),
        "raw_step_ms": round(raw_dt / steps * 1000, 2),
        "ft_overhead_fraction": round(overhead, 4),
        "raw_noise_fraction": round(raw_noise, 4),
        # The claim the README is allowed to make: overhead resolved, or
        # below the measurement's own noise floor.
        "ft_overhead_below_noise": bool(abs(overhead) <= raw_noise),
        "raw_mfu": round(raw_mfu, 4),
        "ft_mfu": round(ft_mfu, 4),
    }


def large_config():
    """The scale-proof model: ~1B params, the largest round shape that fits
    one v5e chip (16 GB HBM) with f32 params + a memory-lean factored
    optimizer — withOUT rematerialization, which measured as a pure loss
    at this size (see the remat field comment).  VERDICT r4 #2: show the
    MFU and heal story survive a ~10x model (reference capability chased:
    'train models such as Llama 3 70B', reference README)."""
    from torchft_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=32000,
        d_model=2048,
        n_layers=12,
        n_heads=16,
        n_kv_heads=16,
        d_ff=8192,
        max_seq=1024,
        # Measured on v5e at batch 8: remat 410 ms/step (58.6% MFU) vs
        # NO remat 334 ms (71.9%) — the flash-attention kernels' O(S*D)
        # residuals and the fused CE's never-materialized logits leave
        # enough HBM at this size that paying the recompute tax is a pure
        # loss.  Larger-than-HBM configs flip remat back on.
        remat=False,
        scan_unroll=12,  # static layer loop, same as the flagship
    )
    return cfg, 8, 1024


def large_chip_benchmark() -> dict:
    """Step time / MFU for the ~1B model on the real chip, plus the live
    heal cost at that size (the full state dict through HTTPTransport on
    localhost — the same bytes a healing replica must ingest)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.models import init_params, loss_fn
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    device = tpu_device()

    cfg, batch_size, seq = large_config()
    tokens_per_step = batch_size * seq
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(batch_size, seq)), dtype=jnp.int32
    )
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    flops_per_step = (
        6 * n_params + 6 * cfg.n_layers * seq * cfg.d_model
    ) * tokens_per_step
    # Remat recomputes the layer stack in backward (~+2N per token of the
    # layer FLOPs); MFU is still stated against the USEFUL flops above —
    # that is the number that compares across configs.
    peak = _peak_flops(device)

    ftmesh = ft_init_mesh({"data": 1}, devices=[device])
    tx = optax.adafactor(3e-4)  # factored second moments: O(d) state, not O(d^2)
    step = TrainStep(ftmesh, tx, lambda p, b: loss_fn(p, b, cfg))
    state = {"params": params, "opt": step.init_opt_state(params)}

    def fetch(x) -> float:
        return float(np.asarray(x))

    def raw_step():
        state["params"], state["opt"], loss = step.full_step(
            state["params"], state["opt"], batch
        )
        return loss

    for _ in range(2):
        loss = raw_step()
    fetch(loss)
    t0 = time.perf_counter()
    fetch(raw_step())
    est = max(1e-3, time.perf_counter() - t0)
    steps = max(10, min(60, int(8.0 / est)))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = raw_step()
    fetch(loss)
    dt = time.perf_counter() - t0
    tps = tokens_per_step * steps / dt
    mfu = flops_per_step * steps / dt / peak

    # Heal cost at this size: stream the full live state dict through the
    # HTTP checkpoint transport (send + chunked recv) on localhost.  This
    # is the byte path a healed replica pays on top of restart.
    flat = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(state["params"]):
        flat["p" + jax.tree_util.keystr(path)] = np.asarray(leaf)
    state_bytes = sum(a.nbytes for a in flat.values())
    # Both live transports; on this 1-core host both endpoints share one
    # core, so these are FLOORS — real multi-host hardware has a NIC and
    # cores per endpoint (TRANSFER_BENCH.json records the same floor for
    # the 2 GB synthetic state).
    from bench_transfer import bench_collective, bench_http

    heal = {
        "state_gb": round(state_bytes / 1e9, 2),
        "http": bench_http(flat, state_bytes, num_chunks=4),
        "collective": bench_collective(flat, state_bytes),
    }

    return {
        "model": f"transformer-lm {cfg.n_layers}L d{cfg.d_model} bf16 seq{seq} "
        f"batch{batch_size} ({n_params/1e6:.0f}M params, "
        f"{'remat' if cfg.remat else 'no-remat'}, adafactor)",
        "steps_timed": steps,
        "step_ms": round(dt / steps * 1000, 2),
        "tokens_per_sec": round(tps, 1),
        "mfu": round(mfu, 4),
        "heal_transfer": heal,
    }


# ---------------------------------------------------------------------------
# Goodput under kill -9 (the BASELINE.md north-star scenario).
# ---------------------------------------------------------------------------


def _read_events(metrics_path: str) -> list:
    # The hardened reader: skips torn/garbage lines AND JSON that parses to
    # a non-dict (a corrupt line reading as a bare scalar would crash every
    # ev.get() consumer below) — one implementation, shared with the
    # attribution/report tooling.
    from torchft_tpu.obs.report import read_events

    return read_events([metrics_path])


class _MetricsTail:
    """Incremental reader of the shared metrics.jsonl.

    The churn watcher polls every 250 ms on the same single core being
    measured; re-parsing the whole (growing) file each tick would steal
    CPU from the heal interval whose duration is the headline number.
    Appends are line-atomic (O_APPEND), so tailing from the last consumed
    newline is safe."""

    def __init__(self, path: str) -> None:
        self._path = path
        self._pos = 0
        self.events: list = []

    def poll(self) -> list:
        try:
            with open(self._path, "rb") as f:
                f.seek(self._pos)
                chunk = f.read()
        except OSError:
            return self.events
        if not chunk:
            return self.events
        # Only consume up to the last complete line.
        end = chunk.rfind(b"\n")
        if end < 0:
            return self.events
        self._pos += end + 1
        for line in chunk[: end + 1].splitlines():
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            if isinstance(ev, dict):  # scalar-parsing garbage: skip, see
                self.events.append(ev)  # obs/report.py::read_events
        return self.events


def _victim_incarnations(events, group: str) -> dict:
    """{replica_id: (first_event_ts, first_commit_ts|None)} for one group."""
    out: dict = {}
    for ev in events:
        rid = str(ev.get("replica_id", ""))
        if rid.split(":", 1)[0] != group:
            continue
        ts = float(ev.get("ts", 0.0))
        first, commit = out.get(rid, (ts, None))
        first = min(first, ts)
        if ev.get("event") == "commit" and ev.get("committed") and (
            commit is None or ts < commit
        ):
            commit = ts
        out[rid] = (first, commit)
    return out


def _run_scenario(workdir: str, window_s: float, plan: dict | None) -> dict:
    """Two supervised replica-group processes; `plan` scripts the fault:

      None                          — undisturbed baseline window.
      {"type": "single", "victim"}  — one SIGKILL at window/3.
      {"type": "single_spare", "victim"} — one SIGKILL, but the launcher
          runs a hot-spare pool: the dead group's id is handed to a
          pre-initialized spare immediately (no scripted respawn delay —
          adoption IS the respawn), measuring the spare-pool downtime.
      {"type": "double", "victim"}  — SIGKILL at window/4; once the
          restarted incarnation COMMITS, kill it again (back-to-back
          failures, the churn the reference's integ tests repeat,
          torchft/manager_integ_test.py:304-352).
      {"type": "during_heal", "victim"} — SIGKILL at window/4; the moment
          the restarted incarnation shows its FIRST event (it is
          rejoining/healing, has not committed), kill it again — a failure
          landing inside recovery.
      {"type": "drain", "victim"}   — cooperative drain at window/3: the
          launcher (spare pool enabled) writes the drain notice and hands
          the group id to a pre-warmed spare; the donor finishes its
          in-flight step, votes commit, tells the lighthouse, and exits.
          Measures the PLANNED-departure path (GCE maintenance /
          preemption notices, SIGTERM grace periods) next to the crash
          numbers: dead time is the donor-to-replacement commit gap, and
          the survivors must see ZERO failed should_commit rounds.
      {"type": "straggler", "victim", "auto_drain"} — no kill at all: at
          window/3 the victim gets an injected per-step sleep (pid-pinned
          straggle file read by examples/_common.maybe_straggle), modeling
          the degraded-but-alive host no heartbeat timeout catches.  The
          lighthouse's straggler sentinel must detect it (healthy ->
          suspect -> straggler on /metrics, alert on /alerts.json; the
          driver stamps the observation into the stream as an ``alert``
          record).  With auto_drain the launcher runs a spare pool +
          sentinel poll and rotates the slow host out through the
          cooperative-drain path; the scenario's post-injection commit
          rate then measures the goodput the sentinel recovered vs the
          no-sentinel run that keeps pacing on the slow host.

    The measurement window only starts once BOTH groups have committed a
    step: startup JIT compilation is excluded from both scenarios, and the
    persistent compilation cache every child inherits from the launcher
    (``launch.export_compile_cache``: one fixed place, shared by every
    process of all scenarios) keeps the post-kill restart from paying it
    again (on a single-core host a restart recompile starves every
    process, which would swamp the FT cost being measured).

    Process management is the framework's own Launcher (torchft_tpu/launch.py)
    — the same supervisor a user gets from ``python -m torchft_tpu.launch``;
    the bench only adds the scripted SIGKILLs.

    Counting is primarily from the Manager's structured metrics stream
    (metrics.jsonl "commit"/"heal_fetched" events — O_APPEND lines are
    atomic on Linux so both groups share one file); the log-grep remains as
    a cross-checked fallback."""
    repo = os.path.dirname(os.path.abspath(__file__))
    from torchft_tpu.launch import Launcher
    from torchft_tpu.metrics import MetricsLogger

    metrics_path = os.path.join(workdir, "metrics.jsonl")
    # The bench driver writes its fault schedule INTO the shared metrics
    # stream ("fault" records), so obs/report.py sees the exact timeline
    # the goodput accounting below charges — the report reproduces the
    # benchmark number from the JSONL alone.
    fault_log = MetricsLogger(metrics_path, replica_id="bench-driver")
    victim = str(plan["victim"]) if plan else None
    kind = plan["type"] if plan else None
    straggler = kind == "straggler"
    auto_drain = bool(plan.get("auto_drain")) if plan else False
    straggle_sleep_s = float(os.environ.get("TPUFT_BENCH_STRAGGLE_SLEEP_S", "1.0"))
    straggle_info: dict = {}
    spares = 1 if kind in ("single_spare", "drain") or (straggler and auto_drain) else 0
    child_env: dict = {
        # Two groups (plus spares) on one host: a chip belongs to one
        # process, so this scenario's workers run on the CPU.
        "JAX_PLATFORMS": "cpu",
        "TPUFT_METRICS_PATH": metrics_path,
        # Worker managers dump their flight recorders here on clean exit
        # (drained donors); SIGKILLed victims leave no dump — their story
        # lives in the LIGHTHOUSE's recorder, which dumps at launcher stop.
        "TPUFT_FLIGHT_DIR": workdir,
    }
    # The embedded lighthouse runs in THIS process; it reads the dump path
    # from the driver's environment at SHUTDOWN, so the var only needs to
    # be set inside the try below (children get it via child_env) — a
    # Launcher construction failure then cannot leak it.
    prev_flight_dir = os.environ.get("TPUFT_FLIGHT_DIR")
    if straggler:
        child_env["TPUFT_STRAGGLE_DIR"] = workdir
    launcher = Launcher(
        [sys.executable, os.path.join(repo, "examples", "train_ddp.py"),
         "--steps", "1000000"],
        num_groups=2,
        lighthouse="embed",
        min_replicas=1,
        join_timeout_ms=2000,
        log_dir=workdir,
        env=child_env,
        cwd=repo,
        spares=spares,
        straggler_auto_drain=auto_drain if straggler else None,
    )
    kill_events: list[tuple[float, str]] = []
    # Churn windows get extra tail so the LAST heal still has room to
    # complete and commit inside the measured window.
    total_window = window_s + (20.0 if kind in ("double", "during_heal") else 0.0)

    def kill_victim():
        now = time.time()
        if straggler:
            # Not a kill: drop the pid-pinned straggle file the victim's
            # train loop polls — from now on its every step pays an extra
            # sleep, until the sentinel rotates the incarnation out (the
            # replacement has a new pid and stays fast).
            pid = launcher.pid(int(victim))
            if pid is None:
                # Victim momentarily dead (supervisor restarting it): a
                # pid-less file would pin the slowness to EVERY future
                # incarnation.  Skip; the next poll tick retries.
                return
            path = os.path.join(workdir, f"straggle_{victim}.json")
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"sleep_s": straggle_sleep_s, "pid": pid}, f)
            os.replace(tmp, path)
            fault_log.emit(
                "fault", ts=now, kind="straggler", group=victim, plan=kind
            )
            fault_log.emit(
                "straggler_injected",
                group=victim,
                sleep_s=straggle_sleep_s,
                pid=pid,
            )
            straggle_info["inject_ts"] = now
            straggle_info["sleep_s"] = straggle_sleep_s
            return
        kill_events.append((now, victim))
        # Same ts as the in-memory kill list (the explicit ts field
        # overrides the logger's own clock) so the recorded stream yields
        # bit-identical goodput arithmetic.
        fault_log.emit(
            "fault",
            ts=now,
            kind="drain" if kind == "drain" else "kill",
            group=victim,
            plan=kind,
        )
        if kind == "drain":
            # Planned departure: the launcher hands the id to a pre-warmed
            # spare and notifies the donor; no kill at all.  A victim that
            # crashed in the poll gap makes drain() raise — record the
            # trial as unrecovered instead of aborting the whole bench
            # (kill() tolerates the same race silently).
            try:
                launcher.drain(int(victim), deadline_s=20.0)
            except RuntimeError as e:
                print(f"drain trial lost its victim before the notice: {e}",
                      file=sys.stderr)
            return
        launcher.kill(int(victim))  # SIGKILL, the real thing
        if spares:
            # Hot adoption IS the respawn: no scripted environment delay.
            launcher.spawn(int(victim))
        else:
            time.sleep(3.0)  # restart delay: the dead window is real
            launcher.spawn(int(victim))

    try:
        os.environ["TPUFT_FLIGHT_DIR"] = workdir
        with launcher:
            start = time.monotonic()
            first_kill_at = None if plan is None else (
                total_window / 3
                if kind in ("single", "single_spare", "drain", "straggler")
                else total_window / 4
            )
            pre_kill_ids: set = set()
            second_done = kind in ("single", "single_spare", "drain", "straggler")
            second_deadline = None
            last_alert_poll = 0.0
            tail = _MetricsTail(metrics_path)
            # Incident auto-capture: poll the embedded lighthouse's
            # /incident.json and bundle the live evidence the moment a
            # trigger lands (replica_stale for kills, alert:<kind> for
            # sentinel raises) — the shutdown dumps are folded in by the
            # finalize pass after the launcher exits.
            from torchft_tpu.obs import incident as obs_incident

            incident_watch = obs_incident.IncidentWatcher(
                launcher.lighthouse_http_address
            )
            incident_bundles: dict[str, dict] = {}
            last_incident_poll = 0.0

            def poll_incidents() -> None:
                nonlocal last_incident_poll
                if time.monotonic() - last_incident_poll < 1.0:
                    return
                last_incident_poll = time.monotonic()
                for trig in incident_watch.poll():
                    try:
                        bundle = obs_incident.capture_bundle(
                            workdir,
                            launcher.lighthouse_http_address,
                            trig,
                            metrics_paths=[metrics_path],
                        )
                    except OSError:
                        # Transient capture failure: re-queue the trigger
                        # so the next poll retries instead of losing the
                        # incident the feed already recorded.
                        incident_watch.unsee(trig.get("id"))
                        continue
                    incident_bundles[bundle] = trig
                    fault_log.emit(
                        "incident_captured",
                        bundle=os.path.basename(bundle),
                        reason=trig.get("reason"),
                        incident_replica=trig.get("replica_id"),
                        incident_id=trig.get("id"),
                    )
            while time.monotonic() - start < total_window:
                time.sleep(0.25)
                if first_kill_at is not None and time.monotonic() - start >= first_kill_at:
                    # Draining a group that never committed (still in its first
                    # JIT) measures nothing: the handoff gap needs a donor
                    # commit timeline on both sides — and a straggler injection
                    # before the first commit has no pre-injection pace to
                    # score against.  Hold the fault until the first commit —
                    # WITHOUT skipping the supervision below (the window clock
                    # keeps running either way).
                    fire_ok = kind not in ("drain", "straggler") or any(
                        commit is not None
                        for _, commit in _victim_incarnations(
                            tail.poll(), victim
                        ).values()
                    )
                    if straggler and fire_ok:
                        # The scenario models a host degrading MID-RUN, so the
                        # injection additionally waits until the victim has
                        # cleared the sentinel's warmup gate (which exists to
                        # ignore JIT-phase pace skew) — injecting during warmup
                        # would measure the gate, not the detection contract.
                        try:
                            warmup = max(
                                0,
                                int(os.environ.get(
                                    "TPUFT_STRAGGLER_WARMUP_STEPS", "10")),
                            )
                        except ValueError:
                            warmup = 10
                        n_commits = sum(
                            1
                            for ev in tail.poll()
                            if ev.get("event") == "commit"
                            and ev.get("committed")
                            and str(ev.get("replica_id", "")).split(":", 1)[0]
                            == victim
                        )
                        fire_ok = n_commits > warmup
                    if fire_ok:
                        pre_kill_ids = set(
                            _victim_incarnations(tail.poll(), victim)
                        )
                        kill_victim()
                        if not straggler or "inject_ts" in straggle_info:
                            # A straggler injection can decline to fire (victim
                            # pid momentarily gone); leave the trigger armed so
                            # the next tick retries instead of silently running
                            # a fault-free window.
                            first_kill_at = None
                            second_deadline = time.monotonic() + 25.0
                elif not second_done and kill_events:
                    # Watch for the respawned incarnation to reach the trigger
                    # state, with a deadline fallback so a stuck restart can't
                    # hang the bench.
                    inc = _victim_incarnations(tail.poll(), victim)
                    fresh = {k: v for k, v in inc.items() if k not in pre_kill_ids}
                    fire = False
                    if kind == "double":
                        fire = any(commit is not None for _, commit in fresh.values())
                    elif kind == "during_heal":
                        fire = bool(fresh)
                    if fire or (second_deadline and time.monotonic() > second_deadline):
                        kill_victim()
                        second_done = True
                # Straggler scenario: watch the lighthouse's /alerts.json for
                # the sentinel's detection and stamp it into the stream (the
                # `alert` record), so detection latency and the trace view come
                # from the recorded data alone.
                if (
                    straggler
                    and "inject_ts" in straggle_info
                    and "alert" not in straggle_info
                    and time.monotonic() - last_alert_poll >= 1.0
                ):
                    last_alert_poll = time.monotonic()
                    alert = _poll_straggler_alert(
                        launcher.lighthouse_http_address, victim,
                        after_ts=straggle_info["inject_ts"],
                    )
                    if alert is not None:
                        straggle_info["alert"] = alert
                        fault_log.emit(
                            "alert",
                            group=victim,
                            alert_id=alert.get("id"),
                            kind=alert.get("kind"),
                            replica_id=alert.get("replica_id"),
                            raised_ms=alert.get("raised_ms"),
                            ratio=alert.get("ratio"),
                            step_time_ms=alert.get("step_time_ms"),
                            auto_drained=alert.get("auto_drained"),
                        )
                poll_incidents()
                # Supervisor: restart any group that died for other reasons.
                launcher.supervise_once()
            # Final sweep while the lighthouse is still serving: a trigger
            # that landed in the last poll gap (e.g. the straggler alert
            # raising near window end) still gets its live snapshot.
            last_incident_poll = 0.0
            poll_incidents()

    finally:
        fault_log.close()
        # Env restore runs on EVERY exit path (a spawn failure or ^C must
        # not leave the driver pointing dumps at a dead temp workdir).
        if prev_flight_dir is None:
            os.environ.pop("TPUFT_FLIGHT_DIR", None)
        else:
            os.environ["TPUFT_FLIGHT_DIR"] = prev_flight_dir
    stats = _scenario_stats(workdir, metrics_path, kill_events, plan)
    stats["flight"] = _flight_stats(workdir, assert_dump=bool(kill_events))
    if straggler:
        stats["straggler"] = _straggler_stats(
            metrics_path, straggle_info, victim, plan
        )
    stats["incident"] = _incident_stats(
        workdir, metrics_path, incident_bundles, victim, plan
    )
    return stats


def _incident_stats(
    workdir: str,
    metrics_path: str,
    incident_bundles: dict,
    victim: str | None,
    plan: dict | None,
) -> dict | None:
    """Finalizes every captured incident bundle (fold in the shutdown
    dumps, compute verdicts) and — for injected-fault plans — ASSERTS the
    auto-capture contract: a bundle exists, its verdict names the
    injected victim group, and (kill plans) >= 90% of the measured lost
    wall time is charged to the matching cause."""
    from torchft_tpu.obs import incident as obs_incident

    if not incident_bundles:
        if plan is not None and plan.get("type") != "drain":
            # A fault was injected but nothing triggered: the auto-capture
            # contract is broken (kills must trip replica_stale; straggler
            # plans trip alert:straggler when the sentinel detects).
            # Drains are PLANNED departures — no incident by design.
            raise AssertionError(
                f"injected fault ({plan.get('type')}) produced no incident "
                "trigger on /incident.json — auto-capture contract broken"
            )
        return None
    events = _read_events(metrics_path)
    out: dict = {"bundles": []}
    named_victim = False
    for bundle in sorted(incident_bundles):
        manifest = obs_incident.finalize_bundle(bundle, workdir, events=events)
        v = manifest.get("verdict", {})
        out["bundles"].append({"path": bundle, "verdict": v})
        if victim is not None and v.get("replica") == victim:
            named_victim = True
            out["verdict"] = v
    if plan is not None and victim is not None and plan.get("type") != "drain":
        assert named_victim, (
            f"no incident verdict named the injected victim {victim!r}: "
            + json.dumps([b["verdict"] for b in out["bundles"]])
        )
        if plan.get("type") in ("single", "single_spare", "double",
                                "during_heal"):
            cf = out.get("verdict", {}).get("charged_fraction")
            assert cf is None or cf >= 0.9, (
                f"kill verdict charged only {cf} of the lost wall to the "
                "dead window — cause attribution too weak"
            )
    return out


def _flight_stats(workdir: str, assert_dump: bool) -> dict:
    """Flight-recorder dump inventory for one scenario workdir.

    Kill trials ASSERT the black box: the embedded lighthouse must have
    dumped at launcher stop, the dump must parse, and the quorum-transition
    sequence around the SIGKILL must be reconstructable from it — the
    post-mortem contract ISSUE 7's acceptance pins.  Fault-free baselines
    report whatever dumped without asserting (a baseline window forms ONE
    quorum whose membership never changes, which is still >= 1 transition).
    """
    import glob as _glob

    from torchft_tpu.obs import flight as obs_flight

    paths = sorted(
        _glob.glob(os.path.join(workdir, "flight_*.json"))
    )
    lighthouse_paths = [p for p in paths if "lighthouse" in os.path.basename(p)]
    if assert_dump:
        assert lighthouse_paths, (
            f"kill trial left no lighthouse flight-recorder dump in {workdir} "
            "(TPUFT_FLIGHT_DIR contract broken)"
        )
    out: dict = {"paths": paths, "dumps": []}
    for path in paths:
        try:
            dump = obs_flight.load_flight_dump(path)
        except (OSError, ValueError) as e:
            if assert_dump and path in lighthouse_paths:
                raise AssertionError(f"flight dump {path} unparseable: {e}")
            out["dumps"].append({"path": path, "ok": False})
            continue
        events = obs_flight.flight_events(dump)
        transitions = obs_flight.quorum_transitions(events)
        out["dumps"].append(
            {
                "path": path,
                "ok": True,
                "server": dump.get("server"),
                "recorded": dump.get("recorded"),
                "events": len(events),
                "quorum_transitions": len(transitions),
            }
        )
        if "lighthouse" in os.path.basename(path):
            out["lighthouse_dump"] = path
            out["quorum_transitions"] = transitions[-8:]
    if assert_dump:
        assert out.get("quorum_transitions"), (
            "lighthouse flight dump holds no quorum_formed transitions — "
            "cannot reconstruct the kill post-mortem"
        )
    return out


def _poll_straggler_alert(http_address: str, victim: str, after_ts: float = 0.0):
    """First straggler alert for the victim group raised AFTER ``after_ts``
    on the lighthouse's /alerts.json, or None.  The time filter keeps a
    stale pre-injection alert (e.g. one the warmup gate would normally
    suppress) from masquerading as the injection's detection.  Any failure
    reads as 'not yet' — the poll runs inside the measured window and must
    never abort the trial."""
    from torchft_tpu.launch import fetch_alerts

    alerts = fetch_alerts(http_address)
    if alerts is None:
        return None
    for alert in alerts.get("alerts", []):
        if alert.get("kind") != "straggler":
            continue
        if float(alert.get("raised_ms", 0)) / 1e3 < after_ts:
            continue
        if str(alert.get("replica_id", "")).split(":", 1)[0] == victim:
            return alert
    return None


def _straggler_stats(
    metrics_path: str, info: dict, victim: str, plan: dict
) -> dict:
    """Sentinel scorecard for one straggler trial: detection latency (wall
    seconds AND victim steps vs the grace budget) plus the post-injection
    cluster commit rate — the number the auto-drain run must beat the
    no-sentinel run on."""
    from torchft_tpu.obs import report as obs_report

    events = _read_events(metrics_path)
    # Same per-group commit timelines the goodput accounting uses — one
    # implementation of the commit-record semantics (obs/report.py).
    commits = obs_report.commit_timelines(events)
    try:
        grace = max(1, int(os.environ.get("TPUFT_STRAGGLER_GRACE_STEPS", "5")))
    except ValueError:
        grace = 5
    try:
        ratio = float(os.environ.get("TPUFT_STRAGGLER_RATIO", "1.5"))
    except ValueError:
        ratio = 1.5
    inject_ts = info.get("inject_ts")
    alert = info.get("alert")
    out: dict = {
        "auto_drain": bool(plan.get("auto_drain")),
        "sleep_s": info.get("sleep_s"),
        "inject_ts": inject_ts,
        "grace_steps": grace,
        "ratio_threshold": ratio,
        "detected": alert is not None,
        "alert": alert,
        "detect_latency_s": None,
        "detect_latency_steps": None,
        "detected_within_grace": None,
        "rotated_out": any(ev.get("event") == "straggler_drain" for ev in events),
        "post_inject_commits": None,
        "post_inject_span_s": None,
        "post_inject_rate_per_s": None,
        "pre_inject_rate_per_s": None,
    }
    if inject_ts is None:
        return out
    all_ts = sorted(ts for lst in commits.values() for ts in lst)
    if all_ts:
        t0 = max(min(lst) for lst in commits.values())
        post = [ts for ts in all_ts if ts >= inject_ts]
        pre = [ts for ts in all_ts if t0 <= ts < inject_ts]
        span_post = max(all_ts) - inject_ts
        span_pre = inject_ts - t0
        out["post_inject_commits"] = len(post)
        out["post_inject_span_s"] = round(span_post, 2)
        if span_post > 0:
            out["post_inject_rate_per_s"] = round(len(post) / span_post, 3)
        if span_pre > 0 and pre:
            out["pre_inject_rate_per_s"] = round(len(pre) / span_pre, 3)
    if alert is not None and alert.get("raised_ms"):
        raised_s = float(alert["raised_ms"]) / 1e3
        out["detect_latency_s"] = round(raised_s - inject_ts, 2)
        steps = sum(
            1 for ts in commits.get(victim, []) if inject_ts < ts <= raised_s
        )
        out["detect_latency_steps"] = steps
        # The sentinel's contract is promotion on the grace-th SLOW step
        # observation.  The raw commit count above includes 1-2 boundary
        # commits (steps in flight when the injection landed, whose
        # telemetry still reflects pre-injection pace), so the contract is
        # checked against the count of commits that actually MEASURED slow
        # — victim step_summaries in the window whose busy time shows the
        # injected sleep.
        slow_thresh_ms = float(info.get("sleep_s", 0.0)) * 1e3 * 0.5
        slow_steps = sum(
            1
            for ev in events
            if ev.get("event") == "step_summary"
            and str(ev.get("replica_id", "")).split(":", 1)[0] == victim
            and inject_ts < float(ev.get("ts", 0.0)) <= raised_s
            and float(ev.get("step_time_ms", 0.0) or 0.0) >= slow_thresh_ms
        )
        out["detect_latency_slow_steps"] = slow_steps
        out["detected_within_grace"] = slow_steps <= grace
    return out


def _scenario_stats(
    workdir: str, metrics_path: str, kill_events: list | None, plan: dict | None = None
) -> dict:
    """Parses the metrics stream into per-group committed counts, the
    dead-window goodput fraction, and (single-kill runs) the victim's
    downtime decomposition.

    Counting starts at t0 = the first moment BOTH groups have committed a
    step, so startup JIT compilation is excluded from the counts (not just
    from the wall window).  Group identity is the prefix of replica_id
    ("<group>:<uuid>").

    The PRIMARY goodput number is dead-window based: for every killed
    group, each commit gap that contains >= 1 kill is charged as downtime
    (minus one median step interval — the step it would have taken
    anyway), and goodput = 1 - total_dead / span.  This accounting is
    robust to host-load rate drift (a slow second half of the window does
    not read as FT loss, which is what made the round-4 rate-extrapolated
    fraction spread 0.23 over 3 trials) and it handles single, double, and
    during-heal kill plans identically: overlapping kills simply land in
    one longer gap."""
    kill_events = kill_events or []
    events = _read_events(metrics_path)

    commits: dict[str, list[float]] = {}
    failed: dict[str, list[float]] = {}
    heals = 0
    heal_ms: list[float] = []
    for ev in events:
        if ev.get("event") == "commit":
            group = str(ev.get("replica_id", "")).split(":", 1)[0]
            if ev.get("committed"):
                commits.setdefault(group, []).append(float(ev["ts"]))
            else:
                failed.setdefault(group, []).append(float(ev["ts"]))
        elif ev.get("event") == "heal_fetched":
            heals += 1
            if ev.get("heal_ms") is not None:
                heal_ms.append(float(ev["heal_ms"]))

    if not commits:
        # Metrics stream missing or empty: fall back to the log contract
        # (pinned by tests/test_bench_contract.py) — totals only, no
        # per-group timing.
        committed = 0
        heals = 0
        # Every process log in the workdir: g<i>.log plus spare_<sid>.log —
        # an adopted hot spare keeps writing to its spare log.
        try:
            logs = [n for n in os.listdir(workdir) if n.endswith(".log")]
        except OSError:
            logs = []
        for name in logs:
            try:
                with open(os.path.join(workdir, name), "rb") as f:
                    for line in f:
                        if b"committed=True" in line:
                            committed += 1
                        if b"healing from replica" in line:
                            heals += 1
            except OSError:
                pass
        return {
            "committed_batches": committed,
            "per_group": {},
            "heals": heals,
            "heal_ms": [],
            "kills": len(kill_events),
            "dead_time_s": None,
            "goodput_deadwindow_fraction": None,
            "victim_downtime_s": None,
            "victim_partial_step_s": None,
            "victim_restart_s": None,
            "victim_ft_resume_s": None,
            "victim_heal_transfer_s": None,
            "goodput_self_fraction": None,
            "victims_recovered": False,
            "drain_handoff_gap_s": None,
            "failed_commits_after_kill": {},
            "step_time_stats": None,
            "metrics_stream": False,
        }

    t0 = max(min(ts_list) for ts_list in commits.values())
    t_end = max(max(ts_list) for ts_list in commits.values())
    per_group = {
        g: sum(1 for ts in ts_list if ts >= t0)
        for g, ts_list in sorted(commits.items())
    }

    # Per-step wall-time distributions (perf-trajectory evidence beyond the
    # goodput scalar): commit-interval percentiles per group, plus the
    # Manager's own BUSY-time telemetry (step_summary step_time_ms — wall
    # minus FT waits, the straggler sentinel's signal) where present.
    def _dist(values: list, unit_round: int) -> dict | None:
        ordered = sorted(values)
        if not ordered:
            return None
        return {
            "p50": round(ordered[len(ordered) // 2], unit_round),
            "p99": round(ordered[min(len(ordered) - 1, int(0.99 * len(ordered)))],
                         unit_round),
            "max": round(ordered[-1], unit_round),
            "n": len(ordered),
        }

    step_time_stats: dict[str, dict] = {}
    busy_ms: dict[str, list[float]] = {}
    for ev in events:
        if ev.get("event") == "step_summary" and ev.get("step_time_ms") is not None:
            group = str(ev.get("replica_id", "")).split(":", 1)[0]
            busy_ms.setdefault(group, []).append(float(ev["step_time_ms"]))
    for g, ts_list in sorted(commits.items()):
        ordered = sorted(ts for ts in ts_list if ts >= t0)
        intervals = [b - a for a, b in zip(ordered, ordered[1:])]
        entry: dict = {}
        iv = _dist(intervals, 4)
        if iv:
            entry["interval_s"] = iv
        bz = _dist(busy_ms.get(g, []), 2)
        if bz:
            entry["busy_ms"] = bz
        if entry:
            step_time_stats[g] = entry

    # --- dead-window accounting (all kill plans) -------------------------
    # Shared with the attribution tool: obs/report.py::deadwindow is the
    # single implementation of this arithmetic, so `python -m
    # torchft_tpu.obs.report metrics.jsonl` reproduces the headline
    # fraction from the recorded stream (tests/test_bench_contract.py pins
    # the equality).
    from torchft_tpu.obs import report as obs_report

    dead_total = None
    deadwindow_fraction = None
    victims_recovered = True
    if kill_events:
        dw = obs_report.deadwindow(commits, kill_events)
        dead_total = dw["dead_time_s"] if dw["dead_time_s"] is not None else 0.0
        deadwindow_fraction = dw["fraction"]
        victims_recovered = dw["victims_recovered"]

    # --- cooperative drain: incarnation-aware accounting -----------------
    # The donor keeps COMMITTING after the notice (that is the point), so
    # the gap containing the notice is a normal step gap and the real
    # handoff cost is the incarnation boundary: last donor commit -> first
    # replacement commit.  A negative gap means the replacement overlapped
    # the donor's tail — genuine zero dead time.
    drain_handoff_gap = None
    failed_after_kill: dict[str, int] = {}
    if kill_events:
        first_kill = min(ts for ts, _ in kill_events)
        failed_after_kill = {
            g: sum(1 for ts in ts_list if ts >= first_kill)
            for g, ts_list in sorted(failed.items())
        }
    if plan is not None and plan.get("type") == "drain" and len(kill_events) == 1:
        notice_ts, victim = kill_events[0]
        pre_ids = {
            str(ev.get("replica_id"))
            for ev in events
            if str(ev.get("replica_id", "")).split(":", 1)[0] == victim
            and float(ev["ts"]) <= notice_ts
        }
        old = sorted(
            float(ev["ts"]) for ev in events
            if ev.get("event") == "commit" and ev.get("committed")
            and str(ev.get("replica_id", "")).split(":", 1)[0] == victim
            and str(ev.get("replica_id")) in pre_ids
        )
        new = sorted(
            float(ev["ts"]) for ev in events
            if ev.get("event") == "commit" and ev.get("committed")
            and str(ev.get("replica_id", "")).split(":", 1)[0] == victim
            and str(ev.get("replica_id")) not in pre_ids
        )
        if old and new:
            drain_handoff_gap = min(new) - max(old)
            steps_iv = [b - a for a, b in zip(old, old[1:])]
            med = sorted(steps_iv)[len(steps_iv) // 2] if steps_iv else 0.0
            dead_total = max(0.0, drain_handoff_gap - med)
            victims_recovered = True
            span = t_end - t0
            if span > 0:
                deadwindow_fraction = max(0.0, 1.0 - dead_total / span)
        else:
            victims_recovered = False
            deadwindow_fraction = None

    # --- single-kill decomposition + self-normalized secondary -----------
    victim_downtime = None
    victim_partial_step = None
    victim_restart = None
    victim_ft_resume = None
    victim_heal_transfer = None
    self_fraction = None
    if len(kill_events) == 1:
        kill_ts, victim = kill_events[0]
        before = [ts for ts in commits.get(victim, []) if ts <= kill_ts]
        after = [ts for ts in commits.get(victim, []) if ts > kill_ts]
        if before and after:
            victim_downtime = min(after) - max(before)
            victim_partial_step = kill_ts - max(before)
        # Decompose the dead window so the parts SUM to victim_downtime_s:
        #   downtime = partial_step (last pre-kill commit -> kill)
        #            + restart     (kill -> restarted process's first event)
        #            + ft_resume   (first event -> first post-kill commit).
        # Replica ids are "<group>:<uuid>" with a fresh uuid per
        # incarnation, so the restarted process's first event of any kind
        # marks "process up + JAX initialized"; restart is environment cost
        # (scripted respawn delay + spawn + init), ft_resume is the FT
        # system's own path (rejoin + heal + vote).  Only single-restart
        # trials decompose — if the respawned process died again before its
        # first commit (>1 new incarnation by then), attributing the extra
        # dead window to "FT resume" would be false, so the trial reports
        # None and is counted separately.
        pre_ids = {
            str(ev.get("replica_id"))
            for ev in events
            if str(ev.get("replica_id", "")).split(":", 1)[0] == victim
            and float(ev["ts"]) <= kill_ts
        }
        new_events = [
            (float(ev["ts"]), str(ev.get("replica_id")))
            for ev in events
            if str(ev.get("replica_id", "")).split(":", 1)[0] == victim
            and str(ev.get("replica_id")) not in pre_ids
            and float(ev["ts"]) > kill_ts
        ]
        if new_events and after:
            t_commit = min(after)
            incarnations_by_commit = {
                rid for ts, rid in new_events if ts <= t_commit
            }
            if len(incarnations_by_commit) == 1:
                t_up = min(ts for ts, _ in new_events)
                victim_restart = t_up - kill_ts
                victim_ft_resume = t_commit - t_up
                # Split ft_resume further: heal TRANSFER time is the part
                # striped multi-donor fetch buys down (it scales with donor
                # count), vs rejoin/vote overhead which does not.  The new
                # incarnation's heal_fetched spans before its first commit
                # carry the measured fetch duration.
                heal_transfer_ms = [
                    float(ev["heal_ms"])
                    for ev in events
                    if ev.get("event") == "heal_fetched"
                    and str(ev.get("replica_id")) in incarnations_by_commit
                    and float(ev["ts"]) <= t_commit
                    and ev.get("heal_ms") is not None
                ]
                if heal_transfer_ms:
                    victim_heal_transfer = sum(heal_transfer_ms) / 1e3
        # Self-normalized goodput (SECONDARY; see docstring): the victim's
        # committed count vs its own pre-kill rate extrapolated over the
        # span.  Sensitive to host-load rate drift, which is why the
        # dead-window fraction above is the headline.
        pre = [ts for ts in before if ts >= t0]
        span_pre = kill_ts - t0
        if len(pre) >= 10 and span_pre > 5.0 and t_end > kill_ts:
            rate_pre = len(pre) / span_pre
            expected = rate_pre * (t_end - t0)
            if expected > 0:
                self_fraction = per_group.get(victim, 0) / expected
        if plan is not None and plan.get("type") == "drain":
            # before/after split by the NOTICE time mixes the donor's
            # post-notice commits into "after"; the honest downtime is the
            # incarnation boundary computed above (clamped: an overlapped
            # handoff costs zero, not negative).
            victim_downtime = (
                max(0.0, drain_handoff_gap) if drain_handoff_gap is not None else None
            )
            victim_partial_step = None

    # Goodput cross-check (obs/ledger.py): the commit-count headline vs
    # the ledger/report classification of the SAME stream — two
    # independent accountings that must agree.  >5% disagreement fails
    # the trial: one of them is lying about where the wall time went.
    from torchft_tpu.obs.ledger import crosscheck_goodput

    try:
        crosscheck = crosscheck_goodput(events)
    except Exception as e:  # noqa: BLE001 — a malformed stream already
        # degrades the headline itself; record, don't abort the bench
        crosscheck = {"ok": True, "error": repr(e)}
    assert crosscheck.get("ok", True), (
        f"goodput cross-check failed: dead-window fraction "
        f"{crosscheck.get('deadwindow_fraction')} vs ledger fraction "
        f"{crosscheck.get('ledger_fraction')} disagree by "
        f"{crosscheck.get('disagreement')} (> 0.05) — the commit-count "
        "headline and the ledger accounting diverged on the same stream"
    )

    return {
        "committed_batches": sum(per_group.values()),
        "per_group": per_group,
        "heals": heals,
        "heal_ms": heal_ms,
        "kills": len(kill_events),
        "dead_time_s": round(dead_total, 2) if dead_total is not None else None,
        "goodput_deadwindow_fraction": (
            round(deadwindow_fraction, 4) if deadwindow_fraction is not None else None
        ),
        "goodput_crosscheck": crosscheck,
        "victim_downtime_s": victim_downtime,
        "victim_partial_step_s": victim_partial_step,
        "victim_restart_s": victim_restart,
        "victim_ft_resume_s": victim_ft_resume,
        "victim_heal_transfer_s": victim_heal_transfer,
        "goodput_self_fraction": self_fraction,
        "victims_recovered": victims_recovered,
        "drain_handoff_gap_s": (
            round(drain_handoff_gap, 3) if drain_handoff_gap is not None else None
        ),
        "failed_commits_after_kill": failed_after_kill,
        "step_time_stats": step_time_stats,
        "metrics_stream": True,
    }


def _mean(values) -> float | None:
    vals = [v for v in values if v is not None]
    return round(sum(vals) / len(vals), 2) if vals else None


def _trial_plans(trials: int) -> list:
    """The churn mix: alternating-victim single kills, hot-spare single
    kills (the launcher's spare pool adopts the dead group), back-to-back
    double kills and kill-during-heal trials (the repeated-failure
    scenarios of torchft/manager_integ_test.py:304-352), plus cooperative
    DRAIN trials — the planned-departure path (maintenance/preemption
    notices) measured next to the crash numbers.  >= 10 trials carries
    3 churn, 2 spare, and 2 drain trials."""
    plans: list[dict] = []
    churn = 3 if trials >= 9 else (2 if trials >= 4 else 0)
    spare = 2 if trials >= 8 else 0
    drain = 2 if trials >= 10 else (1 if trials >= 6 else 0)
    singles = max(0, trials - churn - spare - drain)
    for i in range(singles):
        plans.append({"type": "single", "victim": i % 2})
    for i in range(spare):
        plans.append({"type": "single_spare", "victim": (i + 1) % 2})
    for i in range(drain):
        plans.append({"type": "drain", "victim": i % 2})
    for i in range(churn):
        plans.append(
            {"type": "double" if i % 2 == 0 else "during_heal", "victim": (i + 1) % 2}
        )
    return plans


def kill_benchmark() -> dict:
    """Goodput under SIGKILL churn, measured over many scripted-fault trials.

    Round-3 lesson: on this single-core host, TOTAL committed batches is
    the wrong unit — when a group dies, the surviving group's steps get
    FASTER (it stops sharing the CPU and the quorum shrinks).  Round-4
    lesson: even victim-only rate extrapolation is noisy (spread 0.23 over
    3 trials) because host-load drift changes the commit rate within a
    window.  The headline is therefore the DEAD-WINDOW fraction: the
    victim's commit timeline is charged only for the gaps that contain a
    kill, which is exactly the work the fault cost and is insensitive to
    rate drift.  Trials vary the victim and include double-kill and
    kill-during-heal churn; the mean carries a 95% CI."""
    window = float(os.environ.get("TPUFT_BENCH_KILL_WINDOW_S", "45"))
    trials = max(1, int(os.environ.get("TPUFT_BENCH_KILL_TRIALS", "10")))
    base_trials = max(1, int(os.environ.get("TPUFT_BENCH_BASE_TRIALS", "2")))
    plans = _trial_plans(trials)
    bases, kills = [], []
    for _ in range(base_trials):
        with tempfile.TemporaryDirectory(prefix="tpuft_bench_nokill_") as d:
            bases.append(_run_scenario(d, window_s=window, plan=None))
    for plan in plans:
        with tempfile.TemporaryDirectory(prefix="tpuft_bench_kill_") as d:
            kills.append((plan, _run_scenario(d, window_s=window, plan=plan)))

    singles = [k for p, k in kills if p["type"] == "single"]
    spare_trials = [k for p, k in kills if p["type"] == "single_spare"]
    churny = [k for p, k in kills if p["type"] in ("double", "during_heal")]
    drain_pairs = [(p, k) for p, k in kills if p["type"] == "drain"]
    drains = [k for _, k in drain_pairs]

    # The headline fraction is computed over the SINGLE-kill trials only:
    # churn trials run a longer window and charge two kills, so mixing the
    # two populations into one mean/spread compares incommensurable
    # numbers.  Churn is summarized separately, and dead_time_per_kill_s
    # (invariant across classes) shows whether repeated failures cost more
    # per kill than isolated ones.
    fractions = [
        k["goodput_deadwindow_fraction"]
        for k in singles
        if k["goodput_deadwindow_fraction"] is not None
    ]
    if fractions:
        unit = "deadwindow_single_kill"
        mean = sum(fractions) / len(fractions)
        if len(fractions) > 1:
            var = sum((f - mean) ** 2 for f in fractions) / (len(fractions) - 1)
            half = 1.96 * (var ** 0.5) / (len(fractions) ** 0.5)
        else:
            half = 0.0
        ci95 = [round(mean - half, 4), round(min(1.0, mean + half), 4)]
    else:
        # Metrics stream unavailable: legacy total-count fraction (noisy).
        unit = "total(legacy)"
        totals_b = sum(b["committed_batches"] for b in bases) / max(1, len(bases))
        fractions = [
            k["committed_batches"] / max(1.0, totals_b) for _, k in kills
        ]
        mean = sum(fractions) / len(fractions)
        ci95 = None

    per_kill = [
        k["dead_time_s"] / k["kills"]
        for p, k in kills
        # victims_recovered guards the same case the fraction guards: an
        # unrecovered victim's gaps were never charged, so its dead time
        # would read ~0 and drag the per-kill mean down spuriously.
        # single_spare trials are excluded too: their per-kill cost is
        # ~2.8 s BY DESIGN, and mixing them in would break the
        # "churn costs the same per kill as singles" comparison this
        # number exists for (they get spare_victim_downtime_s instead).
        if k.get("dead_time_s") is not None
        and k["kills"]
        and k["victims_recovered"]
        and p["type"] not in ("single_spare", "drain")
    ]
    base_victims = [b["per_group"].get("1", 0) for b in bases if b["per_group"]]
    base_spread = (
        (max(base_victims) - min(base_victims)) / max(1, min(base_victims))
        if base_victims
        else None
    )
    downtimes = [k["victim_downtime_s"] for k in singles if k["victim_downtime_s"]]
    decomposed = [k for k in singles if k["victim_restart_s"] is not None]
    heal_ms = sorted(ms for _, k in kills for ms in k["heal_ms"])
    heals = sum(k["heals"] for _, k in kills)
    self_fracs = [
        k["goodput_self_fraction"]
        for k in singles
        if k["goodput_self_fraction"] is not None
    ]
    return {
        "window_s": window,
        "trials": len(kills),
        "trial_plans": [
            {"type": p["type"], "victim": p["victim"]} for p, _ in kills
        ],
        "goodput_unit": unit,
        "goodput_under_kill_fraction": round(mean, 4),
        "goodput_fraction_ci95": ci95,
        "goodput_fraction_trials": [round(f, 4) for f in fractions],
        "goodput_fraction_spread": round(max(fractions) - min(fractions), 4),
        # Churn evidence: trials that killed the victim AGAIN during or
        # right after recovery, and whether every victim still recovered.
        # Their windows are longer and charge 2 kills, so their fractions
        # are listed separately rather than averaged into the headline.
        "multi_restart_trials": len(churny),
        "churn_fractions": [
            round(k["goodput_deadwindow_fraction"], 4)
            for k in churny
            if k["goodput_deadwindow_fraction"] is not None
        ],
        # Invariant across trial classes: dead seconds charged PER KILL.
        # Churn matching singles here means repeated/overlapping failures
        # cost no more per failure than isolated ones.
        "dead_time_per_kill_s": _mean(per_kill),
        "dead_time_per_kill_s_trials": [round(x, 2) for x in per_kill],
        # Hot-spare pool (launch --spares): the dead group's id is handed
        # to a pre-initialized process, removing the respawn + runtime-init
        # floor from the dead window.  Compare spare_victim_downtime_s with
        # victim_downtime_s (cold restart) below.
        "spare_fractions": [
            round(k["goodput_deadwindow_fraction"], 4)
            for k in spare_trials
            if k["goodput_deadwindow_fraction"] is not None
        ],
        "spare_victim_downtime_s": _mean(
            [k["victim_downtime_s"] for k in spare_trials]
        ),
        "spare_victim_restart_s": _mean(
            [k["victim_restart_s"] for k in spare_trials]
        ),
        "spare_victim_ft_resume_s": _mean(
            [k["victim_ft_resume_s"] for k in spare_trials]
        ),
        # Cooperative drain (the planned-departure path): the replacement
        # is pre-warmed at notice time, so the handoff gap — last donor
        # commit to first replacement commit — is the whole cost; a
        # negative gap means the replacement overlapped the donor's tail.
        # drain_survivor_failed_commits MUST be 0: nobody crashed, so no
        # collective ever failed mid-step.
        "drain_fractions": [
            round(k["goodput_deadwindow_fraction"], 4)
            for k in drains
            if k["goodput_deadwindow_fraction"] is not None
        ],
        "drain_victim_downtime_s": _mean(
            [k["victim_downtime_s"] for k in drains]
        ),
        "drain_handoff_gap_s_trials": [
            k["drain_handoff_gap_s"] for k in drains
            if k.get("drain_handoff_gap_s") is not None
        ],
        "drain_dead_time_s": _mean(
            [k["dead_time_s"] for k in drains if k.get("dead_time_s") is not None]
        ),
        "drain_survivor_failed_commits": sum(
            n
            for p, k in drain_pairs
            for g, n in k.get("failed_commits_after_kill", {}).items()
            if g != str(p["victim"])
        ),
        "drains_recovered": all(k["victims_recovered"] for k in drains),
        "kills_total": sum(k["kills"] for _, k in kills),
        # Secondary: the round-4 self-normalized victim fraction (rate
        # extrapolation; sensitive to load drift — kept for comparability).
        "goodput_self_fraction_trials": [round(f, 4) for f in self_fracs],
        # Baseline noise floor: the undisturbed victim count's own
        # run-to-run spread.
        "baseline_victim_committed": base_victims,
        "baseline_relative_spread": (
            round(base_spread, 4) if base_spread is not None else None
        ),
        "victim_downtime_s": _mean(downtimes),
        "victim_downtime_s_trials": [round(d, 2) for d in downtimes],
        # Downtime decomposition — partial_step + restart + ft_resume sums
        # to victim_decomposed_downtime_s over the SAME single-kill trial
        # subset (multi-incarnation trials refuse to decompose).
        # restart = scripted 3 s respawn delay + process spawn + JAX/XLA
        # init (environment floor — any per-step-FT system pays it,
        # including the reference's torchelastic restart); ft_resume =
        # quorum rejoin + live heal + first commit (the part THIS system
        # is responsible for).
        "victim_decomposed_downtime_s": _mean(
            [k["victim_downtime_s"] for k in decomposed]
        ),
        "victim_partial_step_s": _mean(
            [k["victim_partial_step_s"] for k in decomposed]
        ),
        "victim_restart_s": _mean([k["victim_restart_s"] for k in decomposed]),
        "victim_ft_resume_s": _mean([k["victim_ft_resume_s"] for k in decomposed]),
        # ft_resume split: heal TRANSFER (the wire time striped multi-donor
        # fetch scales down with donor count) vs rejoin/vote overhead.
        "victim_heal_transfer_s": _mean(
            [k.get("victim_heal_transfer_s") for k in decomposed]
        ),
        "decomposition_skipped": sum(
            1
            for k in singles
            if k["victim_downtime_s"] is not None and k["victim_restart_s"] is None
        ),
        "heal_ms_median": heal_ms[len(heal_ms) // 2] if heal_ms else None,
        # Per-step wall-time distributions (commit intervals + Manager busy
        # time, p50/p99/max per replica group) so the perf trajectory
        # captures the step-time SHAPE, not just the goodput scalar.
        "step_time_stats_single_trials": [
            k.get("step_time_stats") for k in singles
        ],
        "step_time_stats_baseline": [b.get("step_time_stats") for b in bases],
        "committed_batches_undisturbed": sum(b["committed_batches"] for b in bases),
        "committed_batches_with_kill": sum(k["committed_batches"] for _, k in kills),
        "per_group_undisturbed": [b["per_group"] for b in bases],
        "per_group_with_kill": [k["per_group"] for _, k in kills],
        # A kill run where the victim never healed is NOT a valid goodput
        # measurement — surface it rather than presenting fraction as if the
        # north-star heal path had been exercised.
        "heals_with_kill": heals,
        "heal_verified": all(
            k["heals"] >= 1 and k["victims_recovered"] for _, k in kills
        ),
        # The per-window fraction charges 1-2 kills against a ~45-60 s
        # window — a failure rate ~100x anything realistic.  The victim's
        # downtime is a fixed per-failure cost, so the steady-state goodput
        # loss at a given MTBF is downtime/MTBF; this field states it for
        # hourly failures against BASELINE.md's <5% target.
        "goodput_fraction_at_hourly_failures": (
            round(1 - _mean(downtimes) / 3600.0, 5) if downtimes else None
        ),
    }


def kill_scenario_benchmark(trials: int | None = None) -> dict:
    """Standalone SIGKILL scenario (``--scenario kill``): N single-kill
    trials whose workdirs — including the per-trial ``metrics.jsonl`` — are
    KEPT, so the attribution tool can replay the exact streams the numbers
    came from::

        python bench.py --scenario kill
        python -m torchft_tpu.obs.report <workdir>/kill_0/metrics.jsonl

    The printed goodput fraction and the report's dead-window fraction are
    the same function over the same data (obs/report.py::deadwindow; the
    fault schedule rides in the stream as ``fault`` records), pinned by
    tests/test_bench_contract.py."""
    window = float(os.environ.get("TPUFT_BENCH_KILL_WINDOW_S", "45"))
    trials = trials if trials is not None else max(
        1, int(os.environ.get("TPUFT_BENCH_KILL_TRIALS", "2"))
    )
    out_root = os.environ.get("TPUFT_BENCH_WORKDIR") or tempfile.mkdtemp(
        prefix="tpuft_bench_kill_"
    )
    results = []
    for i in range(trials):
        d = os.path.join(out_root, f"kill_{i}")
        os.makedirs(d, exist_ok=True)
        plan = {"type": "single", "victim": i % 2}
        results.append(_run_scenario(d, window_s=window, plan=plan))
    fractions = [
        k["goodput_deadwindow_fraction"]
        for k in results
        if k["goodput_deadwindow_fraction"] is not None
    ]
    return {
        "window_s": window,
        "trials": trials,
        "workdir": out_root,
        "metrics_jsonl": [
            os.path.join(out_root, f"kill_{i}", "metrics.jsonl")
            for i in range(trials)
        ],
        "kill_fractions": [round(f, 4) for f in fractions],
        "kill_goodput_fraction": (
            round(sum(fractions) / len(fractions), 4) if fractions else None
        ),
        "victim_downtime_s": _mean([k["victim_downtime_s"] for k in results]),
        "victim_heal_transfer_s": _mean(
            [k.get("victim_heal_transfer_s") for k in results]
        ),
        "heals": sum(k["heals"] for k in results),
        "victims_recovered": all(k["victims_recovered"] for k in results),
        "step_time_stats": [k.get("step_time_stats") for k in results],
    }


def drain_benchmark(trials: int | None = None) -> dict:
    """Standalone cooperative-drain benchmark (``--scenario drain``): N
    drain trials, no kill baseline needed — the criterion is absolute
    (zero survivor commit failures, handoff gap ~one step interval), and
    the numbers land next to the SIGKILL figures in the BENCH_* artifact."""
    window = float(os.environ.get("TPUFT_BENCH_KILL_WINDOW_S", "45"))
    trials = trials if trials is not None else max(
        1, int(os.environ.get("TPUFT_BENCH_DRAIN_TRIALS", "3"))
    )
    results = []
    for i in range(trials):
        plan = {"type": "drain", "victim": i % 2}
        with tempfile.TemporaryDirectory(prefix="tpuft_bench_drain_") as d:
            results.append((plan, _run_scenario(d, window_s=window, plan=plan)))
    fractions = [
        k["goodput_deadwindow_fraction"]
        for _, k in results
        if k["goodput_deadwindow_fraction"] is not None
    ]
    return {
        "window_s": window,
        "trials": trials,
        "drain_fractions": [round(f, 4) for f in fractions],
        "drain_goodput_fraction": (
            round(sum(fractions) / len(fractions), 4) if fractions else None
        ),
        "drain_victim_downtime_s": _mean([k["victim_downtime_s"] for _, k in results]),
        "drain_handoff_gap_s_trials": [
            k["drain_handoff_gap_s"] for _, k in results
            if k.get("drain_handoff_gap_s") is not None
        ],
        "drain_dead_time_s": _mean(
            [k["dead_time_s"] for _, k in results if k.get("dead_time_s") is not None]
        ),
        "drain_victim_restart_s": _mean([k["victim_restart_s"] for _, k in results]),
        "drain_victim_ft_resume_s": _mean(
            [k["victim_ft_resume_s"] for _, k in results]
        ),
        "drain_survivor_failed_commits": sum(
            n
            for p, k in results
            for g, n in k.get("failed_commits_after_kill", {}).items()
            if g != str(p["victim"])
        ),
        "drains_recovered": all(k["victims_recovered"] for _, k in results),
        "heals": sum(k["heals"] for _, k in results),
    }


def straggler_benchmark(trials: int | None = None) -> dict:
    """Straggler sentinel benchmark (``--scenario straggler``): paired
    runs on the same schedule — per trial, one run WITHOUT auto-drain (the
    sentinel detects, but the cluster keeps pacing on the slow host for
    the rest of the window: the MegaScale-style goodput killer) and one
    WITH ``TPUFT_STRAGGLER_AUTO_DRAIN=1`` + a hot spare (the sentinel's
    alert triggers the cooperative-drain rotation).  Reported:

    - detection latency, in wall seconds AND victim steps, against the
      ``TPUFT_STRAGGLER_GRACE_STEPS`` budget (the sentinel's contract is
      detection within grace steps of the slowness onset);
    - post-injection cluster commit rate for both runs, and their ratio —
      the goodput the auto-drain rotation recovered.

    Workdirs (with per-trial ``metrics.jsonl``) are KEPT so
    ``tools/trace_export.py`` can render the sentinel arc as a timeline."""
    window = float(
        os.environ.get(
            "TPUFT_BENCH_STRAGGLER_WINDOW_S",
            os.environ.get("TPUFT_BENCH_KILL_WINDOW_S", "45"),
        )
    )
    trials = trials if trials is not None else max(
        1, int(os.environ.get("TPUFT_BENCH_STRAGGLER_TRIALS", "1"))
    )
    # Sentinel knobs for the embedded lighthouse (read from THIS process's
    # environment at Launcher construction).  Grace 3 keeps detection well
    # inside a 45 s window at ~1 s steps.  Every mutation is restored on
    # exit: a later benchmark in the same process must see the documented
    # defaults, not this scenario's tuning.
    prior = {
        k: os.environ.get(k)
        for k in (
            "TPUFT_STRAGGLER_RATIO",
            "TPUFT_STRAGGLER_GRACE_STEPS",
            "TPUFT_STRAGGLER_AUTO_DRAIN",
        )
    }
    os.environ.setdefault("TPUFT_STRAGGLER_RATIO", "1.5")
    os.environ.setdefault("TPUFT_STRAGGLER_GRACE_STEPS", "3")
    # Effective knobs, captured while set (the finally below restores the
    # caller's environment before the summary is built).
    ratio_used = float(os.environ["TPUFT_STRAGGLER_RATIO"])
    grace_used = int(os.environ["TPUFT_STRAGGLER_GRACE_STEPS"])
    out_root = os.environ.get("TPUFT_BENCH_WORKDIR") or tempfile.mkdtemp(
        prefix="tpuft_bench_straggler_"
    )
    results: list[tuple[dict, dict]] = []
    try:
        for i in range(trials):
            for auto in (False, True):
                os.environ["TPUFT_STRAGGLER_AUTO_DRAIN"] = "1" if auto else "0"
                d = os.path.join(
                    out_root,
                    f"straggler_{i}_{'auto' if auto else 'noauto'}",
                )
                os.makedirs(d, exist_ok=True)
                plan = {
                    "type": "straggler",
                    "victim": i % 2,
                    "auto_drain": auto,
                }
                results.append(
                    (plan, _run_scenario(d, window_s=window, plan=plan))
                )
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    no_auto = [k["straggler"] for p, k in results if not p["auto_drain"]]
    auto = [k["straggler"] for p, k in results if p["auto_drain"]]
    all_s = no_auto + auto
    latencies_s = [
        s["detect_latency_s"] for s in all_s if s["detect_latency_s"] is not None
    ]
    latencies_steps = [
        s["detect_latency_steps"]
        for s in all_s
        if s["detect_latency_steps"] is not None
    ]
    rate_no = _mean([s["post_inject_rate_per_s"] for s in no_auto])
    rate_auto = _mean([s["post_inject_rate_per_s"] for s in auto])
    recovered = (
        round(rate_auto / rate_no, 3) if rate_no and rate_auto else None
    )
    return {
        "window_s": window,
        "trials": len(results),
        "workdir": out_root,
        "metrics_jsonl": [
            os.path.join(out_root, f"straggler_{i}_{tag}", "metrics.jsonl")
            for i in range(trials)
            for tag in ("noauto", "auto")
        ],
        "sleep_s": float(os.environ.get("TPUFT_BENCH_STRAGGLE_SLEEP_S", "1.0")),
        "ratio_threshold": ratio_used,
        "grace_steps": grace_used,
        "detected_all": all(s["detected"] for s in all_s) if all_s else False,
        "detect_latency_s_trials": latencies_s,
        "detect_latency_s_mean": _mean(latencies_s),
        "detect_latency_steps_trials": latencies_steps,
        "detect_latency_steps_mean": _mean([float(x) for x in latencies_steps]),
        "detect_latency_slow_steps_trials": [
            s.get("detect_latency_slow_steps")
            for s in all_s
            if s.get("detect_latency_slow_steps") is not None
        ],
        "detected_within_grace": (
            all(s["detected_within_grace"] for s in all_s
                if s["detected_within_grace"] is not None)
            if any(s["detected_within_grace"] is not None for s in all_s)
            else False
        ),
        "rotated_out_all": all(s["rotated_out"] for s in auto) if auto else False,
        "pre_inject_rate_per_s": _mean(
            [s["pre_inject_rate_per_s"] for s in all_s]
        ),
        "post_inject_rate_no_drain": rate_no,
        "post_inject_rate_auto_drain": rate_auto,
        "goodput_recovered_fraction": recovered,
        "auto_drain_beats_no_sentinel": (
            rate_auto > rate_no if rate_no and rate_auto else None
        ),
        "per_trial": [
            {"plan": p, **k["straggler"]} for p, k in results
        ],
    }


def slo_benchmark() -> dict:
    """SLO engine + culprit attribution + IncidentWatcher arc
    (``--scenario slo``): two live control-plane cells on the native
    lighthouse, one degraded and one healthy control.

    Degraded cell: replica groups report healthy goodput ledgers over the
    warmup, then the victim turns stall-heavy mid-run (the straggler's
    ledger signature).  Asserted, per the acceptance criteria:

    - a ``goodput_floor`` incident fires whose attribution names the
      VICTIM replica (``culprit_replica``) with a dominant cause and
      positive ``charged_seconds`` — not "cluster";
    - an ``slo_burn`` alert is raised on ``/alerts.json`` carrying the
      same attribution;
    - the IncidentWatcher journals the recommended policy EXACTLY once
      (the flap guard folds the floor trigger and the burn alert into a
      single debounced recommendation).

    Control cell: the same schedule with every replica healthy — zero
    SLO alerts, zero goodput_floor incidents, empty watcher journal.

    The ledgers are pumped through ``ManagerServer.set_ledger`` (real
    heartbeats, real windowing, real attribution — only the train loop
    is synthetic), so the cell runs in seconds instead of warming up
    5 s windows at real step pace."""
    from torchft_tpu._native import LighthouseServer, ManagerServer
    from torchft_tpu.obs.ledger import LOST_CAUSES
    from torchft_tpu.obs.watcher import IncidentWatcher

    prior = {
        k: os.environ.get(k)
        for k in (
            "TPUFT_SLO_TARGET", "TPUFT_SLO_FAST_S", "TPUFT_SLO_SLOW_S",
            "TPUFT_GOODPUT_WARMUP_OBS", "TPUFT_WATCHER_POLL_S",
            "TPUFT_WATCHER_DEBOUNCE_S",
        )
    }
    os.environ["TPUFT_SLO_TARGET"] = "0.92"
    os.environ["TPUFT_SLO_FAST_S"] = "10"
    os.environ["TPUFT_SLO_SLOW_S"] = "20"
    os.environ["TPUFT_GOODPUT_WARMUP_OBS"] = "2"
    out_root = os.environ.get("TPUFT_BENCH_WORKDIR") or tempfile.mkdtemp(
        prefix="tpuft_bench_slo_"
    )
    stall_i = LOST_CAUSES.index("stall")

    def run_cell(name: str, degrade: bool) -> dict:
        workdir = os.path.join(out_root, name)
        os.makedirs(workdir, exist_ok=True)
        lh = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=200,
            quorum_tick_ms=20, heartbeat_timeout_ms=5000,
            http_bind="127.0.0.1:0",
        )
        groups = ("0", "1", "2")
        victim = groups[-1]
        mgrs = {
            g: ManagerServer(
                replica_id=f"{g}:slo", lighthouse_addr=lh.address(),
                bind="127.0.0.1:0", world_size=1, heartbeat_interval_ms=25,
            )
            for g in groups
        }
        watcher = IncidentWatcher(
            [lh.http_address()], workdir,
            poll_interval_s=0.05, debounce_s=60.0,
        )
        comp = {g: 0.0 for g in groups}
        stall = {g: 0.0 for g in groups}

        def pump(g: str, d_comp: float, d_stall: float) -> None:
            comp[g] += d_comp
            stall[g] += d_stall
            lost = [0.0] * len(LOST_CAUSES)
            lost[stall_i] = stall[g]
            tot = comp[g] + stall[g]
            mgrs[g].set_ledger(comp[g] / tot if tot else -1.0, comp[g], lost)

        try:
            # Healthy phase: everyone at ~97% goodput for several windows.
            for _ in range(8):
                for g in groups:
                    pump(g, 2.91, 0.09)
                watcher.poll_once(force=True)
                time.sleep(0.08)
            # Degraded phase: the victim's ledger turns stall-heavy.
            for _ in range(14):
                for g in groups:
                    if degrade and g == victim:
                        pump(g, 1.0, 9.0)
                    else:
                        pump(g, 2.91, 0.09)
                watcher.poll_once(force=True)
                time.sleep(0.08)
            time.sleep(0.3)
            watcher.poll_once(force=True)
            alerts = _fetch_json(lh.http_address(), "/alerts.json") or {}
            incidents = _fetch_json(lh.http_address(), "/incident.json") or {}
            slo = _fetch_json(lh.http_address(), "/slo.json") or {}
        finally:
            for m in mgrs.values():
                m.shutdown()
            lh.shutdown()
        journal_path = os.path.join(workdir, "watcher_journal.jsonl")
        journal = []
        if os.path.exists(journal_path):
            with open(journal_path, "r", encoding="utf-8") as f:
                journal = [json.loads(ln) for ln in f if ln.strip()]
        burn = [a for a in alerts.get("alerts", []) if a.get("kind") == "slo_burn"]
        floors = [
            r for r in incidents.get("incidents", [])
            if r.get("reason") == "goodput_floor"
        ]
        return {
            "victim": f"{victim}:slo",
            "slo": {k: slo.get(k) for k in (
                "burn_rate_fast", "burn_rate_slow", "error_budget_remaining",
                "alert_active",
            )},
            "slo_burn_alerts": burn,
            "goodput_floor_incidents": floors,
            "journal": journal,
            "workdir": workdir,
        }

    try:
        degraded = run_cell("degraded", degrade=True)
        control = run_cell("control", degrade=False)
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    victim = degraded["victim"]
    floors = degraded["goodput_floor_incidents"]
    burns = degraded["slo_burn_alerts"]
    journal = degraded["journal"]
    # Acceptance criteria (ISSUE 17): hard asserts, not soft reporting.
    assert floors, "degraded cell recorded no goodput_floor incident"
    named = [r for r in floors if r.get("culprit_replica") == victim]
    assert named, (
        f"goodput_floor verdicts named {[r.get('culprit_replica') for r in floors]},"
        f" not the victim {victim}"
    )
    assert named[0].get("dominant_cause") == "stall", named[0]
    assert float(named[0].get("charged_seconds") or 0.0) > 0.0, named[0]
    assert burns, "degraded cell raised no slo_burn alert"
    assert burns[-1].get("replica_id") == victim, burns[-1]
    assert len(journal) == 1, (
        f"watcher journal must hold exactly one flap-guarded entry, got "
        f"{len(journal)}: {journal}"
    )
    assert journal[0]["policy"] == "drain" and journal[0]["acted"] is False
    assert journal[0]["target"] == victim.split(":", 1)[0]
    assert not control["slo_burn_alerts"], control["slo_burn_alerts"]
    assert not control["goodput_floor_incidents"], (
        control["goodput_floor_incidents"]
    )
    assert not control["journal"], control["journal"]
    return {
        "ok": True,
        "workdir": out_root,
        "victim": victim,
        "dominant_cause": named[0].get("dominant_cause"),
        "charged_seconds": named[0].get("charged_seconds"),
        "burn_rate_fast": degraded["slo"].get("burn_rate_fast"),
        "burn_rate_slow": degraded["slo"].get("burn_rate_slow"),
        "error_budget_remaining": degraded["slo"].get("error_budget_remaining"),
        "journal_entries": len(journal),
        "journal_policy": journal[0]["policy"],
        "control_clean": True,
        "degraded": degraded,
        "control": control,
    }


def _fetch_json(address: str, path: str):
    from torchft_tpu.obs.incident import fetch_json

    return fetch_json(address, path)


def lighthouse_failover_benchmark() -> dict:
    """HA lighthouse failover scenario (``--scenario lighthouse-failover``):
    N lighthouse replicas behind the lease election, G Manager worker
    groups, one SIGKILL of the active leader mid-run.  Criteria (each
    recorded in HA_BENCH.json): quorum formation resumed within one lease
    period of the kill, ZERO failed commits on the (all-healthy) replica
    groups, straggler-sentinel state and /metrics history intact on the
    new leader at epoch+1, the takeover visible as a
    ``lighthouse_failover`` event in the obs stream, and any remaining
    standby still answering as a follower (no dual-serving).  The heavy
    lifting lives in bench_ha.py (quick mode is tier-1's
    test_ha_quick_smoke)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import bench_ha
    finally:
        sys.path.pop(0)
    workdir = os.environ.get("TPUFT_BENCH_WORKDIR") or tempfile.mkdtemp(
        prefix="tpuft_bench_ha_"
    )
    payload = bench_ha.run_failover(
        workdir,
        lighthouses=int(os.environ.get("TPUFT_BENCH_HA_LIGHTHOUSES", "3")),
        groups=int(os.environ.get("TPUFT_BENCH_HA_GROUPS", "2")),
        lease_ms=int(os.environ.get("TPUFT_BENCH_HA_LEASE_MS", "1500")),
        window_s=float(os.environ.get("TPUFT_BENCH_HA_WINDOW_S", "30")),
    )
    payload["workdir"] = workdir
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "HA_BENCH.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return payload


def scale_benchmark() -> dict:
    """O(dozens)-group scale scenario (``--scenario scale``): control-plane
    cells at N in {4, 8, 16, 32} JAX-free Manager groups against one native
    lighthouse (quorum-formation / heartbeat-fan-in / scrape-cost
    histograms vs N, with a correlated half-N SIGKILL preemption wave at
    the largest N asserting quorum reformation, a flight-recorder
    reconstruction of the wave, and zero leaked fds), plus the
    flat-ring-vs-ring2d data-plane sweep on a shaped 60 ms-RTT link.  The
    heavy lifting lives in bench_scale.py (quick mode is tier-1's
    test_scale_quick_smoke); writes SCALE_BENCH.json."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import bench_scale
    finally:
        sys.path.pop(0)
    payload = bench_scale.run_full(
        ns=[int(n) for n in os.environ.get(
            "TPUFT_BENCH_SCALE_NS", "4,8,16,32").split(",")],
        window_s=float(os.environ.get("TPUFT_BENCH_SCALE_WINDOW_S", "10")),
        mbps=float(os.environ.get("TPUFT_BENCH_SCALE_MBPS", "200")),
        rtt_ms=float(os.environ.get("TPUFT_BENCH_SCALE_RTT_MS", "60")),
        trials=int(os.environ.get("TPUFT_BENCH_SCALE_TRIALS", "2")),
    )
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "SCALE_BENCH.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return payload


def diloco_benchmark() -> dict:
    """Streaming semi-sync scenario (``--scenario diloco``): 2 replica
    groups on a shaped 60 ms-RTT link; inner-step throughput with a
    concurrent background fragment sync (int8+EF wire) vs the blocking
    port's stall vs a no-sync ceiling, plus the quantization-error-vs-
    convergence drift cell (int8+EF vs bf16 vs f32 over many rounds).
    The heavy lifting lives in bench_diloco.py (quick mode is tier-1's
    test_diloco_quick_smoke); writes DILOCO_BENCH.json."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import bench_diloco
    finally:
        sys.path.pop(0)
    payload = bench_diloco.run_full(
        rounds=int(os.environ.get("TPUFT_BENCH_DILOCO_ROUNDS", "6")),
        sync_every=int(os.environ.get("TPUFT_BENCH_DILOCO_SYNC_EVERY", "24")),
        inner_ms=float(os.environ.get("TPUFT_BENCH_DILOCO_INNER_MS", "50")),
        model_mb=float(os.environ.get("TPUFT_BENCH_DILOCO_MODEL_MB", "2")),
        mbps=float(os.environ.get("TPUFT_BENCH_DILOCO_MBPS", "200")),
        rtt_ms=float(os.environ.get("TPUFT_BENCH_DILOCO_RTT_MS", "60")),
    )
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "DILOCO_BENCH.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return payload


def elastic_benchmark() -> dict:
    """Elastic quorum scenario (``--scenario elastic``): a seeded
    spot-market arrival/departure trace over live Manager groups with the
    elastic batch engine holding the global batch constant — cooperative
    drains + hot-admit joins crossing the ring2d/ring boundary both ways,
    EC re-shard at every transition, scored by the goodput ledger's commit
    stream against a fixed-size no-churn oracle.  The heavy lifting lives
    in bench_elastic.py (quick mode is tier-1's test_elastic_quick_smoke);
    writes ELASTIC_BENCH.json."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import bench_elastic
    finally:
        sys.path.pop(0)
    payload = bench_elastic.run_full(
        workdir=os.environ.get("TPUFT_BENCH_WORKDIR"),
        seed=int(os.environ.get("TPUFT_BENCH_ELASTIC_SEED", "20")),
        global_batch=int(os.environ.get("TPUFT_BENCH_ELASTIC_GLOBAL_BATCH", "32")),
        per_sample_s=float(
            os.environ.get("TPUFT_BENCH_ELASTIC_PER_SAMPLE_S", "0.02")
        ),
    )
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "ELASTIC_BENCH.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    return payload


_CHIP_MEASUREMENTS = {"flagship": chip_benchmark, "large": large_chip_benchmark}


def _chip_child(which: str) -> dict:
    """Runs one chip measurement in a child that owns the TPU alone and
    returns its result (the child's last stdout line).  A chip belongs to
    one process: this parent stays off JAX, so each measurement — and then
    the kill scenario's workers — starts with the chip free.  A failing
    child fails the benchmark."""
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--chip", which],
        stdout=subprocess.PIPE,
        text=True,
        check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    # The chip result is computed, assembled, and (on any kill-scenario
    # failure) still printed first: a failure on the subprocess-heavy kill
    # path must never discard the on-chip measurement again (round 2 lost its
    # numbers exactly that way).
    chip = _chip_child("flagship")
    result = {
        "metric": "ft_train_goodput",
        "value": chip["ft_tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": None,
        "detail": {
            **chip,
            "baseline_semantics": "vs_baseline = dead-window goodput under "
            "SIGKILL: over each single-kill trial window, every commit gap "
            "of the killed group that contains the kill is charged as "
            "downtime (minus one median step interval) and goodput = "
            "1 - dead/span; the mean over single-kill trials carries a 95% "
            "CI.  Churn trials (back-to-back double kills and "
            "kill-during-heal, multi_restart_trials) run longer windows "
            "with 2 kills, so they are summarized separately "
            "(churn_fractions) and compared through the class-invariant "
            "dead_time_per_kill_s — churn matching singles there means "
            "repeated failures cost no more per failure.  Dead-window "
            "accounting is insensitive to host-load rate drift, which made "
            "earlier rate-extrapolated fractions spread 0.23 over 3 trials "
            "on this 1-core host.  Context for the absolute value: each "
            "window charges a kill per ~45 s (~100x any realistic failure "
            "rate), and victim_restart_s shows most of the dead window is "
            "the environment's process-respawn + JAX-init floor that ANY "
            "per-step-FT system pays — the FT resume itself "
            "(victim_ft_resume_s: rejoin + live heal + commit) is "
            "sub-second.  goodput_fraction_at_hourly_failures restates the "
            "measured downtime against BASELINE.md's <5% target at a "
            "realistic failure rate.  Drain trials (drain_fractions) "
            "measure the PLANNED-departure path: the launcher pre-warms a "
            "replacement at notice time and the donor finishes its step "
            "and exits, so the cost is the donor-to-replacement commit "
            "gap (drain_handoff_gap_s_trials; negative = overlapped) and "
            "survivors must log zero failed commits "
            "(drain_survivor_failed_commits).  The reference publishes no "
            "absolute numbers.",
        },
    }
    result["detail"]["large_model"] = _chip_child("large")
    try:
        kill = kill_benchmark()
    except Exception as e:  # noqa: BLE001
        result["detail"]["kill_benchmark_error"] = repr(e)
        print(json.dumps(result))
        raise
    result["vs_baseline"] = kill["goodput_under_kill_fraction"]
    result["detail"].update(kill)
    print(json.dumps(result))


def selftest() -> None:
    """Fast structural check (no chip, no subprocess windows): verifies both
    scenario entry points are callable with their real signatures so a
    refactor cannot silently break the headline artifact again."""
    import inspect

    sig = inspect.signature(_run_scenario)
    assert list(sig.parameters) == ["workdir", "window_s", "plan"]
    inspect.signature(kill_benchmark).bind()
    inspect.signature(chip_benchmark).bind()
    inspect.signature(drain_benchmark).bind()
    inspect.signature(kill_scenario_benchmark).bind()
    inspect.signature(straggler_benchmark).bind()
    inspect.signature(slo_benchmark).bind()
    inspect.signature(lighthouse_failover_benchmark).bind()
    inspect.signature(scale_benchmark).bind()
    inspect.signature(diloco_benchmark).bind()
    inspect.signature(elastic_benchmark).bind()
    plans = _trial_plans(10)
    assert len(plans) == 10
    assert {p["type"] for p in plans} == {
        "single", "single_spare", "drain", "double", "during_heal"
    }
    assert {p["victim"] for p in plans} == {0, 1}
    assert sum(p["type"] in ("double", "during_heal") for p in plans) >= 3
    assert sum(p["type"] == "drain" for p in plans) >= 2
    print("bench selftest ok")


if __name__ == "__main__":
    if "--selftest" in sys.argv:
        selftest()
    elif "--chip" in sys.argv:
        print(json.dumps(_CHIP_MEASUREMENTS[sys.argv[sys.argv.index("--chip") + 1]]()))
    elif "--scenario" in sys.argv:
        which = sys.argv[sys.argv.index("--scenario") + 1:]
        if not which or which[0] not in (
            "drain", "kill", "straggler", "slo", "lighthouse-failover",
            "scale", "diloco", "elastic",
        ):
            print(f"unknown --scenario {which[:1] or '(missing)'}", file=sys.stderr)
            sys.exit(2)
        if which[0] == "elastic":
            elastic = elastic_benchmark()
            print(
                json.dumps(
                    {
                        "metric": "elastic_goodput",
                        "value": elastic["goodput_ratio_vs_oracle"],
                        "unit": "goodput_fraction_of_fixed_size_oracle",
                        "detail": {
                            "ok": elastic["ok"],
                            "max_transition_dead_s": elastic[
                                "max_transition_dead_s"
                            ],
                            "survivor_failed_commits": elastic[
                                "survivor_failed_commits"
                            ],
                            "constant_global_batch": elastic[
                                "constant_global_batch"
                            ],
                            "crossover_exercised": elastic[
                                "crossover_exercised"
                            ],
                        },
                    }
                )
            )
        elif which[0] == "diloco":
            diloco = diloco_benchmark()
            print(
                json.dumps(
                    {
                        "metric": "diloco_overlap",
                        "value": diloco["overlap"][
                            "inner_throughput_ratio_streaming_vs_nosync"
                        ],
                        "unit": "inner_throughput_fraction_of_nosync",
                        "detail": {
                            "ok": diloco["ok"],
                            "overlap": diloco["overlap"],
                            "quant": diloco["quant"],
                        },
                    }
                )
            )
        elif which[0] == "scale":
            scale = scale_benchmark()
            print(
                json.dumps(
                    {
                        "metric": "scale",
                        "value": scale["summary"].get("ring2d_speedup_by_n"),
                        "unit": "ring2d_speedup_by_group_count",
                        "detail": scale["summary"],
                    }
                )
            )
        elif which[0] == "lighthouse-failover":
            ha = lighthouse_failover_benchmark()
            print(
                json.dumps(
                    {
                        "metric": "lighthouse_failover",
                        "value": ha.get("takeover_s"),
                        "unit": "seconds_to_takeover",
                        "detail": ha,
                    }
                )
            )
        elif which[0] == "slo":
            slo = slo_benchmark()
            print(
                json.dumps(
                    {
                        "metric": "slo_attribution",
                        "value": slo["charged_seconds"],
                        "unit": "charged_seconds_on_named_culprit",
                        "detail": slo,
                    }
                )
            )
        elif which[0] == "straggler":
            straggler = straggler_benchmark()
            print(
                json.dumps(
                    {
                        "metric": "straggler_sentinel",
                        "value": straggler["detect_latency_steps_mean"],
                        "unit": "steps_to_detect",
                        "detail": straggler,
                    }
                )
            )
        elif which[0] == "drain":
            drain = drain_benchmark()
            print(
                json.dumps(
                    {
                        "metric": "drain_goodput",
                        "value": drain["drain_goodput_fraction"],
                        "unit": "deadwindow_drain_fraction",
                        "detail": drain,
                    }
                )
            )
        else:
            kill = kill_scenario_benchmark()
            print(
                json.dumps(
                    {
                        "metric": "kill_goodput",
                        "value": kill["kill_goodput_fraction"],
                        "unit": "deadwindow_single_kill_fraction",
                        "detail": kill,
                    }
                )
            )
    else:
        main()
