"""The `steady` job: fault-tolerant training with nothing failing.

`groups` replica groups (the traffic file says how many), one process and one
chip each, under the program's own supervisor (`torchft_tpu.launch.Launcher`)
with its embedded lighthouse: per step a new seeded batch made on the host, a
quorum, `TrainStep.ft_step` (gradients, cross-group exchange, commit vote,
update), and the loss fetched.  Closed loop: the next step starts when this one
has ended.

The parent (`run`, called by `run.py`) never touches JAX: it starts the
groups, waits, and puts their results together.  Each group (`worker`, this
file run as a program by the Launcher) owns its chip alone.

A later PR adds another kind of job (a kill and a heal, a save and a resume)
as another file here, named by its traffic files' `"job"`.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402

AVG_VS_MEAN_LIMIT = 1e-5  # float32 wire: summation order only (chip_smoke.WIRE_TOL)
METRICS_PATH_ENV = "TPUFT_METRICS_PATH"  # the program's own event stream
WARMUP_INDEX = 1 << 30  # batches of the warm-up steps: indices no window reaches
SETUP_TIMEOUT_S = 1100  # a checkout's first run compiles; the contract allows it 1200 s in all


def chip_env(group: int) -> Dict[str, str]:
    """The TPU runtime's settings that confine one process to chip `group`
    of the host (as `chip_smoke.chip_env`): one process per chip."""
    return {
        "TPU_VISIBLE_CHIPS": str(group),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def make_batch(seed: int, group: int, index: int, traffic: Dict[str, Any], vocab: int):
    """Step `index` of group `group`: token ids drawn from the seed, the
    targets the tokens one place on.  Every seed gives the same shapes."""
    import numpy as np

    rng = np.random.default_rng([seed, group, index])
    tokens = rng.integers(0, vocab, size=(traffic["sequences_per_step"], traffic["seq_len"]), dtype=np.int32)
    return {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)}


# ---------------------------------------------------------------------------
# The parent: orchestration only.
# ---------------------------------------------------------------------------


def run(bench, cell: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        t0_wall: float, platform: str = "tpu", out_dir: Optional[str] = None) -> Dict[str, Any]:
    """Runs the cell once.  Returns {"correct", "attempted", "failed",
    "end_to_end", "per_layer", "device", "breakdown", "checks"}; raises where
    a group failed (the run then has no result)."""
    from torchft_tpu.launch import Launcher

    import torchft_tpu._native  # noqa: F401 — built and loaded once, before any child

    traffic = bench.traffic(cell["traffic"])
    groups = int(traffic["groups"])
    if platform == "tpu" and groups != cell["chips"]:
        raise ValueError(f"{cell['name']}: {groups} groups on {cell['chips']} chips; one chip a group")
    tag = f"{cell['name']}.{seed}" + (".trace" if trace else "")
    out_dir = out_dir or os.path.join(bench.bench_dir, "out")
    run_dir = os.path.join(out_dir, tag + ".run")
    os.makedirs(run_dir, exist_ok=True)
    for name in os.listdir(run_dir):  # a fixed path, so what an earlier run left goes first
        path = os.path.join(run_dir, name)
        if os.path.isfile(path):
            os.remove(path)
    spec = {
        "root": bench.root, "cell": cell["name"], "seed": seed, "seconds": seconds, "trace": trace,
        "t0_wall": t0_wall, "platform": platform, "run_dir": run_dir, "groups": groups,
        "steps_path": os.path.join(out_dir, tag + ".steps.jsonl"),
    }
    spec_path = os.path.join(run_dir, "spec.json")
    with open(spec_path, "w", encoding="utf-8") as f:
        json.dump(spec, f)

    group_env = {g: dict(chip_env(g)) for g in range(groups)} if platform == "tpu" and groups > 1 else {}
    timeout = SETUP_TIMEOUT_S + seconds
    cmd = [sys.executable, os.path.abspath(__file__), spec_path]
    with Launcher(cmd, num_groups=groups, lighthouse="embed", min_replicas=groups,
                  join_timeout_ms=int(traffic.get("join_timeout_ms", 100)), max_restarts=0,
                  log_dir=run_dir, cwd=bench.root, group_env=group_env or None) as launcher:
        deadline = time.monotonic() + timeout
        results = [os.path.join(run_dir, f"g{g}.result.json") for g in range(groups)]
        while not (all(os.path.exists(p) for p in results) and not launcher.running()):
            launcher.supervise_once()
            if launcher.exhausted():
                g = launcher.exhausted()[0]
                raise RuntimeError(f"group {g} failed\n{_tail(os.path.join(run_dir, f'g{g}.log'))}")
            if time.monotonic() > deadline:
                raise RuntimeError(f"no end within {timeout:.0f} s\n{_tail(os.path.join(run_dir, 'g0.log'))}")
            time.sleep(0.02)
        launcher.supervise_once()
        if not launcher.all_exited_clean():
            raise RuntimeError("a group did not exit cleanly")
    found = []
    for p in results:
        with open(p, encoding="utf-8") as f:
            found.append(json.load(f))
    result = combine(found, groups, run_dir)
    # The rate goes under the name this cell reports it by (`tokens_per_s`, or
    # `tokens_per_s.<kind>` where a kind of cell has a bound of its own).
    rate = result["end_to_end"].pop("tokens_per_s")
    for metric in bench.end_to_end(cell["name"]):
        if metric["name"].split(".")[0] == "tokens_per_s":
            result["end_to_end"][metric["name"]] = rate
    return result


def combine(results: List[Dict[str, Any]], groups: int, run_dir: str) -> Dict[str, Any]:
    """One result from the groups': tokens of committed steps summed over
    the groups over group 0's window; every check of every group has to hold."""
    import numpy as np

    from benchmark import compare

    lead = results[0]
    checks: Dict[str, Any] = {f"g{r['group']}.reference": r["reference_check"] for r in results}
    ok = all(r["reference_check"]["ok"] for r in results)
    if groups > 1:
        samples = []
        for g in range(groups):
            with np.load(os.path.join(run_dir, f"g{g}.first_merged.npz")) as z:
                samples.append({k: z[k] for k in z.files})
        locals_ = [{k[6:]: v for k, v in s.items() if k.startswith("local:")} for s in samples]
        for g, s in enumerate(samples):
            avg = {k[4:]: v for k, v in s.items() if k.startswith("avg:")}
            checks[f"g{g}.avg_vs_mean"] = compare.mean_of_locals(avg, locals_, AVG_VS_MEAN_LIMIT)
            ok = ok and checks[f"g{g}.avg_vs_mean"]["ok"]
        digests = {r["params_digest"] for r in results}
        counts = {r["steps"] for r in results}
        checks["digests_identical"] = {"distinct": len(digests), "limit": 1, "ok": len(digests) == 1}
        checks["same_step_count"] = {"distinct": len(counts), "limit": 1, "ok": len(counts) == 1}
        ok = ok and len(digests) == 1 and len(counts) == 1
    infinite = [r["group"] for r in results if not r["finite"]]
    checks["losses_finite"] = {"groups_with_a_loss_not_finite": infinite, "limit": [], "ok": not infinite}
    short = [r["group"] for r in results if r["min_participants"] != groups]
    checks["participants"] = {"groups_with_a_step_short_of_all": short, "limit": [], "ok": not short}
    compiled = sum(r["compiles_in_window"] for r in results)
    checks["compiles_in_window"] = {"count": compiled, "limit": 0, "ok": compiled == 0}
    attempted = lead["steps"]
    failed = max(r["steps"] - r["committed"] for r in results)
    ok = ok and not infinite and not short and failed == 0 and attempted > 0
    tokens = sum(r["committed_tokens"] for r in results)
    end_to_end = {
        "tokens_per_s": tokens / lead["window_s"] if lead["window_s"] > 0 else 0.0,
        "setup_s": lead["setup_s"],
    }
    device = dict(lead["device"], count=groups,
                  memory_peak_bytes=max(r["device"]["memory_peak_bytes"] or 0 for r in results))
    return {
        "correct": bool(ok), "attempted": attempted, "failed": failed, "end_to_end": end_to_end,
        "per_layer": lead.get("per_layer", {}), "device": device, "breakdown": lead.get("breakdown"),
        "checks": checks, "compiled_in_window": compiled,
        "samples": {
            "steps": lead["steps"], "setup_phases_s": lead["setup_phases_s"],
            "reference_after_window_s": lead["reference_after_window_s"],
            "peak_bytes_after": lead["peak_bytes_after"],
            "step_ms": {"median": stats.median(lead["step_ms"]), "max": max(lead["step_ms"]),
                        "p90_nearest_rank": stats.percentile_nearest_rank(lead["step_ms"], 90)} if lead["steps"] else None,
        },
        "cache": [r["cache"] for r in results],
    }


def _tail(path: str, n: int = 5000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return "(no log)"


# ---------------------------------------------------------------------------
# One group: owns its chip.
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts this process's compilations: persistent-cache hits and misses
    (as `chip_smoke.CacheCounter`) and every backend compile."""

    def __init__(self) -> None:
        import jax.monitoring

        self.hits = self.misses = self.compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, _secs: float, **__: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def total(self) -> int:
        return self.compiles + self.hits + self.misses


def worker(spec: Dict[str, Any]) -> Dict[str, Any]:
    gid = int(os.environ["REPLICA_GROUP_ID"])
    run_dir, groups, seed = spec["run_dir"], spec["groups"], spec["seed"]
    phases: Dict[str, float] = {}
    peak_after: Dict[str, Any] = {}  # the allocator's peak so far, phase by phase: says which phase set it
    device = None

    def mark(name: str) -> None:
        """Seconds since the benchmark's process started, at the end of a set-up phase."""
        phases[name] = round(time.time() - spec["t0_wall"], 3)
        if device is not None:
            peak_after[name] = (device.memory_stats() or {}).get("peak_bytes_in_use")

    mark("worker_started")
    os.environ[METRICS_PATH_ENV] = os.path.join(run_dir, f"g{gid}.metrics.jsonl")

    import jax
    import numpy as np

    from benchmark import compare, trace_reduce
    from benchmark.spec import Benchmark

    mark("imports")
    device = jax.devices()[0]
    mark("device")
    if device.platform != spec["platform"]:
        raise RuntimeError(
            f"the benchmark measures {spec['platform']!r} and JAX found {device.platform!r} "
            f"({device.device_kind}) — no result"
        )
    if len(jax.devices()) != 1:
        raise RuntimeError(f"group {gid} sees {len(jax.devices())} devices, not its one chip")
    bench = Benchmark(spec["root"])
    cell = bench.cell(spec["cell"])
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    peaks = bench.peaks(device.device_kind) if device.platform == "tpu" else None
    counter = CompileCounter()
    reference = bench.reference(config["architecture"])
    program = bench.program(config["architecture"])
    tokens_per_step = traffic["sequences_per_step"] * traffic["seq_len"]

    def batch_of(index: int):
        host = make_batch(seed, gid, index, traffic, config["vocab_size"])
        return {k: jax.numpy.asarray(v) for k, v in host.items()}

    # -- the seed's weights and the program's gradient of the first batch ----
    # What the plain reference is compared with after the window: the sample
    # goes to the host now, the reference runs when the train step's state is gone.
    weights = reference.make_weights(seed, config)
    ftmesh, step = program.train_step(config, device)
    first = batch_of(0)
    jax.block_until_ready(weights)
    mark("weights")
    loss0, grads0 = step.grads(weights, first)
    jax.block_until_ready(grads0)
    mark("gradient_program")
    indices = compare.sample_indices(seed, weights)
    local_sample = compare.sample(grads0, indices)
    loss0 = float(loss0)

    state = {"params": weights, "opt": step.init_opt_state(weights)}
    del weights
    manager = program.manager(state, str(gid))
    ftmesh.manager = manager
    mark("optimizer_state_and_manager")
    records: List[Dict[str, Any]] = []
    averager_stats: Dict[str, Any] = {}
    try:
        # -- warm-up: every program and buffer the window will use ----------
        if groups > 1:
            # The first merged step in the split form `ft_step` wraps, to see
            # the averaged gradient: it has to be the float32 mean of the
            # groups' local ones.
            manager.start_quorum()
            averager = program.gradient_averager(manager)
            avg = averager.allreduce(grads0)
            averager_stats = dict(averager.last_stats)
            np.savez(os.path.join(run_dir, f"g{gid}.first_merged.npz"),
                     **{f"local:{k}": v for k, v in local_sample.items()},
                     **{f"avg:{k}": v for k, v in compare.sample(avg, indices).items()})
            if not (manager.should_commit() and manager.num_participants() == groups):
                raise RuntimeError("the first step was not a merged step of every group")
            state["params"], state["opt"] = step.apply(state["params"], state["opt"], avg)
            del avg
        del grads0
        for i in range(int(traffic["warmup_steps"])):
            _one_step(step, manager, state, batch_of(WARMUP_INDEX + i))
        jax.block_until_ready(state["params"])
        mark("warm_up")

        # -- the window -----------------------------------------------------
        trace_dir = os.path.join(run_dir, f"g{gid}.trace")
        trace_from = int(traffic["trace_from_step"]) if spec["trace"] and gid == 0 else None
        trace_to = None if trace_from is None else trace_from + int(traffic["trace_steps"])
        tracing = False
        compiles_before = counter.total()
        seconds, longest = float(spec["seconds"]), 0.0
        t_open_wall, t_open = time.time(), time.monotonic()
        k, next_start = 0, None
        while _go(run_dir, gid, groups, k, stats.may_start(time.monotonic() - t_open, longest, seconds)):
            if trace_from is not None and k == trace_from:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # the harness's annotations only
                options.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                tracing, next_start = True, None
            mono_ns = next_start or time.monotonic_ns()  # back to back: a step starts where the last ended
            with jax.profiler.TraceAnnotation(trace_reduce.STEP, mono_ns=mono_ns, index=k):
                with jax.profiler.TraceAnnotation("next_batch"):
                    batch = batch_of(k + 1)
                parts = {"next_batch": (time.monotonic_ns() - mono_ns) / 1e6}
                loss, committed = _one_step(step, manager, state, batch, parts)
            end_ns = time.monotonic_ns()
            records.append({
                "i": k, "start_mono_ns": mono_ns, "ms": (end_ns - mono_ns) / 1e6, "committed": bool(committed),
                "participants": manager.num_participants(), "loss": loss, "traced": tracing,
                "after_trace": next_start is None and k > 0 and not tracing, "host_ms": parts,
            })
            longest = max(longest, (end_ns - mono_ns) / 1e9)
            k, next_start = k + 1, end_ns
            if tracing and k == trace_to:
                jax.profiler.stop_trace()
                tracing, next_start = False, None
        if tracing:
            jax.profiler.stop_trace()
        compiles_in_window = counter.total() - compiles_before
        peak = peak_after["window"] = (device.memory_stats() or {}).get("peak_bytes_in_use")
        digest = _digest(state["params"]) if groups > 1 else None
    finally:
        manager.shutdown()

    # -- after the window: the comparison with the plain reference -----------
    # Here, so that the allocator's peak read above is the train step's alone
    # and set-up holds only what serves the window.  The weights are made
    # again from the seed (the first update consumed them); the program's
    # loss and gradient sample of the first batch have waited on the host.
    state.clear()
    t_reference = time.monotonic()
    weights = reference.make_weights(seed, config)
    reference_check = compare.against_reference(
        reference, config, weights, first, loss0, local_sample, indices)
    del weights
    reference_s = round(time.monotonic() - t_reference, 3)
    print(json.dumps({"group": gid, "reference_check": reference_check}), flush=True)

    # -- what was counted ----------------------------------------------------
    kept = records[: stats.whole_steps([(r["start_mono_ns"] + r["ms"] * 1e6) / 1e9 - t_open for r in records], seconds)]
    spans = _read_spans(os.environ[METRICS_PATH_ENV])
    for r in kept:
        lo, hi = r["start_mono_ns"], r["start_mono_ns"] + r["ms"] * 1e6
        r["spans"] = {}
        for phase, a, b in spans:
            if lo <= b <= hi:
                r["spans"][phase] = round(r["spans"].get(phase, 0.0) + (b - a) / 1e6, 4)
    with open(spec["steps_path"] if gid == 0 else os.path.join(run_dir, f"g{gid}.steps.jsonl"),
              "w", encoding="utf-8") as f:
        for r in kept:
            f.write(json.dumps(r) + "\n")
    committed = [r for r in kept if r["committed"]]
    window_s = sum(r["ms"] for r in kept) / 1e3
    result: Dict[str, Any] = {
        "group": gid, "reference_check": reference_check, "setup_phases_s": phases,
        "reference_after_window_s": reference_s, "peak_bytes_after": peak_after,
        "steps": len(kept), "committed": len(committed),
        "committed_tokens": len(committed) * tokens_per_step, "window_s": window_s,
        "step_ms": [r["ms"] for r in kept], "setup_s": t_open_wall - spec["t0_wall"],
        "min_participants": min([r["participants"] for r in kept], default=0),
        "finite": all(np.isfinite(r["loss"]) for r in kept),
        "params_digest": digest, "compiles_in_window": compiles_in_window,
        "cache": {"dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"), "hits": counter.hits,
                  "misses": counter.misses, "backend_compiles": counter.compiles},
        "device": {"platform": device.platform, "kind": device.device_kind, "count": 1,
                   "memory_peak_bytes": peak},
    }
    if spec["trace"] and gid == 0:
        reduced = None
        if trace_from is not None and any(r["traced"] for r in kept):
            import glob

            files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
            if files:
                loaded = trace_reduce.load(files[-1], device.platform)
                with open(os.path.join(run_dir, "trace_events.json"), "w", encoding="utf-8") as f:
                    json.dump(loaded, f)
                reduced = trace_reduce.reduce(loaded, spans, program.kernel_names(),
                                              skip_steps=int(traffic.get("trace_skip_steps", 0)))
        if reduced is None:
            raise RuntimeError("the traced run read no operation on the device — no result")
        # Outside the capture, and not the step that follows it on a drained device.
        steady = [r for r in kept if not r["traced"] and not r["after_trace"]]
        ctx = {
            "bench": bench, "cell": cell, "config": config, "traffic": traffic, "peaks": peaks, "steps": kept,
            "steady_steps": steady, "trace": reduced, "averager_stats": averager_stats,
            "alloc_peak_bytes": peak, "tokens_per_step": tokens_per_step,
        }
        result["per_layer"] = {}
        for metric in bench.per_layer(cell["name"]):
            value = bench.reader(metric["name"]).read(ctx)
            if value is not None:
                result["per_layer"][metric["name"]] = float(value)
        result["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    return result


def _one_step(step, manager, state: Dict[str, Any], batch, parts: Optional[Dict[str, float]] = None) -> Any:
    """A quorum, `ft_step`, and the loss on the host.  `parts` receives what
    the host spent in `ft_step` and waiting for the device, in ms."""
    import jax

    t0 = time.monotonic_ns()
    with jax.profiler.TraceAnnotation("ft_step"):
        manager.start_quorum()
        state["params"], state["opt"], loss, committed = step.ft_step(state["params"], state["opt"], batch)
    t1 = time.monotonic_ns()
    with jax.profiler.TraceAnnotation("wait_device"):
        jax.block_until_ready(loss)
        loss = float(loss)
    if parts is not None:
        parts.update(ft_step=(t1 - t0) / 1e6, wait_device=(time.monotonic_ns() - t1) / 1e6)
    return loss, committed


def _go(run_dir: str, gid: int, groups: int, k: int, own: bool) -> bool:
    """Whether step k of the window starts.  With several groups, group 0
    decides by its clock and the others follow, so that no group is left
    waiting for a quorum that will not form."""
    if groups == 1:
        return own
    path = os.path.join(run_dir, f"window_step_{k}")
    if gid == 0:
        with open(path + ".tmp", "w", encoding="utf-8") as f:
            f.write("go" if own else "stop")
        os.replace(path + ".tmp", path)
        return own
    deadline = time.monotonic() + 300
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise RuntimeError(f"group 0 never decided on step {k} of the window")
        time.sleep(0.0005)
    with open(path, encoding="utf-8") as f:
        return f.read() == "go"


def _read_spans(path: str) -> List[Any]:
    """The program's spans as (phase, start_mono_ns, end_mono_ns)."""
    out = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("event") == "span":
                    end = rec["t_mono"] * 1e9
                    out.append((rec["phase"], end - rec["duration_ms"] * 1e6, end))
    except OSError:
        pass
    return out


def _digest(params: Any) -> str:
    """A bitwise checksum of the parameters, computed on the device (the
    bytes of 2.5 GB stay there): per leaf the sum of its 32-bit words and
    their sum weighted by position, both modulo 2**32.  Equal parameters give
    equal digests; it is compared across groups, not kept."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def words(leaf):
        u = jax.lax.bitcast_convert_type(leaf.astype(jnp.float32), jnp.uint32).reshape(-1)
        weight = jnp.arange(u.shape[0], dtype=jnp.uint32) * jnp.uint32(2654435761) + jnp.uint32(1)
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32), jnp.sum(u * weight, dtype=jnp.uint32)])

    return "-".join(f"{int(a):08x}{int(b):08x}" for a, b in (words(l) for l in jax.tree.leaves(params)))


def main(argv: List[str]) -> int:
    with open(argv[1], encoding="utf-8") as f:
        spec = json.load(f)
    # The compile cache's place comes from the Launcher (`export_compile_cache`).
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    result = worker(spec)
    gid = result["group"]
    tmp = os.path.join(spec["run_dir"], f"g{gid}.result.json.tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(result, f)
    os.replace(tmp, os.path.join(spec["run_dir"], f"g{gid}.result.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
