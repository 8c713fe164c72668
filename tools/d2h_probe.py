#!/usr/bin/env python3
"""What a device-to-host fetch of one large array costs on this host, and why.

    chiprun -- python tools/d2h_probe.py                         # one process, one chip
    chiprun --chips 4 -- python tools/d2h_probe.py --procs 4     # four at once, a chip each

A standalone probe: it imports nothing of the program and no cell runs it.
Each process makes one float32 array of `--mb` megabytes on its chip and times,
`--reps` times each, every process starting each phase together:

- `host_first_touch` / `host_retouch`: filling a new numpy array of that size,
  and filling it again — the host's rate of writing untouched pages against
  touched ones, with no device in it;
- `fetch_new`: `np.asarray` of a new device array (PJRT lands it in a newly
  allocated host buffer: what `d2h_fetch` does every step);
- `fetch_slices_<MB>`: the same bytes fetched in device slices of that size
  and copied into one touched host buffer (a small landing buffer can be
  reused by the allocator);
- `fetch_pinned`: the array moved to the `pinned_host` memory kind, then
  read from there;
- `h2d_source_reuse`: `jax.device_put` of a host array that is overwritten
  as soon as the call returns — whether the call has read its source by then;
- `fetch_window_all` / `fetch_window_<W>`: twelve arrays of the four-group
  cell's leaf sizes (2.52 GB) fetched largest first, with `copy_to_host_async`
  called on all twelve up front (what the exchange did before PR 28) against a
  window of `W` arrays hinted beyond the one being fetched (0, 1, 2);
  `first_share` is the first fetch's part of the whole: near 1 where the
  first fetch waits for every transfer, 0.30 where each takes its own time;
- `fetch_turns` (with `--procs 4`): `fetch_new`, one process at a time while
  the other three idle — the single-stream rate of the four-chip host;
- `fetch_turns_busy` (with `--procs 4`): the same, while the other three run a
  host load shaped like the four-group cell's between two fetches: a `numpy`
  add over a buffer of `--mb` megabytes and a `jax.device_put` of 268 MB, in
  a loop (`load_rounds`: how many each loader finished) — the single-stream
  rate a group would see inside the cell;
- `fetch_pairs` / `fetch_pairs_busy` (with `--procs 4`): two processes fetch
  at once (ranks 0+1, then 2+3) while the other two idle, or run that load:
  whether two transfers move more bytes a second together than one alone.

`--phases a,b` runs only the named phases (a prefix selects a family:
`fetch_window`).

One JSON line per process and phase goes to
`chiprun_out/d2h_probe/p<procs>.g<i>.jsonl`; the summary (median GB/s per
phase and process count) is printed last.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out", "d2h_probe")
SLICES_MB = (4, 64)
# Elements of the twelve float32 gradient leaves of `internlm2-1.8b` at 4
# layers: embedding and head, three feed-forward leaves, wq / wo, wk / wv,
# three norms.
CELL_LEAF_ELEMS = (92544 * 2048,) * 2 + (4 * 2048 * 8192,) * 3 + (4 * 2048 * 2048,) * 2 \
    + (4 * 2048 * 1024,) * 2 + (4 * 2048,) * 2 + (2048,)
WINDOWS = ("all", 0, 1, 2)


def barrier(sync_dir: str, name: str, rank: int, procs: int) -> None:
    """Every process leaves a file and waits for the others'."""
    open(os.path.join(sync_dir, f"{name}.{rank}"), "w").close()
    deadline = time.monotonic() + 600
    while not all(os.path.exists(os.path.join(sync_dir, f"{name}.{r}")) for r in range(procs)):
        if time.monotonic() > deadline:
            raise TimeoutError(f"barrier {name}: a process did not arrive")
        time.sleep(0.005)


def child(rank: int, procs: int, mb: int, reps: int, sync_dir: str, phases: tuple) -> None:
    import numpy as np

    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    n = mb * 1_000_000 // 4
    nbytes = n * 4
    os.makedirs(OUT, exist_ok=True)
    log = open(os.path.join(OUT, f"p{procs}.g{rank}.jsonl"), "w", encoding="utf-8")

    def note(phase: str, rep: int, seconds: float, moved: int = nbytes, **more) -> None:
        rec = {"phase": phase, "procs": procs, "rank": rank, "rep": rep, "seconds": seconds,
               "gb_per_s": moved / seconds / 1e9, "mb": moved / 1e6, "device": device.device_kind, **more}
        log.write(json.dumps(rec) + "\n")
        log.flush()

    def want(phase: str) -> bool:
        return not phases or any(phase.startswith(p) for p in phases)

    fresh = jax.jit(lambda k: jnp.arange(n, dtype=jnp.float32) + k)

    def new_array(k: int):
        return jax.block_until_ready(fresh(jnp.float32(k)))

    new_array(0)  # compiled before any phase
    k = 0
    for rep in range(reps if want("host_") else 0):
        barrier(sync_dir, f"touch{rep}", rank, procs)
        t0 = time.perf_counter()
        a = np.empty(n, np.float32)
        a.fill(1.0)
        t1 = time.perf_counter()
        a.fill(2.0)
        t2 = time.perf_counter()
        note("host_first_touch", rep, t1 - t0)
        note("host_retouch", rep, t2 - t1)
        del a

    for rep in range(reps if want("fetch_new") else 0):
        k += 1
        x = new_array(k)
        barrier(sync_dir, f"new{rep}", rank, procs)
        t0 = time.perf_counter()
        host = np.asarray(x)
        note("fetch_new", rep, time.perf_counter() - t0, first=float(host[0]))
        del host, x

    landing = np.zeros(n, np.float32)  # touched
    for slice_mb in SLICES_MB:
        m = slice_mb * 1_000_000 // 4
        if m > n or not want(f"fetch_slices_{slice_mb}"):
            continue
        cut = jax.jit(lambda x, i: jax.lax.dynamic_slice(x, (i,), (m,)))
        starts = list(range(0, n - m + 1, m))
        x = new_array(0)
        jax.block_until_ready(cut(x, 0))
        for rep in range(reps):
            k += 1
            x = new_array(k)
            barrier(sync_dir, f"slices{slice_mb}.{rep}", rank, procs)
            t0 = time.perf_counter()
            parts = [cut(x, i) for i in starts]  # dispatched ahead, fetched in turn
            for i, part in zip(starts, parts):
                landing[i:i + m] = np.asarray(part)
            seconds = time.perf_counter() - t0
            rec_bytes = len(starts) * m * 4
            note(f"fetch_slices_{slice_mb}", rep, seconds * nbytes / rec_bytes, first=float(landing[0]))
            del parts, x

    for rep in range(reps if want("fetch_pinned") else 0):
        k += 1
        x = new_array(k)
        barrier(sync_dir, f"pinned{rep}", rank, procs)
        try:
            t0 = time.perf_counter()
            pinned = jax.block_until_ready(jax.device_put(x, x.sharding.with_memory_kind("pinned_host")))
            t1 = time.perf_counter()
            host = np.asarray(pinned)
            t2 = time.perf_counter()
            note("fetch_pinned", rep, t2 - t0, to_pinned_s=t1 - t0, read_s=t2 - t1, first=float(host[0]))
            del host, pinned
        except Exception as e:  # noqa: BLE001 — the probe reports what the backend refuses
            note("fetch_pinned", rep, float("inf"), error=repr(e)[:300])
        del x

    for rep in range(reps if want("h2d_source_reuse") else 0):
        src = np.full(n, 1.0, np.float32)
        barrier(sync_dir, f"h2d{rep}", rank, procs)
        t0 = time.perf_counter()
        y = jax.device_put(src, device)
        t1 = time.perf_counter()
        src.fill(2.0)  # the next step's rewrite of a persistent buffer
        jax.block_until_ready(y)
        t2 = time.perf_counter()
        note("h2d_source_reuse", rep, t1 - t0, until_ready_s=t2 - t0,
             put_saw_rewrite=bool(float(jnp.max(y)) != 1.0))
        del y, src

    def turns(phase: str, at_a_time: int, busy: bool) -> None:
        """`fetch_new` by `at_a_time` processes at once, group after group;
        the others idle, or (`busy`) add over a host buffer and put 268 MB to
        their chip until every fetcher has left its marker."""
        nonlocal k
        if busy:
            load_host = np.ones(n, np.float32)
            load_src = np.ones(268_000_000 // 4, np.float32)
        for rep in range(reps):
            for first in range(0, procs, at_a_time):
                fetchers = range(first, first + at_a_time)
                k += 1
                x = new_array(k) if rank in fetchers else None
                done = [os.path.join(sync_dir, f"{phase}.done{rep}.{r}") for r in fetchers]
                barrier(sync_dir, f"{phase}{rep}.{first}", rank, procs)
                if rank in fetchers:
                    if busy:
                        time.sleep(0.5)  # the loaders are in their loop
                    t0 = time.perf_counter()
                    host = np.asarray(x)
                    seconds = time.perf_counter() - t0
                    open(done[rank - first], "w").close()
                    note(phase, rep, seconds, first=float(host[0]))
                    del host, x
                elif busy:
                    rounds, t0 = 0, time.perf_counter()
                    while not all(map(os.path.exists, done)):
                        np.add(load_host, 1.0, out=load_host)
                        jax.block_until_ready(jax.device_put(load_src, device))
                        rounds += 1
                    note(f"{phase}.load", rep, time.perf_counter() - t0, moved=0, load_rounds=rounds)
                barrier(sync_dir, f"{phase}.end{rep}.{first}", rank, procs)

    # By prefix, `--phases fetch_turns` selects both kinds of turn, `fetch_pairs` both of pair.
    if procs > 1 and want("fetch_turns"):
        turns("fetch_turns", 1, busy=False)
    if procs > 1 and want("fetch_turns_busy"):
        turns("fetch_turns_busy", 1, busy=True)
    if procs == 4 and want("fetch_pairs"):
        turns("fetch_pairs", 2, busy=False)
    if procs == 4 and want("fetch_pairs_busy"):
        turns("fetch_pairs_busy", 2, busy=True)

    sizes = sorted((e * mb // 758 for e in CELL_LEAF_ELEMS), reverse=True)
    make = {e: jax.jit(lambda k, e=e: jnp.arange(e, dtype=jnp.float32) + k) for e in set(sizes)}
    jax.block_until_ready([make[e](jnp.float32(0)) for e in make])
    total = 4 * sum(sizes)
    for window in WINDOWS:
        for rep in range(reps if want(f"fetch_window_{window}") else 0):
            k += 1
            arrays = jax.block_until_ready([make[e](jnp.float32(k)) for e in sizes])
            barrier(sync_dir, f"window{window}.{rep}", rank, procs)
            t0 = time.perf_counter()
            ahead = len(arrays) if window == "all" else window
            hinted = 1  # a fetch starts its own copy
            each = []
            for pos, a in enumerate(arrays):
                for b in arrays[max(hinted, pos + 1):pos + 1 + ahead]:
                    b.copy_to_host_async()
                hinted = max(hinted, pos + 1 + ahead)
                t1 = time.perf_counter()
                host = np.asarray(a)
                each.append(round(time.perf_counter() - t1, 4))
                del host
            seconds = time.perf_counter() - t0
            note(f"fetch_window_{window}", rep, seconds, moved=total, each_s=each, first_share=each[0] / seconds)
            del arrays
    log.close()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--procs", type=int, default=1, choices=(1, 4))
    parser.add_argument("--mb", type=int, default=758)
    parser.add_argument("--reps", type=int, default=3)
    parser.add_argument("--phases", default="", help="comma-separated phase names or prefixes; default all")
    parser.add_argument("--child", type=int)
    parser.add_argument("--sync-dir")
    args = parser.parse_args()
    if args.child is not None:
        child(args.child, args.procs, args.mb, args.reps, args.sync_dir,
              tuple(p for p in args.phases.split(",") if p))
        return 0
    # The parent stays off JAX: a chip belongs to one process.
    with tempfile.TemporaryDirectory() as sync_dir:
        children = []
        for rank in range(args.procs):
            env = dict(os.environ)
            if args.procs > 1:  # one process per chip
                env.update(TPU_VISIBLE_CHIPS=str(rank), TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                           TPU_PROCESS_BOUNDS="1,1,1")
            children.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--child", str(rank), "--procs", str(args.procs),
                 "--mb", str(args.mb), "--reps", str(args.reps), "--phases", args.phases, "--sync-dir", sync_dir], env=env))
        codes = [c.wait() for c in children]
    by_phase = {}
    for rank in range(args.procs):
        try:
            with open(os.path.join(OUT, f"p{args.procs}.g{rank}.jsonl"), encoding="utf-8") as f:
                for rec in map(json.loads, f):
                    by_phase.setdefault(rec["phase"], []).append(rec)
        except OSError:
            pass
    for phase, recs in by_phase.items():
        if phase.endswith(".load"):
            print(json.dumps({"phase": phase, "procs": args.procs, "n": len(recs),
                              "load_rounds": sorted(r["load_rounds"] for r in recs)}), flush=True)
            continue
        rates = [r["gb_per_s"] for r in recs]
        line = {"phase": phase, "procs": args.procs, "mb": recs[0]["mb"], "n": len(rates),
                "gb_per_s_median": statistics.median(rates), "gb_per_s_min": min(rates), "gb_per_s_max": max(rates)}
        if phase == "h2d_source_reuse":
            line["put_saw_rewrite"] = sum(r["put_saw_rewrite"] for r in recs)
            line["until_ready_s_median"] = statistics.median(r["until_ready_s"] for r in recs)
        if phase.startswith("fetch_window_"):
            line["first_share_median"] = statistics.median(r["first_share"] for r in recs)
            line["each_s_of_rank0_rep0"] = next(r["each_s"] for r in recs if r["rank"] == 0 and r["rep"] == 0)
        if phase.startswith("fetch_pairs"):
            line["together_gb_per_s_median"] = 2 * line["gb_per_s_median"]
        if phase == "fetch_pinned":
            errors = sorted({r["error"] for r in recs if "error" in r})
            line.update({"errors": errors} if errors else
                        {"to_pinned_s_median": statistics.median(r["to_pinned_s"] for r in recs),
                         "read_s_median": statistics.median(r["read_s"] for r in recs)})
        print(json.dumps(line), flush=True)
    return max(codes)


if __name__ == "__main__":
    sys.exit(main())
