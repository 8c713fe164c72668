#!/usr/bin/env python
"""Per-op TPU profile of the flagship training step.

Captures ``jax.profiler.trace`` around chained grad steps and prints the
XLA-op time breakdown parsed straight from the Chrome-trace JSON — no
TensorBoard needed.  This is how the round-3 static-loop win was found
(the trace fully accounts the device step; look for op classes that are
overhead rather than matmul FLOPs, e.g. dynamic-update-slice fusions).

Measurement rules: chain iterations through a data dependency and end the
timed window with a completion barrier.

Usage: python tools/profile_step.py [--steps 3] [--outdir /tmp/jaxprof]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def capture(outdir: str, steps: int) -> str:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from chip_smoke import flagship_config
    from torchft_tpu.models import init_params, loss_fn

    rng = np.random.default_rng(0)
    cfg, B, S = flagship_config()
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(B, S)), dtype=jnp.int32
    )
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    params = init_params(jax.random.PRNGKey(0), cfg)

    # Scalar-carry chaining: every iteration depends on the previous one.
    # The carry must be added on the OUTPUT side — a `0.0*c` inside the
    # grad target is dropped by differentiation (d/dp of it is zero), which
    # silently unchains the iterations.  And the carry must consume EVERY
    # grad leaf or XLA dead-code-eliminates parts of the backward out of
    # the profile.
    def step(p, c):
        g = jax.grad(lambda pp: loss_fn(pp, batch, cfg))(p)
        return (
            sum(jnp.sum(leaf) for leaf in jax.tree.leaves(g)).astype(
                jnp.float32
            )
            + 0.0 * c
        )

    f = jax.jit(step)
    c = jnp.float32(0)
    for _ in range(3):  # warmup/compile outside the trace
        c = f(params, c)
    float(np.asarray(c))

    os.makedirs(outdir, exist_ok=True)
    with jax.profiler.trace(outdir):
        c = jnp.float32(0)
        for _ in range(steps):
            c = f(params, c)
        float(np.asarray(c))

    traces = sorted(
        glob.glob(os.path.join(outdir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime,
    )
    if not traces:
        raise SystemExit(f"no trace written under {outdir}")
    return traces[-1]


def build_report(trace_path: str, steps: int, top: int = 20) -> dict:
    """Parses a Chrome-trace .json.gz into the op-time breakdown.

    Machine-readable (--json prints exactly this): device-side and
    runtime-side profiles can be joined in one report — obs/report.py
    attributes the runtime phases, this gives the on-chip split of the
    'productive' bucket."""
    with gzip.open(trace_path) as fh:
        trace = json.load(fh)
    events = trace["traceEvents"]

    pids, tids = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pids[e["pid"]] = e["args"].get("name", "")
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tids[(e["pid"], e["tid"])] = e["args"].get("name", "")
    device_pids = [p for p, n in pids.items() if "TPU" in str(n)]
    op_tracks = [k for k, n in tids.items() if n == "XLA Ops" and k[0] in device_pids]
    if not op_tracks:
        raise SystemExit(f"no XLA Ops track; processes: {pids}")

    durs: dict = collections.defaultdict(float)
    args_of: dict = {}
    for e in events:
        if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in op_tracks:
            durs[e["name"]] += e.get("dur", 0)
            if e.get("args"):
                args_of.setdefault(e["name"], e["args"])

    total = sum(durs.values())
    ops = []
    for name, d in sorted(durs.items(), key=lambda kv: -kv[1])[:top]:
        a = args_of.get(name, {})
        ops.append(
            {
                "name": name,
                "ms_per_step": round(d / steps / 1e3, 4),
                "gb_accessed": round(int(a.get("bytes_accessed", 0)) / 1e9, 3),
                "category": a.get("hlo_category", "?"),
            }
        )
    classes: dict = collections.defaultdict(float)
    for n, d in durs.items():
        classes[re.sub(r"[.\d]+$", "", n)] += d
    by_class = [
        {"op_class": n, "ms_per_step": round(d / steps / 1e3, 4)}
        for n, d in sorted(classes.items(), key=lambda kv: -kv[1])[:12]
    ]
    return {
        "schema": 1,
        "trace": trace_path,
        "steps": steps,
        "device_total_ms_per_step": round(total / steps / 1e3, 4),
        "distinct_ops": len(durs),
        "ops": ops,
        "by_class": by_class,
    }


def print_report(rep: dict) -> None:
    print(
        f"device ops total: {rep['device_total_ms_per_step']:.2f} ms/step "
        f"({rep['distinct_ops']} distinct ops, {rep['steps']} steps)"
    )
    print(f"\ntop {len(rep['ops'])} ops:")
    for op in rep["ops"]:
        print(
            f"  {op['ms_per_step']:8.3f} ms/step  {op['gb_accessed']:6.2f} GB  "
            f"[{op['category']}]  {op['name'][:50]}"
        )
    print("\nby op class:")
    for c in rep["by_class"]:
        print(f"  {c['ms_per_step']:8.3f} ms/step  {c['op_class']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--outdir", default="/tmp/jaxprof_step")
    ap.add_argument(
        "--trace",
        default=None,
        help="parse an existing .trace.json.gz instead of capturing on-chip",
    )
    ap.add_argument(
        "--json", action="store_true", help="machine-readable report on stdout"
    )
    args = ap.parse_args()
    trace_path = args.trace or capture(args.outdir, args.steps)
    rep = build_report(trace_path, args.steps)
    if args.json:
        print(json.dumps(rep))
    else:
        print_report(rep)
