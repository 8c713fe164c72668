#!/usr/bin/env python3
"""What flash attention's backward costs on this chip, one pass against two.

    chiprun -- python tools/fa_bwd_probe.py         # the cells' shapes

A standalone probe: no cell runs it.  For each `--shapes` entry
`BHxSxDqk[/Dv]` (bf16 operands from the seed, causal) it times
`ops/attention._fa_bwd_pallas` in the form the shapes select — the one-pass
`tpuft_fa_bwd_dkdv_dq` wherever the dq row fits the VMEM budget — and in the
two-pass form (`tpuft_fa_bwd_dkdv` then `tpuft_fa_bwd_dq`), which the probe
reaches by setting the module's budget to zero around the compile (the
program has no option for it), and the forward kernel beside them: median of
`--reps` after one warm-up, host clock around `block_until_ready`.  Each line
gives ms, the share of the bf16 peak that the REQUIRED products reach (forward 2,
backward 4: dV, dP, dQ, dK over the visible pairs; the recomputed scores are
not counted, as in `benchmark/flops/tpuft_fa.py`), and for the backward the
largest difference between the two forms' results over the largest
magnitude.  One JSON line per reading on standard output, all of them in
`chiprun_out/fa_bwd_probe.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PEAK_BF16 = 197e12  # TPU v5e, benchmark/peaks.json


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shapes", default="32x4096x128,64x4096x128,32x8192x256/128,32x1024x128,4x32768x128,2x65536x128")
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import attention as fa

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"the probe measures a TPU and JAX found {device.platform!r}", file=sys.stderr)
        return 1
    readings = []

    def timed(fn, *operands):
        out = jax.block_until_ready(fn(*operands))
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(time.perf_counter() - t)
        return statistics.median(times) * 1e3, out

    def backward(budget, *operands):
        """The backward compiled with `budget` bytes for the dq row."""
        kept, fa._DQ_ROW_VMEM_BUDGET = fa._DQ_ROW_VMEM_BUDGET, budget
        try:
            return jax.jit(
                lambda q, k, v, o, lse, g: fa._fa_bwd_pallas(q, k, v, o, lse, g, scale, True)
            ).lower(*operands).compile()
        finally:
            fa._DQ_ROW_VMEM_BUDGET = kept

    for spec in args.shapes.split(","):
        dims, _, dv = spec.partition("/")
        bh, seq, d = (int(x) for x in dims.split("x"))
        dv = int(dv) if dv else d
        scale = d ** -0.5
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q, k = (jax.random.normal(kk, (bh, seq, d), jnp.bfloat16) for kk in keys[:2])
        v, g = (jax.random.normal(kk, (bh, seq, dv), jnp.bfloat16) for kk in keys[2:])
        pairs = seq * (seq + 1) / 2.0
        need_fwd = bh * 2.0 * pairs * (d + dv)               # QK^T at d, PV at dv
        need_bwd = bh * 2.0 * pairs * (2 * d + 2 * dv)       # dQ, dK at d; dV, dP at dv

        def record(what, form, ms, need, **more):
            rec = {"shape": spec, "what": what, "form": form, "ms": round(ms, 4),
                   "percent_of_bf16_peak": round(100 * need / (ms / 1e3) / PEAK_BF16, 2), **more}
            readings.append(rec)
            print(json.dumps(rec), flush=True)

        ms, (o, lse) = timed(jax.jit(lambda q_, k_, v_: fa._fa_pallas_call(q_, k_, v_, scale, True)), q, k, v)
        record("fwd", "tpuft_fa_fwd", ms, need_fwd)
        results = {}
        chosen = "one_pass" if fa._dq_row_resident(seq, d) else "two_pass"
        for form, budget in ((chosen, fa._DQ_ROW_VMEM_BUDGET), ("two_pass", 0)):
            if form in results:
                continue
            try:
                ms, results[form] = timed(backward(budget, q, k, v, o, lse, g), q, k, v, o, lse, g)
            except Exception as e:  # noqa: BLE001 — a form the compiler refuses is a reading too
                rec = {"shape": spec, "what": "bwd", "form": form, "error": f"{type(e).__name__}: {str(e)[:300]}"}
                readings.append(rec)
                print(json.dumps(rec), flush=True)
                continue
            more = {}
            if form == "two_pass" and chosen in results and chosen != form:
                more["max_diff_over_max"] = [
                    float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for a, b in zip(results[chosen], results[form])
                ]
            record("bwd", form, ms, need_bwd, **more)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "fa_bwd_probe.json"), "w", encoding="utf-8") as f:
        json.dump({"device": device.device_kind, "readings": readings}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
