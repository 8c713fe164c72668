#!/usr/bin/env python3
"""What flash attention's kernels cost on this chip: a tile, a grid step, one pass against two.

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
magnitude.

Every line also counts the walk: `grid_steps` (the product of each
`pallas_call`'s grid, read from the traced jaxpr), `tiles_visited` (the tiles
that hold a visible pair: heads x n (n + 1) / 2 where causal, heads x n x n
where not) and `us_per_tile`.  Where the two differ the kernel issues steps
that do nothing.  `--noncausal` shapes are read with `causal=False` as well
(forward and the chosen backward), so that one tree gives the cost of an idle
step: `u = T_noncausal / tiles`, `idle = (T_causal - visited * u) /
(grid_steps - visited)`.  `--masked` shapes run the kernels under a packed
int8 mask (`tpuft_dsa_attn_fwd`, `tpuft_dsa_attn_bwd_dkdv_dq`) with
`--kv-group` query heads a KV head and a mask of the Keye cell's density:
every earlier key for a query before `--topk`, then `--topk` a query, spread
evenly over its visible keys.  `--windowed` shapes run the band walk
(`tpuft_swa_fwd`, `tpuft_swa_bwd_dkdv_dq`) under `--window`; their
`tiles_visited` are the band's tiles and the required products those over the
band's pairs.  One JSON line per reading on standard output, all of them in
`chiprun_out/fa_bwd_probe.json`.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PEAK_BF16 = 197e12  # TPU v5e, benchmark/peaks.json


def grid_steps(fn, *operands) -> int:
    """Grid steps of every `pallas_call` that `fn` traces to."""
    import jax

    def steps(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                total += math.prod(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += steps(sub)
        return total

    return steps(jax.make_jaxpr(fn)(*operands).jaxpr)


def even_mask_tile(i, j, tile: int, topk: int):
    """Tile (i, j) of a mask in which query t keeps every key s <= t while
    t < topk and after that exactly topk of them, evenly spread."""
    import jax
    import jax.numpy as jnp

    t = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    s = j * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    spread = ((s + 1) * topk) // (t + 1) > (s * topk) // (t + 1)  # s * topk < 2**31 up to 1M positions
    return (((t < topk) | spread) & (s <= t)).astype(jnp.int8)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shapes", default="32x4096x128,64x4096x128,32x8192x256/128,32x1024x128,4x32768x128,2x65536x128")
    parser.add_argument("--noncausal", default="4x32768x128", help="shapes read with causal=False too")
    parser.add_argument("--masked", default="32x32768x128", help="shapes read under a packed mask of the Keye cell's density")
    parser.add_argument("--windowed", default="64x16384x128", help="shapes read under a window (the band walk)")
    parser.add_argument("--window", type=int, default=512)
    parser.add_argument("--kv-group", type=int, default=8)
    parser.add_argument("--topk", type=int, default=2048)
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

    def operands_of(spec, kv_group=1):
        dims, _, dv = spec.partition("/")
        bh, seq, d = (int(x) for x in dims.split("x"))
        dv = int(dv) if dv else d
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q = jax.random.normal(keys[0], (bh, seq, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (bh // kv_group, seq, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (bh // kv_group, seq, dv), jnp.bfloat16)
        g = jax.random.normal(keys[3], (bh, seq, dv), jnp.bfloat16)
        return bh, seq, d, dv, q, k, v, g

    def read(spec, walk, causal=True, mask=None, kv_group=1, two_pass=False, pairs=None, window=None, **noted):
        """Forward and backward of one shape (`two_pass`: the backward in
        that form too); `walk` names the reading and `noted` goes into each
        of its lines."""
        bh, seq, d, dv, q, k, v, g = operands_of(spec, kv_group)
        scale = d ** -0.5
        side = fa._block_sizes(seq, seq)[0]
        n = seq // side
        tiles = bh * (n * (n + 1) // 2 if causal else n * n)
        if window is not None:
            tiles = bh * len(fa._Walk(True, seq, seq, side, side, window=window).tables[0])
            pairs = bh * (seq * (seq + 1) / 2.0 - (seq - window) * (seq - window + 1) / 2.0)
        if pairs is None:
            pairs = bh * (seq * (seq + 1) / 2.0 if causal else float(seq) * seq)
        need_fwd = 2.0 * pairs * (d + dv)               # QK^T at d, PV at dv
        need_bwd = 2.0 * pairs * (2 * d + 2 * dv)       # dQ, dK at d; dV, dP at dv
        more_kw = {} if mask is None else {"mask": mask, "kv_group": kv_group}
        if window is not None:
            more_kw["window"] = window
        family = "tpuft_dsa_attn" if mask is not None else "tpuft_fa" if window is None else "tpuft_swa"

        def record(what, form, ms, need, steps, passes=1, **more):
            rec = {"shape": spec, "walk": walk, "kv_group": kv_group, "what": what, "form": form, "ms": round(ms, 4),
                   "percent_of_bf16_peak": round(100 * need / (ms / 1e3) / PEAK_BF16, 2),
                   "grid_steps": steps, "tiles_visited": passes * tiles,
                   "us_per_tile": round(ms * 1e3 / (passes * tiles), 4), **noted, **more}
            readings.append(rec)
            print(json.dumps(rec), flush=True)

        fwd = lambda q_, k_, v_: fa._fa_pallas_call(q_, k_, v_, scale, causal, **more_kw)  # noqa: E731
        ms, (o, lse) = timed(jax.jit(fwd), q, k, v)
        record("fwd", family + "_fwd", ms, need_fwd, grid_steps(fwd, q, k, v))
        results = {}
        chosen = "one_pass" if fa._dq_row_resident(seq, d) else "two_pass"
        for form, budget in ((chosen, fa._DQ_ROW_VMEM_BUDGET), ("two_pass", 0)):
            if form in results or (form != chosen and not two_pass):
                continue
            # The backward traced with `budget` bytes for the dq row: a function of
            # its own a form, or the second would be the first's cached trace.
            bwd = lambda q_, k_, v_, o_, lse_, g_: fa._fa_bwd_pallas(q_, k_, v_, o_, lse_, g_, scale, causal, **more_kw)  # noqa: E731
            kept, fa._DQ_ROW_VMEM_BUDGET = fa._DQ_ROW_VMEM_BUDGET, budget
            try:
                steps = grid_steps(bwd, q, k, v, o, lse, g)
                ms, results[form] = timed(jax.jit(bwd).lower(q, k, v, o, lse, g).compile(), q, k, v, o, lse, g)
            except Exception as e:  # noqa: BLE001 — a form the compiler refuses is a reading too
                rec = {"shape": spec, "walk": walk, "what": "bwd", "form": form, "error": f"{type(e).__name__}: {str(e)[:300]}"}
                readings.append(rec)
                print(json.dumps(rec), flush=True)
                continue
            finally:
                fa._DQ_ROW_VMEM_BUDGET = kept
            more = {}
            if form == "two_pass" and chosen in results and chosen != form:
                more["max_diff_over_max"] = [
                    float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for a, b in zip(results[chosen], results[form])
                ]
            record("bwd", form, ms, need_bwd, steps, passes=2 if form == "two_pass" else 1, **more)

    for spec in filter(None, args.shapes.split(",")):
        read(spec, "causal", two_pass=True)
    for spec in filter(None, args.noncausal.split(",")):
        read(spec, "noncausal", causal=False)
    for spec in filter(None, args.masked.split(",")):
        bh, seq = (int(x) for x in spec.split("x")[:2])
        tile = fa._block_sizes(seq, seq)[0]
        rows, cols = zip(*[(i, j) for i in range(seq // tile) for j in range(i + 1)])  # `ops.attention._tri`'s order
        mask = jax.jit(jax.vmap(lambda i, j: even_mask_tile(i, j, tile, args.topk)))(
            jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32))[None]
        selected = int(jnp.sum(mask, dtype=jnp.int32))
        read(spec, "masked", mask=mask, kv_group=args.kv_group, pairs=float(bh) * selected,
             selected_share=selected / (seq * (seq + 1) / 2.0))
    for spec in filter(None, args.windowed.split(",")):
        read(spec, "windowed", window=args.window)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "fa_bwd_probe.json"), "w", encoding="utf-8") as f:
        json.dump({"device": device.device_kind, "readings": readings}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
