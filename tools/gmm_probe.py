#!/usr/bin/env python3
"""What the grouped matmuls of an expert layer cost on this chip, three ways.

    chiprun -- python tools/gmm_probe.py            # the OLMoE cell's shapes

A standalone probe: no cell runs it.  Rows of `--tokens` x `--top-k`
(token, expert) assignments over `--experts` experts, the group sizes drawn
from the seed (a multinomial over a mildly uneven router), and for each of
the layer's two shapes — up/gate [rows, hidden] x [experts, hidden, inner] and
down [rows, inner] x [experts, inner, hidden] — the time of the forward
product alone and of forward plus both gradients, median of `--reps` after
one warm-up, for:

- `tpuft_<tile>`: the repo's kernels (`ops/grouped_matmul.py`) at that row
  tile, on the layout they ask for (each group padded to whole tiles);
- `ragged_dot`: `jax.lax.ragged_dot` under autodiff, on the exact sizes;
- `megablox_<tm>x<tk>x<tn>`: `jax.experimental.pallas.ops.tpu.megablox.gmm`
  (its own custom VJP), on the exact sizes.

The weights are float32 as the program holds them; the two others round them
to bf16 first, as a model would (`w.astype(bf16)`), inside the timed call.
Each line gives ms and the share of the bf16 peak that the required
operations (2 * rows * hidden * inner a product, padding not counted) reach.
One JSON line per reading on standard output, all of them in
`chiprun_out/gmm_probe.json`.
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
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--top-k", type=int, default=8)
    parser.add_argument("--experts", type=int, default=64)
    parser.add_argument("--hidden", type=int, default=2048)
    parser.add_argument("--inner", type=int, default=1024)
    parser.add_argument("--tiles", default="128,256,512")
    parser.add_argument("--megablox", default="512x1024x1024,256x2048x1024,128x128x128")
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)

    import numpy as np

    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops.grouped_matmul import grouped_matmul, padded_group_sizes

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"the probe measures a TPU and JAX found {device.platform!r}", file=sys.stderr)
        return 1
    rows, n_exp = args.tokens * args.top_k, args.experts
    rng = np.random.default_rng(args.seed)
    share = rng.dirichlet(np.full(n_exp, 30.0))  # max/mean near 1.4
    counts = rng.multinomial(rows, share).astype(np.int32)
    readings = []

    def timed(fn, *operands):
        jax.block_until_ready(fn(*operands))
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(time.perf_counter() - t)
        return statistics.median(times) * 1e3

    def measure(name, product, lhs, rhs, sizes, k, n):
        """`product(lhs, rhs, sizes)` -> [M, n]; timed alone and under grad."""
        fwd = jax.jit(product)
        both = jax.jit(jax.grad(lambda l, r, s: jnp.sum(product(l, r, s).astype(jnp.float32) * 1e-3), argnums=(0, 1)))
        need = 2.0 * rows * k * n
        for what, fn, products in (("fwd", fwd, 1), ("fwd+bwd", both, 3)):
            try:
                ms = timed(fn, lhs, rhs, sizes)
            except Exception as e:  # noqa: BLE001 — a candidate the compiler refuses is a reading too
                rec = {"candidate": name, "shape": [k, n], "what": what, "error": f"{type(e).__name__}: {str(e)[:300]}"}
            else:
                rec = {"candidate": name, "shape": [k, n], "what": what, "ms": round(ms, 4),
                       "percent_of_bf16_peak": round(100 * products * need / (ms / 1e3) / PEAK_BF16, 2),
                       "rows_with_padding": int(lhs.shape[0])}
            readings.append(rec)
            print(json.dumps(rec), flush=True)

    def operands(k, n, sizes, total_rows, key):
        """bf16 rows (zeros outside the groups' real rows) and f32 matrices."""
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        real = np.zeros(total_rows, bool)
        for s, c in zip(starts, counts):
            real[s:s + c] = True
        lhs = jax.random.normal(key, (total_rows, k), jnp.bfloat16) * jnp.asarray(real)[:, None].astype(jnp.bfloat16)
        rhs = jax.random.normal(jax.random.fold_in(key, 1), (n_exp, k, n), jnp.float32) * k ** -0.5
        return lhs, rhs, jnp.asarray(sizes, jnp.int32)

    key = jax.random.PRNGKey(args.seed)
    for k, n in ((args.hidden, args.inner), (args.inner, args.hidden)):
        for tile in (int(t) for t in args.tiles.split(",")):
            sizes = np.asarray(padded_group_sizes(jnp.asarray(counts), tile))
            total = -(-(rows + n_exp * tile) // tile) * tile
            lhs, rhs, s = operands(k, n, sizes, total, key)
            measure(f"tpuft_{tile}", lambda l, r, s_, tile=tile: grouped_matmul(l, r, s_, row_tile=tile), lhs, rhs, s, k, n)
        lhs, rhs, s = operands(k, n, counts, rows, key)
        measure("ragged_dot", lambda l, r, s_: jax.lax.ragged_dot(l, r.astype(l.dtype), s_), lhs, rhs, s, k, n)
        from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

        for tiling in args.megablox.split(","):
            tm, tk, tn = (int(t) for t in tiling.split("x"))
            tiles = (tm, min(tk, k), min(tn, n))
            measure(f"megablox_{tiling}",
                    lambda l, r, s_, tiles=tiles: megablox_gmm(l, r.astype(l.dtype), s_, jnp.bfloat16, tiles),
                    lhs, rhs, s, k, n)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "gmm_probe.json"), "w", encoding="utf-8") as f:
        json.dump({"device": device.device_kind, "rows": rows, "experts": n_exp, "counts_max_over_mean":
                   float(counts.max() / counts.mean()), "readings": readings}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
