"""Ring-attention layout benchmark: contiguous vs zigzag causal work balance.

Two complementary outputs, because the virtual CPU mesh SERIALIZES its
8 'devices' onto the host cores — sequential execution measures each
layout's TOTAL work, while real parallel chips pay the per-round MAX:

1. measured: attention forward+backward wall time per layout on the
   8-way virtual ring (XLA_FLAGS=--xla_force_host_platform_device_count=8).
   Both layouts skip fully-masked blocks, so their total FLOPs are equal —
   this run proves zigzag costs nothing extra (and shaves the per-block
   mask/select VPU work off the off-diagonal rounds, which need no
   masking at all in zigzag).
2. analytic: the exact per-device causal work distribution each schedule
   produces (units of one [S_local x S_local] block; exactly what every
   ppermute round executes).  On parallel hardware the ring's wall-clock
   per round is the busiest device, so max/mean IS the speedup the layout
   buys: contiguous -> max = N blocks vs mean (N+1)/2, i.e. ~2x at large
   N; zigzag -> every device identical.

Usage: python tools/bench_ring_layout.py [--seqs 8192,16384]
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"  # schedule arithmetic, not a device metric
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def device_work_blocks(n: int, layout: str):
    """Per-device causal work, in units of ONE [S_local x S_local] block's
    matmuls, summed over the N ring rounds — the exact schedule cost.

    contiguous: device i computes a block for every source at-or-below its
    diagonal -> i+1 blocks.  zigzag: round 0 is the diagonal (2 chunk-level
    causal pieces + 1 full = 3/4 block in matmul area) and every later
    round is half a block on every device."""
    if layout == "contiguous":
        return [i + 1 for i in range(n)]
    return [0.75 + 0.5 * (n - 1)] * n


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seqs", default="8192,16384")
    parser.add_argument("--heads", type=int, default=2)
    parser.add_argument("--d_head", type=int, default=64)
    parser.add_argument("--trials", type=int, default=3)
    args = parser.parse_args()

    from jax.sharding import Mesh

    from torchft_tpu.ops.ring_attention import ring_attention_sharded, to_zigzag

    n = 8
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(1, n), ("data", "sequence"))
    rows = []
    for seq in [int(s) for s in args.seqs.split(",")]:
        rng = np.random.default_rng(0)
        shape = (1, args.heads, seq, args.d_head)
        q = jnp.asarray(rng.standard_normal(shape), dtype=jnp.float32)
        k = jnp.asarray(rng.standard_normal(shape), dtype=jnp.float32)
        v = jnp.asarray(rng.standard_normal(shape), dtype=jnp.float32)

        def time_layout(layout: str) -> float:
            if layout == "zigzag":
                qq, kk, vv = (to_zigzag(x, n, axis=2) for x in (q, k, v))
            else:
                qq, kk, vv = q, k, v

            def loss(q, k, v):
                out = ring_attention_sharded(
                    mesh, q, k, v, causal=True, batch_axis="data",
                    head_axis=None, layout=layout,
                )
                return jnp.sum(out.astype(jnp.float32) ** 2)

            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            jax.block_until_ready(step(qq, kk, vv))  # compile
            times = []
            for _ in range(args.trials):
                t0 = time.perf_counter()
                jax.block_until_ready(step(qq, kk, vv))
                times.append(time.perf_counter() - t0)
            return statistics.median(times)

        t_contig = time_layout("contiguous")
        t_zigzag = time_layout("zigzag")
        rows.append((seq, t_contig, t_zigzag))
        print(
            f"seq {seq:>6}: contiguous {t_contig*1e3:8.1f} ms   "
            f"zigzag {t_zigzag*1e3:8.1f} ms   speedup {t_contig/t_zigzag:5.2f}x",
            flush=True,
        )

    print(
        "\nMeasured on the SEQUENTIAL virtual mesh (total-work parity check;"
        " both layouts skip fully-masked blocks):"
    )
    print("| seq | contiguous fwd+bwd | zigzag fwd+bwd | total-work ratio |")
    print("|---|---|---|---|")
    for seq, tc, tz in rows:
        print(f"| {seq} | {tc*1e3:.0f} ms | {tz*1e3:.0f} ms | {tc/tz:.2f}x |")

    print(
        "\nAnalytic per-device work (blocks/device over the ring; parallel"
        " hardware pays the MAX per round):"
    )
    print("| layout | per-device blocks | max | mean | max/mean |")
    print("|---|---|---|---|---|")
    for layout in ("contiguous", "zigzag"):
        w = device_work_blocks(n, layout)
        disp = ", ".join(f"{x:g}" for x in w)
        print(
            f"| {layout} | [{disp}] | {max(w):g} | {sum(w)/len(w):g} "
            f"| {max(w)/(sum(w)/len(w)):.2f} |"
        )
    wc = device_work_blocks(n, "contiguous")
    wz = device_work_blocks(n, "zigzag")
    print(
        f"\nprojected parallel speedup (contiguous max / zigzag max): "
        f"{max(wc)/max(wz):.2f}x at ring size {n}"
    )


if __name__ == "__main__":
    main()
