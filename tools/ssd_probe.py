"""Standalone: the `tpuft_ssd_*` kernels (ops/ssd.py) on the chip — against the
recurrence position by position in float32 at 16 heads x 2,048 positions
(output and the four gradients, at the seeded decay's range and at a log decay
of -40 a position), the chunked bf16 scan's error by eighth of 16,384 positions
at the Nemotron cell's 64 heads of 64 in 8 groups, and forward, forward with the
chunks' states and backward timed at that shape with their GB/s.

    chiprun -- python tools/ssd_probe.py [--timing]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def inputs(seed, heads, p, groups, n, seq, dtype, fast=False):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.nn.silu(jax.random.normal(ks[0], (1, seq, heads * p)))
    bm = jax.nn.silu(jax.random.normal(ks[1], (1, seq, groups * n)))
    cm = jax.nn.silu(jax.random.normal(ks[2], (1, seq, groups * n)))
    # the seeded layer's: steps log-uniform in [0.001, 0.1], rates uniform in [1, 16]
    dt = jnp.exp(jax.random.uniform(ks[3], (1, seq, heads), minval=jnp.log(0.001), maxval=jnp.log(0.1)))
    la = -jax.random.uniform(ks[4], (heads,), minval=1.0, maxval=16.0) * dt
    if fast:
        la = la.at[:, ::3].set(-40.0)
    xdt = x * jnp.repeat(dt, p, axis=-1)
    return xdt.astype(dtype), bm.astype(dtype), cm.astype(dtype), la


def timed(fn, *args, repeats=5):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--timing", action="store_true", help="leave the comparisons out")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import ssd

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(f"JAX found {device.platform!r}, not a TPU — no reading")
    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32)) / jnp.linalg.norm(b.astype(jnp.float32)))  # noqa: E731
    bf16, f32 = jnp.bfloat16, jnp.float32

    if not args.timing:
        heads, p, groups, n, seq = 16, 64, 2, 128, 2048
        kw = dict(head_dim=p, groups=groups)
        for fast in (False, True):
            ops = inputs(1, heads, p, groups, n, seq, bf16, fast)
            as_f32 = tuple(a.astype(f32) for a in ops)
            weight = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)
            run = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(ssd.ssd(*a, **kw).astype(f32) * weight), argnums=(0, 1, 2, 3)))
            loop = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(ssd.ssd_loop(*a, **kw)[0] * weight), argnums=(0, 1, 2, 3)))
            (_, got), (_, want) = run(*ops), loop(*as_f32)
            y, y_loop = jax.jit(lambda *a: ssd.ssd(*a, **kw))(*ops), jax.jit(lambda *a: ssd.ssd_loop(*a, **kw)[0])(*as_f32)
            print(json.dumps({"kernels_vs_loop": "bf16 kernels against the float32 recurrence", "shape": [heads, seq, p],
                              "a_log_decay_of_-40_every_third_position": fast, "finite": bool(jnp.all(jnp.isfinite(y))),
                              "y": rel(y, y_loop), **{"d" + k: rel(g, w) for k, g, w in zip(("xdt", "B", "C", "la"), got, want)}}),
                  flush=True)

        heads, p, groups, n, seq = 64, 64, 8, 128, 16_384
        kw = dict(head_dim=p, groups=groups)
        ops = inputs(2, heads, p, groups, n, seq, bf16)
        y = jax.jit(lambda *a: ssd.ssd(*a, **kw))(*ops)
        y_loop = jax.jit(lambda *a: ssd.ssd_loop(*a, **kw)[0])(*(a.astype(f32) for a in ops))
        eighth = seq // 8
        print(json.dumps({"chunked_bf16_scan_vs_float32_recurrence_by_eighth_of_16384_positions":
                          [rel(y[:, i * eighth:(i + 1) * eighth], y_loop[:, i * eighth:(i + 1) * eighth]) for i in range(8)],
                          "whole": rel(y, y_loop), "decay_mean": float(jnp.mean(jnp.exp(ops[3])))}), flush=True)

    heads, p, groups, n, seq = 64, 64, 8, 128, 16_384
    xdt, bm, cm, la = inputs(3, heads, p, groups, n, seq, bf16)
    per_group, chunks = heads // groups, seq // ssd.CHUNK
    c = jnp.cumsum(la.reshape(1, chunks, ssd.CHUNK, groups, per_group), axis=2)
    c_col = jnp.moveaxis(c, 3, 1).reshape(1, groups, seq, per_group)
    c_row = jnp.transpose(c, (0, 3, 1, 4, 2))
    fwd = jax.jit(lambda *a: ssd._fwd_pallas(*a, p, ssd.CHUNK, False)[0])
    fwd_states = jax.jit(lambda *a: ssd._fwd_pallas(*a, p, ssd.CHUNK, True))
    bwd = jax.jit(lambda *a: ssd._bwd_pallas(*a, p, ssd.CHUNK))
    _, states = fwd_states(xdt, bm, cm, c_col, c_row)
    row, shared = seq * heads * p * 2, seq * groups * n * 2
    lines = {
        "tpuft_ssd_fwd": (timed(fwd, xdt, bm, cm, c_col, c_row), 2 * row + 2 * shared),
        "tpuft_ssd_fwd_with_states": (timed(fwd_states, xdt, bm, cm, c_col, c_row), 2 * row + 2 * shared + states.size * 4),
        "tpuft_ssd_bwd": (timed(bwd, xdt, bm, cm, c_col, c_row, states, xdt), 3 * row + 4 * shared + states.size * 4),
    }
    for name, (ms, nbytes) in lines.items():
        print(json.dumps({"kernel": name, "shape": [heads, seq, p], "grid": [groups, chunks], "ms": ms,
                          "us_a_group_and_chunk": ms * 1e3 / (groups * chunks), "gb_per_s": nbytes / ms / 1e6}), flush=True)
    print(json.dumps({"ok": True, "device": {"platform": device.platform, "kind": device.device_kind}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
