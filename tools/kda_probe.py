"""Standalone: the `tpuft_kda_*` kernels at the Kimi cell's shape (32 heads x
16,384 positions x 128), timed forward, forward with the chunks' states and
backward at 1, 2, 4 and 8 heads a grid step (the traced grid and µs a head and
chunk beside each), and at 4 heads x 2,048 positions compared with the
recurrence position by position in float32 (output and all five gradients, at
decays from the seeded gate's range and at g = -20 a position).

    chiprun -- python tools/kda_probe.py [--heads 1,2,4,8] [--timing]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def inputs(seed, heads, seq, width, g_scale, dtype):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (1, heads, seq, width))) * width ** -0.5
    k = unit(jax.random.normal(ks[1], (1, heads, seq, width)))
    v = jax.random.normal(ks[2], (1, heads, seq, width))
    g = -g_scale * jax.random.uniform(ks[3], (1, heads, seq, width))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, heads, seq)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def timed(fn, *args, repeats=5):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def grid_of(fn, *args):
    """The grid of the one `pallas_call` that ``fn`` traces."""
    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    (grid,) = found
    return grid


def compare(da) -> None:
    import jax
    import jax.numpy as jnp

    for g_scale in (0.1, 2.0, 20.0):
        args = inputs(1, 4, 2048, 128, g_scale, jnp.bfloat16)
        co = jax.random.normal(jax.random.PRNGKey(7), (1, 4, 2048, 128))
        got = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(da.kda(*a).astype(jnp.float32) * co), argnums=range(5)))(*args)
        want = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(da.kda_loop(*a)[0] * co), argnums=range(5)))(*args)
        rel = [float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)) for a, b in zip(got[1], want[1])]
        print(json.dumps({"g_scale": g_scale, "loss": [float(got[0]), float(want[0])],
                          "grad_rel_q_k_v_g_beta": rel,
                          "finite": bool(all(jnp.all(jnp.isfinite(a)) for a in got[1]))}), flush=True)
    # how the chunked bfloat16 scan's error grows along 16,384 positions: 4 heads against the float32 recurrence,
    # the error's norm over the reference's, by eighth of the sequence
    args = inputs(3, 4, 16384, 128, 0.3, jnp.bfloat16)
    o = jax.jit(da.kda)(*args).astype(jnp.float32)
    want = jax.jit(lambda *a: da.kda_loop(*a)[0])(*args)
    eighths = [float(jnp.linalg.norm(o[:, :, i:i + 2048] - want[:, :, i:i + 2048]) / jnp.linalg.norm(want[:, :, i:i + 2048]))
               for i in range(0, 16384, 2048)]
    print(json.dumps({"error_by_eighth_of_16384_positions": eighths}), flush=True)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--heads", default="1,2,4,8", help="heads a grid step to time, a list")
    parser.add_argument("--timing", action="store_true", help="the timings alone, no comparison with the recurrence")
    opts = parser.parse_args()
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import delta_attention as da

    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    if not opts.timing:
        compare(da)
    bh, seq = 32, 16384
    args = inputs(2, bh, seq, 128, 0.5, jnp.bfloat16)
    flat = [a.reshape(bh, seq, *a.shape[3:]) for a in args]
    steps = bh * seq // da.CHUNK                                  # heads x chunks
    first = None
    for heads in (int(h) for h in opts.heads.split(",")):
        fwd = jax.jit(lambda *a: da._fwd_pallas(*a, da.CHUNK, False, heads_per_step=heads)[0])
        fwd_states = jax.jit(lambda *a: da._fwd_pallas(*a, da.CHUNK, True, heads_per_step=heads))
        bwd = jax.jit(lambda *a: da._bwd_pallas(*a, da.CHUNK, heads_per_step=heads))
        try:
            o, states = fwd_states(*flat)
            ms = {"fwd": timed(fwd, *flat), "fwd_states": timed(fwd_states, *flat), "bwd": timed(bwd, *flat, states, o)}
            results = (o,) + tuple(bwd(*flat, states, o))
        except Exception as e:  # noqa: BLE001 — a count the compiler refuses is a line of the table
            print(json.dumps({"heads_per_step": heads, "refused": str(e)[-300:]}), flush=True)
            continue
        first = first or results
        print(json.dumps({"heads_per_step": heads, "grid": grid_of(fwd, *flat), "grid_bwd": grid_of(bwd, *flat, states, o),
                          "ms": ms, "us_a_head_and_chunk": {name: 1e3 * t / steps for name, t in ms.items()},
                          "bitwise_the_first_line": all(bool(jnp.array_equal(a, b)) for a, b in zip(results, first))}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
