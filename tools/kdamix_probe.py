"""Standalone: the `tpuft_kdamix_*` kernels (ops/kda_mix.py) at the Kimi
cell's shape — one sequence of 16,384 positions x 32 heads of 128 — each
timed alone, tile height by tile height and block by block, beside the XLA
halves they stand for (`models/kda.py::_kda_before`, `_kda_after`,
forward and gradient), with the bytes each must move over the time as GB/s;
and at 2,048 positions x 4 heads compared with those halves on the chip
(outputs and every gradient).

    chiprun -- python tools/kdamix_probe.py [--tiles 1024,512 --rows 128,64]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NAMES = ("kda_conv_q", "kda_conv_k", "kda_conv_v", "A_log", "dt_bias", "kda_norm", "kda_g_bias")


def timed(fn, *args, repeats=10):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def inputs(seed, batch, seq, heads, dtype):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 24)
    hd = heads * 128
    joined = [jax.random.normal(ks[i], (batch, seq, hd)).astype(dtype) for i in range(6)]     # q0 k0 v0 a gate dout
    major = [jax.random.normal(ks[6 + i], (batch, heads, seq, 128)).astype(dtype) for i in range(4)]  # o dq dk dv
    dg = jax.random.normal(ks[10], (batch, heads, seq, 128))
    b = jax.random.normal(ks[11], (batch, seq, heads)).astype(dtype)
    w = {"kda_conv_q": 0.5 * jax.random.normal(ks[12], (4, hd)), "kda_conv_k": 0.5 * jax.random.normal(ks[13], (4, hd)),
         "kda_conv_v": 0.5 * jax.random.normal(ks[14], (4, hd)), "A_log": jnp.log(jax.random.uniform(ks[15], (heads,), minval=1.0, maxval=16.0)),
         "dt_bias": jax.random.normal(ks[16], (hd,)), "kda_norm": 1 + 0.3 * jax.random.normal(ks[17], (128,)),
         "kda_g_bias": jax.random.normal(ks[18], (hd,))}
    return joined, major, dg, b, w


def compare(seed=1, batch=2, seq=2048, heads=4):
    """Kernels against the XLA halves on the chip: the largest difference over
    the reference's largest value, outputs and every gradient."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.kda import _kda_after, _kda_before
    from torchft_tpu.ops import kda_mix

    (q0, k0, v0, a, gate, dout), (o, dq, dk, dv), dg, b, w = inputs(seed, batch, seq, heads, jnp.bfloat16)
    f32 = jnp.float32
    dot = lambda outs, cots: sum(jnp.sum(x.astype(f32) * c.astype(f32)) for x, c in zip(outs, cots))  # noqa: E731
    rel = lambda x, y: float(jnp.max(jnp.abs(x.astype(f32) - y.astype(f32))) / jnp.maximum(jnp.max(jnp.abs(y.astype(f32))), 1e-30))  # noqa: E731

    def before_xla(q0, k0, v0, a, w):
        outs = _kda_before(q0, k0, v0, a, b, w, heads)[:4]
        return dot(outs, (dq, dk, dv, dg)), outs

    def before_kernels(q0, k0, v0, a, w):
        outs = kda_mix.before(q0, k0, v0, a, *(w[n] for n in NAMES[:5]))
        return dot(outs, (dq, dk, dv, dg)), outs

    def after_xla(o, gate, w):
        out = _kda_after(o, gate, w, 1e-5)
        return dot([out], [dout]), out

    def after_kernels(o, gate, w):
        out = kda_mix.after(o, gate, w["kda_norm"], w["kda_g_bias"], eps=1e-5)
        return dot([out], [dout]), out

    found = {}
    for name, fns, args in (("before", (before_kernels, before_xla), (q0, k0, v0, a, w)),
                            ("after", (after_kernels, after_xla), (o, gate, w))):
        (_, got), got_grads = jax.jit(jax.value_and_grad(fns[0], argnums=range(len(args)), has_aux=True))(*args)
        (_, want), want_grads = jax.jit(jax.value_and_grad(fns[1], argnums=range(len(args)), has_aux=True))(*args)
        pairs = zip(jax.tree.leaves(got), jax.tree.leaves(want))
        found[name] = {"out": [rel(x, y) for x, y in pairs],
                       "grads": [rel(x, y) for x, y in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads))]}
    return found


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="1024")
    ap.add_argument("--rows", default="128")
    ap.add_argument("--skip-xla", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.kda import _kda_after, _kda_before
    from torchft_tpu.ops import kda_mix

    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    print(json.dumps({"kernels_against_xla_halves_2x2048x4": compare()}), flush=True)
    batch, seq, heads = 1, 16384, 32
    (q0, k0, v0, a, gate, dout), (o, dq, dk, dv), dg, b, w = inputs(2, batch, seq, heads, jnp.bfloat16)
    taps = jnp.stack([w[n] for n in NAMES[:3]])
    bias, rate = w["dt_bias"][None], -jnp.repeat(jnp.exp(w["A_log"]), 128)[None]
    norm, g_bias = w["kda_norm"][None], w["kda_g_bias"][None]
    row16 = batch * seq * heads * 128 * 2
    need = {"before_fwd": 9 * row16, "before_bwd": 13 * row16, "after_fwd": 3 * row16, "after_bwd": 5 * row16}
    if not args.skip_xla:
        f32 = jnp.float32
        # each half under a checkpoint, as `_kda_mixer` runs it; the cotangents are arguments, not constants of the program
        before = jax.checkpoint(lambda q0, k0, v0, a, w, b: _kda_before(q0, k0, v0, a, b, w, heads)[:4])
        after = jax.checkpoint(lambda o, gate, w: _kda_after(o, gate, w, 1e-5))
        before_loss = lambda q0, k0, v0, a, w, b, *cots: sum(  # noqa: E731
            jnp.sum(x.astype(f32) * c) for x, c in zip(before(q0, k0, v0, a, w, b), cots))
        after_loss = lambda o, gate, w, dout: jnp.sum(after(o, gate, w).astype(f32) * dout)  # noqa: E731
        print(json.dumps({"xla_halves_ms": {
            "before_fwd": timed(jax.jit(before), q0, k0, v0, a, w, b),
            "before_grad": timed(jax.jit(jax.grad(before_loss, argnums=(0, 1, 2, 3, 4))), q0, k0, v0, a, w, b, dq, dk, dv, dg),
            "after_fwd": timed(jax.jit(after), o, gate, w),
            "after_grad": timed(jax.jit(jax.grad(after_loss, argnums=(0, 1, 2))), o, gate, w, dout)}}), flush=True)
    for tile in (int(t) for t in args.tiles.split(",")):
        for rows in (int(r) for r in args.rows.split(",")):
            kda_mix._ROWS = rows
            ms = {
                "before_fwd": timed(jax.jit(lambda *x: kda_mix._before_fwd_pallas(*x, tile)), q0, k0, v0, a, taps, bias, rate),
                "before_bwd": timed(jax.jit(lambda *x: kda_mix._before_bwd_pallas(*x, tile)), q0, k0, v0, a, taps, bias,
                                    rate, dq, dk, dv, dg),
                "after_fwd": timed(jax.jit(lambda *x: kda_mix._after_fwd_pallas(*x, 1e-5, tile)), o, gate, norm, g_bias),
                "after_bwd": timed(jax.jit(lambda *x: kda_mix._after_bwd_pallas(*x, 1e-5, tile)), o, gate, norm, g_bias, dout),
            }
            print(json.dumps({"tile": tile, "rows": rows, "ms": ms,
                              "gb_per_s": {k: need[k] / v / 1e6 for k, v in ms.items()},
                              "layer_ms_two_forwards_one_backward": 2 * (ms["before_fwd"] + ms["after_fwd"]) + ms["before_bwd"] + ms["after_bwd"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
