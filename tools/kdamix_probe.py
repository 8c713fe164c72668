"""Standalone: the `tpuft_kdamix_*` kernels (ops/kda_mix.py) at the Kimi
cell's shape — one sequence of 16,384 positions x 32 heads of 128, a decay a
channel — and at the Qwen3-Next cell's — Gated DeltaNet's 16 key heads under 32
value heads of 128, no decay a channel, SiLU for the gate — each timed alone,
tile height by tile height and block by block, beside the XLA halves they
stand for (`models/kda.py::_kda_before`, `_kda_after`; `models/gdn.py::
_gdn_before`, `_gdn_after`; forward and gradient), with the bytes each must
move over the time as GB/s; and at 2,048 positions x 4 (key) heads compared
with those halves on the chip (outputs and every gradient).  Under Gated
DeltaNet's shapes the half before the scan is timed both ways: v's two lane
tiles riding with the key head that reads them (one call, what the program
runs), and q, k in one call with v in a call of its own.

    chiprun -- python tools/kdamix_probe.py [--forms kda,gdn --tiles 1024,512 --rows 128,64]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LEAVES = {"kda": ("kda_conv_q", "kda_conv_k", "kda_conv_v", "A_log", "dt_bias", "kda_norm", "kda_g_bias"),
          "gdn": ("gdn_conv_q", "gdn_conv_k", "gdn_conv_v", "A_log", "dt_bias", "gdn_norm")}
# arrays of [positions, 4,096] bf16 each kernel must move at the cells' shapes (benchmark/flops/tpuft_kdamix.py, tpuft_gdnmix.py)
UNITS = {"kda": {"before_fwd": 9, "before_bwd": 13, "after_fwd": 3, "after_bwd": 5},
         "gdn": {"before_fwd": 4, "before_bwd": 6, "after_fwd": 3, "after_bwd": 5}}


def timed(fn, *args, repeats=10):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def inputs(seed, batch, seq, heads, dtype, form="kda"):
    """`heads` heads each its own key and a decay a channel (kda), or `heads`
    KEY heads under twice as many value heads and a decay a value head (gdn)."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 24)
    values = heads * (2 if form == "gdn" else 1)
    wide = lambda n: jax.random.normal(ks[n], (batch, seq, (heads if n < 2 else values) * 128)).astype(dtype)  # noqa: E731
    q0, k0, v0, gate, dout = wide(0), wide(1), wide(2), wide(4), wide(5)
    a = jax.random.normal(ks[3], (batch, seq, values * (128 if form == "kda" else 1))).astype(dtype)
    major = lambda n, h: jax.random.normal(ks[n], (batch, h, seq, 128)).astype(dtype)  # noqa: E731
    o, dq, dk, dv = major(6, values), major(7, heads), major(8, heads), major(9, values)
    dg = jax.random.normal(ks[10], (batch, values, seq, 128)) if form == "kda" else None
    b = jax.random.normal(ks[11], (batch, seq, values)).astype(dtype)
    w = {"conv_q": 0.5 * jax.random.normal(ks[12], (4, heads * 128)), "conv_k": 0.5 * jax.random.normal(ks[13], (4, heads * 128)),
         "conv_v": 0.5 * jax.random.normal(ks[14], (4, values * 128)),
         "A_log": jnp.log(jax.random.uniform(ks[15], (values,), minval=1.0, maxval=16.0)),
         "dt_bias": jax.random.normal(ks[16], (values * (128 if form == "kda" else 1),)),
         "norm": 1 + 0.3 * jax.random.normal(ks[17], (128,)), "g_bias": jax.random.normal(ks[18], (values * 128,))}
    w = {name: w[name.replace(form + "_", "")] for name in LEAVES[form]}
    return (q0, k0, v0, a, gate, dout), (o, dq, dk, dv), dg, b, w


def halves(form, heads, b):
    """(before by the kernels, before in XLA, after by the kernels, after in XLA), each of the half's arrays and leaves."""
    from torchft_tpu.models.gdn import _gdn_after, _gdn_before
    from torchft_tpu.models.kda import _kda_after, _kda_before
    from torchft_tpu.ops import kda_mix

    names = LEAVES[form]
    if form == "kda":
        return (lambda q0, k0, v0, a, w: kda_mix.before(q0, k0, v0, a, *(w[n] for n in names[:5])),
                lambda q0, k0, v0, a, w: _kda_before(q0, k0, v0, a, b, w, heads)[:4],
                lambda o, gate, w: kda_mix.after(o, gate, w["kda_norm"], w["kda_g_bias"], eps=1e-5),
                lambda o, gate, w: _kda_after(o, gate, w, 1e-5))
    return (lambda q0, k0, v0, a, w: kda_mix.before(q0, k0, v0, None, *(w[n] for n in names[:3])),
            lambda q0, k0, v0, a, w: _gdn_before(q0, k0, v0, a, b, w, heads, 2 * heads)[:3],
            lambda o, gate, w: kda_mix.after(o, gate, w["gdn_norm"], None, eps=1e-5),
            lambda o, gate, w: _gdn_after(o, gate, w, 1e-5))


def compare(form, seed=1, batch=2, seq=2048, heads=4):
    """Kernels against the XLA halves on the chip: the largest difference over
    the reference's largest value, outputs and every gradient."""
    import jax
    import jax.numpy as jnp

    (q0, k0, v0, a, gate, dout), (o, dq, dk, dv), dg, b, w = inputs(seed, batch, seq, heads, jnp.bfloat16, form)
    before_kernels, before_xla, after_kernels, after_xla = halves(form, heads, b)
    f32 = jnp.float32
    dot = lambda outs, cots: sum(jnp.sum(x.astype(f32) * c.astype(f32)) for x, c in zip(outs, cots))  # noqa: E731
    rel = lambda x, y: float(jnp.max(jnp.abs(x.astype(f32) - y.astype(f32))) / jnp.maximum(jnp.max(jnp.abs(y.astype(f32))), 1e-30))  # noqa: E731
    scalar = lambda fn, cots: lambda *xs: (lambda outs: (dot(outs, cots), outs))(fn(*xs))  # noqa: E731
    found = {}
    for name, fns, cots, args in (("before", (before_kernels, before_xla), (dq, dk, dv, dg), (q0, k0, v0, a, w)),
                                  ("after", (after_kernels, after_xla), (dout,), (o, gate, w))):
        wrap = (lambda fn: fn) if name == "before" else (lambda fn: lambda *xs: (fn(*xs),))
        (_, got), got_grads = jax.jit(jax.value_and_grad(scalar(wrap(fns[0]), cots), argnums=range(len(args)), has_aux=True))(*args)
        (_, want), want_grads = jax.jit(jax.value_and_grad(scalar(wrap(fns[1]), cots), argnums=range(len(args)), has_aux=True))(*args)
        pairs = zip(jax.tree.leaves(got), jax.tree.leaves(want))
        found[name] = {"out": [rel(x, y) for x, y in pairs],
                       "grads": [rel(x, y) for x, y in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads))]}
    return found


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default="kda,gdn")
    ap.add_argument("--tiles", default="1024")
    ap.add_argument("--rows", default="128")
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--skip-xla", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import kda_mix

    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    batch, seq, f32 = 1, args.seq, jnp.float32
    row16 = batch * seq * 4096 * 2
    for form in args.forms.split(","):
        heads = 32 if form == "kda" else 16                                    # Kimi's heads; Gated DeltaNet's KEY heads
        print(json.dumps({"form": form, "kernels_against_xla_halves_2x2048x4": compare(form)}), flush=True)
        (q0, k0, v0, a, gate, dout), (o, dq, dk, dv), dg, b, w = inputs(2, batch, seq, heads, jnp.bfloat16, form)
        names = LEAVES[form]
        need = {k: n * row16 for k, n in UNITS[form].items()}
        if not args.skip_xla:
            # each half under a checkpoint, as the mixer runs it; the cotangents are arguments, not constants of the program
            _, before_xla, _, after_xla = halves(form, heads, b)
            before, after = jax.checkpoint(before_xla), jax.checkpoint(after_xla)
            before_loss = lambda q0, k0, v0, a, w, *cots: sum(  # noqa: E731
                jnp.sum(x.astype(f32) * c) for x, c in zip(before(q0, k0, v0, a, w), cots))
            after_loss = lambda o, gate, w, dout: jnp.sum(after(o, gate, w).astype(f32) * dout)  # noqa: E731
            cots = [c for c in (dq, dk, dv, dg) if c is not None]
            print(json.dumps({"form": form, "xla_halves_ms": {
                "before_fwd": timed(jax.jit(before), q0, k0, v0, a, w),
                "before_grad": timed(jax.jit(jax.grad(before_loss, argnums=(0, 1, 2, 3, 4))), q0, k0, v0, a, w, *cots),
                "after_fwd": timed(jax.jit(after), o, gate, w),
                "after_grad": timed(jax.jit(jax.grad(after_loss, argnums=(0, 1, 2))), o, gate, w, dout)}}), flush=True)
        stack = lambda *xs: jnp.stack(xs).astype(f32)  # noqa: E731
        conv_q, conv_k, conv_v = (w[n] for n in names[:3])
        norm = w[names[5]][None]
        if form == "kda":
            taps, bias, rate = stack(conv_q, conv_k, conv_v), w["dt_bias"][None], -jnp.repeat(jnp.exp(w["A_log"]), 128)[None]
            g_bias, decay = w["kda_g_bias"][None], a
        else:   # v's taps a value head of the key head's, as `kda_mix.before` lays them
            taps = stack(conv_q, conv_k, *conv_v.reshape(4, heads, 2, 128).transpose(2, 0, 1, 3).reshape(2, 4, heads * 128))
            bias = rate = g_bias = decay = None
        for tile in (int(t) for t in args.tiles.split(",")):
            for rows in (int(r) for r in args.rows.split(",")):
                kda_mix._ROWS = rows
                jax.clear_caches()                                              # the four calls are jitted: trace them at these rows
                fwd = lambda *x: kda_mix._before_fwd_pallas(*x, tile)           # noqa: E731
                bwd = lambda *x: kda_mix._before_bwd_pallas(*x, tile)           # noqa: E731
                ms = {
                    "before_fwd": timed(jax.jit(fwd), q0, k0, v0, decay, taps, bias, rate),
                    "before_bwd": timed(jax.jit(bwd), q0, k0, v0, decay, taps, bias, rate, dq, dk, dv, dg),
                    "after_fwd": timed(jax.jit(lambda *x: kda_mix._after_fwd_pallas(*x, 1e-5, tile)), o, gate, norm, g_bias),
                    "after_bwd": timed(jax.jit(lambda *x: kda_mix._after_bwd_pallas(*x, 1e-5, tile)), o, gate, norm, g_bias, dout),
                }
                line = {"form": form, "tile": tile, "rows": rows, "ms": ms, "gb_per_s": {k: need[k] / v / 1e6 for k, v in ms.items()},
                        "layer_ms_two_forwards_one_backward": 2 * (ms["before_fwd"] + ms["after_fwd"]) + ms["before_bwd"] + ms["after_bwd"]}
                if form == "gdn":   # the other choice: q, k in one call at the key heads, v in a call of its own at the value heads
                    qk, v_taps = taps[:2], conv_v.astype(f32)[None]
                    apart = {
                        "before_fwd_qk": timed(jax.jit(fwd), q0, k0, None, None, qk, None, None),
                        "before_fwd_v": timed(jax.jit(fwd), None, None, v0, None, v_taps, None, None),
                        "before_bwd_qk": timed(jax.jit(bwd), q0, k0, None, None, qk, None, None, dq, dk, None, None),
                        "before_bwd_v": timed(jax.jit(bwd), None, None, v0, None, v_taps, None, None, None, None, dv, None),
                    }
                    line["v_in_a_call_of_its_own_ms"] = dict(apart, before_fwd=apart["before_fwd_qk"] + apart["before_fwd_v"],
                                                             before_bwd=apart["before_bwd_qk"] + apart["before_bwd_v"])
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
