#!/usr/bin/env python3
"""The `tpuft_ssmmix_*` kernels (ops/ssm_mix.py: what Mamba-2 puts around its
scan) at the Nemotron cell's shape — one sequence of 16,384 positions, 64 heads
of 64 in 8 groups over a state of 128: u [1, 16,384, 6,144], x, y, z
[1, 16,384, 4,096].

    python tools/ssmmix_probe.py --schedule [--tile 1024 --after-tile 1024 --block 16384]

No chip and no timing: each of the four kernels compiled for a described v5e
in a child process with `LIBTPU_INIT_ARGS=--xla_jf_dump_to` (as
`tools/dsa_probe.py` does it; the child ends in the compiler's abort over a
missing report template AFTER the files are written — expected) and the final
schedule counted loop by loop (`dsa_probe.read_loops`, over
`fa_bwd_probe.schedule_bundles` / `slots_taken`): each loop's bundles, its
spill stores and fills, the units' slots taken.  Depth 1 is the grid's loop,
the depth-2 loops are the blocks of rows (`bundles_a_block` is each one's own
bundles: times the blocks a tile and the grid's steps they are a floor for the
kernel at the clock).

    chiprun -- python tools/ssmmix_probe.py [--tiles 1024,512 --after-tiles 1024,512 --blocks 16384,8192]

On the chip: the kernels against the XLA halves (`models/mamba.py::_before`,
`_after`) at 2 x 2,048 positions x 8 heads of 64 in 2 groups (outputs and every
gradient, the largest difference over the reference's largest value), the XLA
halves timed at the cell's shape under their checkpoints, and each kernel timed
alone, tile height by tile height, with the bytes it must move
(`benchmark/flops/tpuft_ssmmix.py`'s units) over the time as GB/s.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

KERNELS = ("tpuft_ssmmix_fwd", "tpuft_ssmmix_bwd", "tpuft_ssmmix_out_fwd", "tpuft_ssmmix_out_bwd")
SEQ, HEADS, P, GROUPS, STATE = 16_384, 64, 64, 8, 128
EPS = 1e-5


def calls(tile, after_tile, batch=1, seq=SEQ, heads=HEADS, p=P, groups=GROUPS, state=STATE):
    """{kernel: (the function of its operands, their shapes and types)} at a shape."""
    import jax.numpy as jnp

    from torchft_tpu.ops import ssm_mix

    bf16, f32 = jnp.bfloat16, jnp.float32
    inner, bc = heads * p, groups * state
    channels, hp = inner + 2 * bc, -(-heads // 128) * 128
    u, wide, narrow, dt = ((batch, seq, channels), bf16), ((batch, seq, inner), bf16), ((batch, seq, bc), bf16), ((batch, seq, hp), f32)
    taps, bias, column = ((4, channels), f32), ((1, channels), f32), ((1, inner), f32)
    tile, after_tile = ssm_mix.tile_of(seq, tile), ssm_mix._after_tile(seq, inner // groups, after_tile)
    return {
        "tpuft_ssmmix_fwd": (lambda *a: ssm_mix._before_fwd_pallas(*a, p, inner, tile), [u, dt, taps, bias]),
        "tpuft_ssmmix_bwd": (lambda *a: ssm_mix._before_bwd_pallas(*a, p, inner, tile),
                             [u, dt, taps, bias, wide, wide, narrow, narrow]),
        "tpuft_ssmmix_out_fwd": (lambda *a: ssm_mix._after_fwd_pallas(*a, groups, EPS, after_tile), [wide] * 3 + [column] * 2),
        "tpuft_ssmmix_out_bwd": (lambda *a: ssm_mix._after_bwd_pallas(*a, groups, EPS, after_tile),
                                 [wide] * 3 + [column] * 2 + [wide]),
    }


def child(args) -> int:
    from fa_bwd_probe import described_v5e

    one_chip = described_v5e(args.dump)
    import jax

    from torchft_tpu.ops import ssm_mix

    if args.block:
        ssm_mix._BLOCK = args.block
    fn, shapes = calls(args.tile, args.after_tile)[args.child]
    jax.jit(fn).lower(*(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes)).compile()
    return 0


def schedule(args) -> int:
    from dsa_probe import read_loops

    for kernel in filter(None, args.kernels.split(",")):
        rec = {"kernel": kernel, "tile": args.tile, "after_tile": args.after_tile, "block": args.block}
        command = [sys.executable, os.path.abspath(__file__), "--child", kernel, "--tile", str(args.tile), "--after-tile",
                   str(args.after_tile), "--block", str(args.block)]
        with tempfile.TemporaryDirectory() as dump:
            done = subprocess.run(command + ["--dump", dump], capture_output=True, text=True, check=False)
            try:
                rec.update(read_loops(dump, kernel))
            except (ValueError, IndexError, OSError) as e:  # no such file: the compile failed before the kernel
                rec["error"] = f"{type(e).__name__}: {e}; the child said: {done.stderr[-1500:]}"
        if "loops" in rec:
            rec["bundles_a_block"] = [loop["bundles"] for loop in rec["loops"] if loop["depth"] == 2]
            rec["spills_a_block"] = [loop["spill_stores"] for loop in rec["loops"] if loop["depth"] == 2]
        print(json.dumps(rec), flush=True)
    return 0


def timed(fn, *args, repeats=10):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def weights(key, heads, p, channels):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    return {"ssm_conv": 0.5 * jax.random.normal(ks[0], (channels, 4)), "ssm_conv_bias": 0.3 * jax.random.normal(ks[1], (channels,)),
            "dt_bias": jax.random.normal(ks[2], (heads,)), "A_log": jnp.log(jax.random.uniform(ks[3], (heads,), minval=1.0, maxval=16.0)),
            "ssm_D": 1 + 0.3 * jax.random.normal(ks[4], (heads,)), "ssm_norm": 1 + 0.3 * jax.random.normal(ks[5], (heads * p,))}


def halves(heads, p, groups, state):
    """(before, after) as functions of arrays, kernels and XLA: {"kernels": ..., "xla": ...} each."""
    from torchft_tpu.models import mamba
    from torchft_tpu.ops import ssm_mix

    cfg = types.SimpleNamespace(ssm_head_dim=p, ssm_groups=groups, ssm_state=state, rms_eps=EPS)
    before = {"xla": lambda u, dt_raw, w: mamba._before(u, dt_raw, w, cfg, heads)[:5],
              "kernels": lambda u, dt_raw, w: ssm_mix.before(u, dt_raw, w["ssm_conv"].T, w["ssm_conv_bias"], w["dt_bias"],
                                                             w["A_log"], head_dim=p)}
    after = {"xla": lambda y, x, z, w: mamba._after(y, x, z, w, cfg, heads),
             "kernels": lambda y, x, z, w: ssm_mix.after(y, x, z, w["ssm_D"], w["ssm_norm"], groups=groups, eps=EPS)}
    return before, after


def arrays(seed, batch, seq, heads, p, groups, state):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 16)
    inner, bc = heads * p, groups * state
    bf16 = jnp.bfloat16
    normal = lambda i, width, dtype=bf16: jax.random.normal(ks[i], (batch, seq, width)).astype(dtype)   # noqa: E731
    u, dt_raw = normal(0, inner + 2 * bc), normal(1, heads)
    cots = [normal(2, inner), normal(3, inner), normal(4, bc), normal(5, bc), normal(6, heads, jnp.float32)]
    y, x, z, dout = (normal(7 + i, inner) for i in range(4))
    return u, dt_raw, cots, (y, x, z), dout, weights(ks[11], heads, p, inner + 2 * bc)


def compare(seed=1, batch=2, seq=2048, heads=8, p=64, groups=2, state=128):
    """Kernels against the XLA halves on the chip: the largest difference over
    the reference's largest value, outputs and every gradient."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    u, dt_raw, cots, yxz, dout, w = arrays(seed, batch, seq, heads, p, groups, state)
    before, after = halves(heads, p, groups, state)
    rel = lambda x, y: float(jnp.max(jnp.abs(x.astype(f32) - y.astype(f32))) / jnp.maximum(jnp.max(jnp.abs(y.astype(f32))), 1e-30))  # noqa: E731
    found = {}
    for name, fns, operands, cot in (("before", before, (u, dt_raw, w), cots), ("after", after, (*yxz, w), [dout])):
        def loss(fn):
            def inner(*a):
                outs = fn(*a)
                outs = outs if isinstance(outs, tuple) else (outs,)
                return sum(jnp.sum(o.astype(f32) * c.astype(f32)) for o, c in zip(outs, cot)), outs
            return jax.jit(jax.value_and_grad(inner, argnums=range(len(operands)), has_aux=True))
        (_, got), got_grads = loss(fns["kernels"])(*operands)
        (_, want), want_grads = loss(fns["xla"])(*operands)
        found[name] = {"out": [rel(a, b) for a, b in zip(got, want)],
                       "grads": [rel(a, b) for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads))]}
    return found


def on_chip(args) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark.flops import tpuft_ssmmix as count
    from torchft_tpu.ops import ssm_mix

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"the probe measures a TPU and JAX found {device.platform!r}", file=sys.stderr)
        return 1
    print(json.dumps({"device": device.device_kind}), flush=True)
    print(json.dumps({"kernels_against_xla_halves_2x2048x8": compare()}), flush=True)
    f32 = jnp.float32
    u, dt_raw, cots, yxz, dout, w = arrays(2, 1, SEQ, HEADS, P, GROUPS, STATE)
    if not args.skip_xla:
        # each half under a checkpoint, as `mamba2_mixer` runs it; the cotangents are arguments, not constants of the program
        before, after = (jax.checkpoint(fns["xla"]) for fns in halves(HEADS, P, GROUPS, STATE))
        before_loss = lambda u, dt_raw, w, *c: sum(jnp.sum(o.astype(f32) * ci.astype(f32)) for o, ci in zip(before(u, dt_raw, w), c))  # noqa: E731
        after_loss = lambda y, x, z, w, dout: jnp.sum(after(y, x, z, w).astype(f32) * dout.astype(f32))  # noqa: E731
        print(json.dumps({"xla_halves_ms": {
            "before_fwd": timed(jax.jit(before), u, dt_raw, w),
            "before_grad": timed(jax.jit(jax.grad(before_loss, argnums=(0, 1, 2))), u, dt_raw, w, *cots),
            "after_fwd": timed(jax.jit(after), *yxz, w),
            "after_grad": timed(jax.jit(jax.grad(after_loss, argnums=(0, 1, 2, 3))), *yxz, w, dout)}}), flush=True)
    array = SEQ * HEADS * P * 2
    need = {"tpuft_ssmmix_fwd": count.UNITS["before_forward"] * array, "tpuft_ssmmix_bwd": count.UNITS["before_backward"] * array,
            "tpuft_ssmmix_out_fwd": count.UNITS["after_forward"] * array, "tpuft_ssmmix_out_bwd": count.UNITS["after_backward"] * array}
    dt = jnp.pad(jax.nn.softplus(dt_raw.astype(f32) + w["dt_bias"]), [(0, 0), (0, 0), (0, -HEADS % 128)])
    taps, bias = w["ssm_conv"].T, w["ssm_conv_bias"][None]
    d, norm = jnp.repeat(w["ssm_D"], P)[None], w["ssm_norm"][None]
    operands = {"tpuft_ssmmix_fwd": (u, dt, taps, bias), "tpuft_ssmmix_bwd": (u, dt, taps, bias, *cots[:4]),
                "tpuft_ssmmix_out_fwd": (*yxz, d, norm), "tpuft_ssmmix_out_bwd": (*yxz, d, norm, dout)}
    for block in (int(x) for x in args.blocks.split(",")):
        ssm_mix._BLOCK = block
        jax.clear_caches()                     # the kernels' traces are kept a process: another block is another trace
        for tile, after_tile in zip((int(t) for t in args.tiles.split(",")), (int(t) for t in args.after_tiles.split(","))):
            fns = calls(tile, after_tile)
            ms = {name: timed(jax.jit(fns[name][0]), *operands[name]) for name in KERNELS}
            print(json.dumps({"tile": tile, "after_tile": after_tile, "block": block, "ms": ms,
                              "gb_per_s": {k: need[k] / v / 1e6 for k, v in ms.items()},
                              "block_ms_two_forwards_one_backward": 2 * (ms[KERNELS[0]] + ms[KERNELS[2]]) + ms[KERNELS[1]] + ms[KERNELS[3]]}),
                  flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--schedule", action="store_true", help="no chip: the compiler's final schedule of each kernel, loop by loop")
    parser.add_argument("--kernels", default=",".join(KERNELS))
    parser.add_argument("--tile", type=int, default=0, help="rows a grid step of the before kernels (0: what the program reads from the shape)")
    parser.add_argument("--after-tile", type=int, default=0)
    parser.add_argument("--block", type=int, default=0, help="elements of a block of rows in the after kernels (0: the module's)")
    parser.add_argument("--tiles", default="0")
    parser.add_argument("--after-tiles", default="0")
    parser.add_argument("--blocks", default="16384")
    parser.add_argument("--skip-xla", action="store_true")
    parser.add_argument("--child", default="", help=argparse.SUPPRESS)
    parser.add_argument("--dump", default="", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        return child(args)
    return schedule(args) if args.schedule else on_chip(args)


if __name__ == "__main__":
    sys.exit(main())
