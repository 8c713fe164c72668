#!/usr/bin/env python3
"""What the selection-side kernels of learned sparse attention cost a loop, read from the compiler's schedule.

    python tools/dsa_probe.py [--shape 32x32768x128 --kv-heads 4 --index 16x64 --topk 2048] [--heads-a-body 1,2,4]

No chip and no timing.  `tpuft_dsa_select` and `tpuft_dsa_index_loss`
(`ops/sparse_attention.py`) are each compiled at `--shape` (query heads x
positions x head width), `--kv-heads` and `--index` (index heads x their
width) for a described v5e, in a child process with
`LIBTPU_INIT_ARGS=--xla_jf_dump_to` as `tools/fa_bwd_probe.py --bundles` does
it (the child ends in the compiler's abort over a missing report template
AFTER the files are written — expected), and the final schedule is counted
loop by loop: the dump marks a bundle inside loops with a `>` a level and a
loop's first bundle `LB`.  One JSON line a kernel; `loops` holds, in program
order, each loop's depth, its first bundle, its own bundles (`bundles`: the
loops inside it not counted; `with_inner` counts them once each), the spill
stores and fills among them and each unit's slots taken (the units' slots a
bundle under `slots_a_bundle`).  Depth 1 is the grid's loop — the
straight-line work of a grid step; in `tpuft_dsa_select` the depth-2 loops
are, in order, `fill`, the sign's count, `bit_step`'s 31 passes (its count
at depth 3), the counts above and at the threshold, `cut_step`'s passes (its
count at depth 3), the `top` fold and the log-sum-exp fold, each a visible
key tile an iteration; in `tpuft_dsa_index_loss` the one depth-2 loop is the
query heads' (`--heads-a-body` heads an iteration, through the kernel's
private argument: without the flag what the kernel reads from the shapes;
`bundles_a_head` is that loop's bundles over the heads of one iteration).

A schedule is static: bundles at the clock (1.5 GHz) are a floor for the
measured kernel, not its time; a loop's bundles times its trips (a visible
tile, a pass, a head) sum to the kernel's.

    chiprun -- python tools/dsa_probe.py --against parent_tree [--heads-a-body 1,2,4,8]

On the chip: both kernels at the same shapes on operands from `--seed`
(the mask from the selection, the row statistics from `tpuft_dsa_attn_fwd`
under it), from this tree and from the tree given (`git archive <commit> |
tar -x -C parent_tree`: its `torchft_tpu/ops/sparse_attention.py` loaded
beside this one), each timed (median of `--reps` after a warm-up, host clock
around `block_until_ready`) and the results compared bit for bit — `tau`,
`cut`, `z`; `kl`, `da`, `dbt`, `dw` — a JSON line a reading, all of them in
`chiprun_out/dsa_probe.json` (~2 min).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

from fa_bwd_probe import described_v5e, schedule_bundles, slots_taken  # noqa: E402

KERNELS = ("tpuft_dsa_select", "tpuft_dsa_index_loss")
UNITS = ("MXU", "VALU", "XLU", "EUP", "VLOAD", "VSTORE")


def read_loops(dump: str, kernel: str) -> dict:
    """``kernel``'s final schedule in ``dump`` by loop: {"bundles": all of
    them, "slots_a_bundle": {unit: slots}, "loops": [a record a loop, in
    program order]}.  A loop opens at an `LB` bundle and holds what follows at
    its depth or deeper, up to the next `LB` at its depth or the first
    shallower bundle."""
    bundles = schedule_bundles(dump, kernel)
    slots, taken = slots_taken(dump, kernel)
    loops, open_loops = [], []
    for number, label, depth, _ in bundles:
        while open_loops and (open_loops[-1]["depth"] > depth or (label == "LB" and open_loops[-1]["depth"] == depth)):
            open_loops.pop()
        if label == "LB":
            loop = {"depth": depth, "first_bundle": number, "bundles": 0, "with_inner": 0, "spill_stores": 0,
                    "spill_fills": 0, "slots_taken": dict.fromkeys(UNITS, 0)}
            loops.append(loop)
            open_loops.append(loop)
        for loop in open_loops:
            loop["with_inner"] += 1
        if open_loops and open_loops[-1]["depth"] == depth:
            own, used = open_loops[-1], taken[number]
            own["bundles"] += 1
            own["spill_stores"] += used["VSTORE:SPILL"]
            own["spill_fills"] += used["VLOAD:FILL"]
            for unit in UNITS:
                own["slots_taken"][unit] += used[unit]
    return {"bundles": len(bundles), "slots_a_bundle": {unit: slots[unit] for unit in UNITS}, "loops": loops}


def child(args) -> int:
    """Compile ``args.child`` for a described v5e with the compiler dumping
    its final schedule into ``args.dump`` (a process a kernel: see the
    module's note on the abort)."""
    one_chip = described_v5e(args.dump)
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import attention as fa
    from torchft_tpu.ops import sparse_attention as sa

    heads, seq, d = (int(x) for x in args.shape.split("x"))
    j, di = (int(x) for x in args.index.split("x"))
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    a, bt, w = shaped((1, j, seq, di)), shaped((1, di, seq)), shaped((1, seq, j), jnp.float32)
    if args.child == "tpuft_dsa_select":
        jax.jit(lambda a_, bt_, w_: sa._select_pallas(a_, bt_, w_, args.topk)).lower(a, bt, w).compile()
        return 0
    tile = fa._block_sizes(seq, seq)[0]
    n = seq // tile
    operands = (shaped((1, seq, heads, d)), shaped((1, seq, args.kv_heads, d)), shaped((1, heads, seq), jnp.float32),
                a, bt, w, shaped((1, seq, 1), jnp.float32), shaped((1, n * (n + 1) // 2, tile, sa.BLOCK_K), jnp.int8))
    more = {"heads_a_body": args.heads_a_body[0]} if args.heads_a_body else {}
    jax.jit(lambda *x: sa._index_loss_pallas(*x, d ** -0.5, **more)).lower(*operands).compile()
    return 0


def on_chip(args) -> int:
    """`--against`: the two kernels of this tree and of the tree given, timed and compared bit for bit."""
    import importlib.util
    import statistics
    import time

    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import sparse_attention as sa

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"the probe measures a TPU and JAX found {device.platform!r}", file=sys.stderr)
        return 1
    spec = importlib.util.spec_from_file_location(
        "sparse_attention_of_the_other_tree", os.path.join(args.against, "torchft_tpu", "ops", "sparse_attention.py"))
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)
    heads, seq, d = (int(x) for x in args.shape.split("x"))
    j, di = (int(x) for x in args.index.split("x"))
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    bf = jnp.bfloat16
    q, k, v = (jax.random.normal(key, (1, seq, n, d), bf) for key, n in zip(ks, (heads, args.kv_heads, args.kv_heads)))  # position-major
    a, bt = jax.random.normal(ks[3], (1, j, seq, di), bf), jax.random.normal(ks[4], (1, di, seq), bf)
    w = jax.random.normal(ks[5], (1, seq, j), jnp.float32) * (j * di) ** -0.5
    scale = d ** -0.5
    readings = []

    def timed(fn, *operands):
        out = jax.block_until_ready(fn(*operands))
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(time.perf_counter() - t)
        return statistics.median(times) * 1e3, out

    def line(kernel, tree, ms, out, first, **more):
        rec = {"kernel": kernel, "tree": tree, "shape": args.shape, "ms": round(ms, 4), **more,
               "bitwise_the_first_lines": all(bool(jnp.array_equal(x, y)) for x, y in zip(out, first))}
        readings.append(rec)
        print(json.dumps(rec), flush=True)

    first = None
    for tree, module in ((args.against, other), (".", sa)):
        ms, out = timed(jax.jit(lambda a_, bt_, w_, m=module: m._select_pallas(a_, bt_, w_, args.topk)), a, bt, w)
        first = first or out
        line("tpuft_dsa_select", tree, ms, out, first)
    tau, cut, z = first
    mask = jax.jit(sa._mask_pallas)(a, bt, w, tau, cut)
    _, lse = jax.jit(lambda *x: sa._masked_flash_fwd(*x, scale))(q, k, v, mask)
    operands = (q, k, lse, a, bt, w, z, mask)
    # a tree from before PR 65 takes q and k head-major
    theirs = operands if hasattr(other._fa, "_entry_and_block") else tuple(x.transpose(0, 2, 1, 3) for x in (q, k)) + operands[2:]
    ms, first = timed(jax.jit(lambda *x: other._index_loss_pallas(*x, scale)), *theirs)
    line("tpuft_dsa_index_loss", args.against, ms, first, first)
    for u in args.heads_a_body or [None]:
        ms, out = timed(jax.jit(lambda *x, u=u: sa._index_loss_pallas(*x, scale, heads_a_body=u)), *operands)
        line("tpuft_dsa_index_loss", ".", ms, out, first, heads_a_body=u or sa._heads_a_body(heads))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "dsa_probe.json"), "w", encoding="utf-8") as f:
        json.dump({"device": device.device_kind, "readings": readings}, f, indent=1)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shape", default="32x32768x128", help="query heads x positions x head width (the Keye cell's)")
    parser.add_argument("--kv-heads", type=int, default=4)
    parser.add_argument("--index", default="16x64", help="index heads x their width")
    parser.add_argument("--topk", type=int, default=2048)
    parser.add_argument("--kernels", default=",".join(KERNELS))
    parser.add_argument("--heads-a-body", type=lambda text: [int(x) for x in text.split(",")], default=None,
                        help="query heads an iteration of the index loss's loop (default: what the kernel reads from the shape)")
    parser.add_argument("--against", default="", help="on the chip: another tree of this repo whose two kernels are timed "
                        "beside this tree's and compared with them bit for bit")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--child", default="", help=argparse.SUPPRESS)
    parser.add_argument("--dump", default="", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        return child(args)
    if args.against:
        return on_chip(args)
    from torchft_tpu.ops import sparse_attention as sa

    read_from_the_shape = sa._heads_a_body(int(args.shape.split("x")[0]))
    for kernel in filter(None, args.kernels.split(",")):
        for heads in (args.heads_a_body or [read_from_the_shape]) if kernel == "tpuft_dsa_index_loss" else [None]:
            rec = {"kernel": kernel, "shape": args.shape, "kv_heads": args.kv_heads, "index": args.index}
            command = [sys.executable, os.path.abspath(__file__), "--child", kernel, "--shape", args.shape, "--kv-heads",
                       str(args.kv_heads), "--index", args.index, "--topk", str(args.topk)]
            if heads:
                rec["heads_a_body"] = heads
                command += ["--heads-a-body", str(heads)]
            with tempfile.TemporaryDirectory() as dump:
                done = subprocess.run(command + ["--dump", dump], capture_output=True, text=True, check=False)
                try:
                    rec.update(read_loops(dump, kernel))
                except (ValueError, IndexError, OSError) as e:  # no such file: the compile failed before the kernel
                    rec["error"] = f"{type(e).__name__}: {e}; the child said: {done.stderr[-600:]}"
            if heads and "loops" in rec:
                rec["bundles_a_head"] = [round(loop["bundles"] / heads, 1) for loop in rec["loops"] if loop["depth"] == 2]
            print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
