#!/usr/bin/env python3
"""What a token's k gathered rows cost on this chip, three ways.

    chiprun -- python tools/moe_rows_probe.py                       # the cells' shapes
    python tools/moe_rows_probe.py --bundles [--shapes keye --tokens-a-step 64]   # no chip: the kernel's schedule

A standalone probe: no cell runs it.  A shape is an expert layer's combine as
a cell has it — ``T x k`` assignments out of a source of ``R`` rows of ``E``
bfloat16 columns, a share of them with a row on this chip (the others past the
end, each its own index, as `models/moe.py::_dropless_ffn` gives them), the
rows they have distinct — and for each the time of:

- `xla`: today's ``jnp.take`` out of ``[R, E]`` (`models/moe.py::_take_rows`:
  k-major where k is no multiple of 8) and the float32 weighting pass
  (``kte,tk->te``), and the gather alone (`xla_gather`: the ``[T, k, E]``
  array written and nothing more);
- `xla_words`: the same ``jnp.take`` out of the row-contiguous
  ``[R, 1, E / 2]`` uint32 source (two adjacent columns a word), with the turn
  into that form and the same weighting pass on the unpacked halves, and that
  gather alone (`xla_words_gather`, the turn outside what is timed);
- `kernel_<tb>x<buffers>`: ``ops/moe_rows.moe_rows`` — the turn of the source,
  `tpuft_moe_rows` and the turn of the result — at each ``--tokens-a-step`` and
  count of buffers, and the kernel alone on operands already turned
  (`kernel_alone_<tb>x<buffers>`; `..._one_row`: the assignments without a row
  all read row R - 1, where the program's read ``dest % R``, each its own).

Each line gives the device's ms a call (`device_ms`: the `XLA Ops` of a traced
run of ``--reps`` calls, summed, and `device_ops` the largest of them by
name — the kernel apart from XLA's turns around it), ns a gathered row
(`device_ms` over T * k: every assignment's row is fetched, with a row or
without, and ``fetches`` says so), the host's clock around a call
(`host_ms`, median of ``--reps``: it holds the dispatch), and for the two
candidates the largest difference from `xla`'s result and whether they are
bit for bit the same.  The sum form
(``gates=None``, `_rows_bwd`'s) is read with ``--sum``.

``--bundles`` compiles the kernel for a described v5e in a child process a
reading (`tools/fa_bwd_probe.py`'s way) and counts the compiler's final
schedule loop by loop (`tools/dsa_probe.py::read_loops`): the two issue loops
(the first block's and the next block's), the waits and the weighting, as
bundles a turn — a turn is 8 rows issued or waited for, 8 tokens (8 k rows)
weighted.  A schedule is a floor: the DMAs' stalls are not in it.

One JSON line a reading on standard output, all of them in
`chiprun_out/moe_rows_probe.json`.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "tools")):
    if path not in sys.path:
        sys.path.insert(0, path)

# name: (tokens, k, columns, rows of the source, share of the assignments with a row here)
SHAPES = {
    "smallthinker": (16384, 6, 2560, 25600, 0.125),   # 8 of 64 experts held, k-major
    "keye": (32768, 8, 2048, 67584, 0.125),           # 16 of 128 held; SDAR's, to the row
    "laguna": (16384, 8, 2048, 36864, 0.125),
    "olmoe": (8192, 8, 2048, 73728, 1.0),             # every expert held: every row exists
    "moonlight": (16384, 6, 2048, 25600, 0.125),      # the control: XLA prefetches its 100 MiB source
    "zaya": (16384, 1, 2048, 17408, 0.5),             # k = 1, a 68 MiB source that XLA reads slowly all the same
}
KERNEL = "tpuft_moe_rows"


def bundles_child(args) -> int:
    """Compile the kernel at one shape for a described v5e, the compiler dumping its final schedule."""
    from fa_bwd_probe import described_v5e

    one_chip = described_v5e(args.dump)
    import jax
    import jax.numpy as jnp

    from torchft_tpu.ops import moe_rows as mr

    tokens, k, cols, n_rows, _ = SHAPES[args.bundles_child]
    tb = args.tokens_a_step[0]
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    scalars = (tokens // tb, 1, tb * k)
    jax.jit(lambda s, i, w: mr._rows_pallas(s, i, w, tb=tb, k=k, buffers=args.buffers[0])).lower(
        jax.eval_shape(mr._tiled, shaped((n_rows, cols), jnp.bfloat16)), shaped(scalars, jnp.int32), shaped(scalars, jnp.float32)).compile()
    return 0


def bundles(args) -> int:
    from dsa_probe import read_loops

    for name in args.shapes:
        for tb in args.tokens_a_step:
            for buffers in args.buffers:
                rec = {"shape": name, "tokens_a_step": tb, "buffers": buffers, "rows_a_step": tb * SHAPES[name][1]}
                with tempfile.TemporaryDirectory() as dump:
                    child = subprocess.run([sys.executable, os.path.abspath(__file__), "--bundles-child", name, "--dump", dump,
                                            "--tokens-a-step", str(tb), "--buffers", str(buffers)],
                                           capture_output=True, text=True, check=False)
                    try:
                        read = read_loops(dump, KERNEL)
                    except (ValueError, IndexError, OSError) as e:  # no such file: the compile failed before the kernel
                        rec["error"] = f"{type(e).__name__}: {e}; the child said: {child.stderr[-600:]}"
                    else:
                        rec["bundles"] = read["bundles"]
                        rec["loops"] = [{"depth": loop["depth"], "bundles_a_turn": loop["bundles"], "spill_stores": loop["spill_stores"],
                                         "slots_taken": {u: n for u, n in loop["slots_taken"].items() if n}} for loop in read["loops"]]
                print(json.dumps(rec), flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shapes", default=",".join(SHAPES), help=f"of {', '.join(SHAPES)}")
    parser.add_argument("--tokens-a-step", default="32,64,128")
    parser.add_argument("--buffers", default="1,2")
    parser.add_argument("--sum", action="store_true", help="gates=None: the plain sum over k")
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--bundles", action="store_true")
    parser.add_argument("--bundles-child", default="")
    parser.add_argument("--dump", default="")
    args = parser.parse_args(argv)
    args.shapes = [s for s in args.shapes.split(",") if s]
    args.tokens_a_step = [int(x) for x in args.tokens_a_step.split(",")]
    args.buffers = [int(x) for x in args.buffers.split(",")]
    if args.bundles_child:
        return bundles_child(args)
    if args.bundles:
        return bundles(args)

    import numpy as np

    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from torchft_tpu.models.moe import _k_leads, _rows_summed, _take_rows
    from torchft_tpu.ops import moe_rows as mr

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"the probe measures a TPU and JAX found {device.platform!r}", file=sys.stderr)
        return 1
    readings = []

    def timed(fn, *operands):
        """(host ms a call, device ms a call, {device operation: ms a call}, the result): the host's clock around a
        call and its wait (it holds a dispatch, ~1 ms here), and the device's own — the `XLA Ops` of a traced run."""
        out = jax.block_until_ready(fn(*operands))
        times = []
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(time.perf_counter() - t)
        with tempfile.TemporaryDirectory() as trace:
            with jax.profiler.trace(trace):
                for _ in range(args.reps):
                    jax.block_until_ready(fn(*operands))
            path, = glob.glob(os.path.join(trace, "plugins", "profile", "*", "*.xplane.pb"))
            (events,) = trace_reduce.load(path, "tpu")["devices"].values()
        ops = {}
        for op, _, dur_ns in events:
            ops[op] = ops.get(op, 0.0) + dur_ns / 1e6 / args.reps
        return statistics.median(times) * 1e3, sum(ops.values()), ops, out

    for name in args.shapes:
        tokens, k, cols, n_rows, share = SHAPES[name]
        n_assign, half = tokens * k, cols // 2
        rng = np.random.default_rng(args.seed)
        here = rng.random(n_assign) < share if share < 1.0 else np.ones(n_assign, bool)
        dest = n_rows + np.arange(n_assign)
        dest[here] = rng.permutation(n_rows)[:here.sum()]
        dest = jnp.asarray(dest.reshape(tokens, k), jnp.int32)
        rows = jnp.asarray(rng.standard_normal((n_rows, cols), np.float32), jnp.bfloat16)
        gates = None if args.sum else jnp.asarray(rng.random((tokens, k), np.float32))
        every = share == 1.0
        k_axis, product = (0, "kte,tk->te") if _k_leads(k) else (1, "tke,tk->te")

        def weighted(picked, gates, dtype=jnp.bfloat16):
            return (jnp.sum(picked, axis=k_axis) if gates is None else jnp.einsum(product, picked, gates)).astype(dtype)

        def words_of(rows):  # [R, 1, E / 2]: two adjacent columns a word, a row one piece
            return jax.lax.bitcast_convert_type(rows.reshape(n_rows, 1, half, 2), jnp.uint32)

        def take_words(words, dest):
            return _take_rows(words, dest, every)[..., 0, :]  # [T, k, E / 2] or [k, T, E / 2]

        def from_words(words, dest, gates):
            picked = take_words(words, dest)
            lo = jax.lax.bitcast_convert_type(picked << 16, jnp.float32)
            hi = jax.lax.bitcast_convert_type(picked & jnp.uint32(0xFFFF0000), jnp.float32)
            return jnp.stack([weighted(lo, gates), weighted(hi, gates)], axis=-1).reshape(tokens, cols)

        def attempt(form, fn, *operands, want=None, **more):
            """A line of ``form``: ``fn(*operands)`` timed and, with ``want``, compared with it; the result."""
            try:
                ms, device_ms, ops, out = timed(fn, *operands)
            except Exception as e:  # noqa: BLE001 — a form the compiler refuses is a reading too
                rec, out = {"shape": name, "form": form, "error": f"{type(e).__name__}: {str(e)[:400]}"}, None
            else:
                rec = {"shape": name, "form": form, "sum": args.sum, "tokens": tokens, "k": k, "columns": cols, "source_rows": n_rows,
                       "source_mib": round(n_rows * cols * 2 / 2**20, 1), "fetches": n_assign, "with_a_row": int(here.sum()),
                       "device_ms": round(device_ms, 4), "ns_a_row": round(device_ms * 1e6 / n_assign, 2), "host_ms": round(ms, 4),
                       "device_ops": {op: round(t, 4) for op, t in sorted(ops.items(), key=lambda kv: -kv[1])[:6]}, **more}
                if want is not None:
                    diff = np.abs(np.asarray(out, np.float32) - np.asarray(want, np.float32))
                    rec.update(max_diff=float(diff.max()), bitwise=bool((np.asarray(out) == np.asarray(want)).all()))
            readings.append(rec)
            print(json.dumps(rec), flush=True)
            return out

        want = attempt("xla", jax.jit(lambda r, d, g: _rows_summed(r, d, g, every, False)), rows, dest, gates)  # the program's own
        attempt("xla_gather", jax.jit(lambda r, d: _take_rows(r, d, every)), rows, dest)
        attempt("xla_words", jax.jit(lambda r, d, g: from_words(words_of(r), d, g)), rows, dest, gates, want=want)
        words = jax.block_until_ready(jax.jit(words_of)(rows))
        attempt("xla_words_gather", jax.jit(take_words), words, dest)
        # the kernel alone: its operands as `_moe_rows` makes them, made outside what is timed
        src, exists = mr._tiled(rows), dest < n_rows
        weights = exists.astype(jnp.float32) if gates is None else jnp.where(exists, gates, 0.0)
        for tb in args.tokens_a_step:
            for buffers in args.buffers:
                tag = f"{tb}x{buffers}"
                attempt(f"kernel_{tag}", lambda r, d, g: mr.moe_rows(r, d, g, tokens_a_step=tb, buffers=buffers),  # noqa: B023
                        rows, dest, gates, want=want, grid_steps=tokens // tb)
                alone = jax.jit(lambda s, i, w: mr._rows_pallas(s, i, w, tb=tb, k=k, buffers=buffers))  # noqa: B023
                w = mr._by_block(weights, tb)
                attempt(f"kernel_alone_{tag}", alone, src, mr._by_block(dest % n_rows, tb), w)
                if not every:
                    attempt(f"kernel_alone_{tag}_one_row", alone, src, mr._by_block(jnp.minimum(dest, n_rows - 1), tb), w)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_rows_probe.json"), "w") as f:
        json.dump({"device": {"platform": device.platform, "kind": device.device_kind}, "readings": readings}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
