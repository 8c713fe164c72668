#!/usr/bin/env python3
"""Standalone: the device allocator's counters after each call of a cell's two programs
(the gradient program, the update), to see which call sets `alloc_peak_bytes`.

    chiprun -- python3 tools/alloc_by_call.py --workload laguna-xs.2.steady-1g-16k [--tree parent_tree]

Builds the cell's train step from `--tree`'s benchmark files as
`benchmark/jobs/steady.py` does (seeded weights, AdamW's state, no Manager),
then: the gradient program once, the optimizer's state, and three steps of
gradient program then update, each waited for; then three steps dispatched
back to back as `ft_step` dispatches them (only the loss waited for, so a
step's gradient program is enqueued while the update before it may still hold
that step's gradients).  One JSON line after each call with `bytes_in_use`,
`peak_bytes_in_use`, `largest_alloc_size` and `num_allocs`.
No cell runs this; PERF.md section 6 (PR 39) cites its reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--tree", default=".")
    parser.add_argument("--seed", type=int, default=7)
    args = parser.parse_args()
    tree = os.path.join(ROOT, args.tree)
    os.chdir(tree)
    sys.path.insert(0, tree)
    import jax

    from benchmark.jobs.steady import make_batch
    from benchmark.spec import Benchmark

    device = jax.devices()[0]
    bench = Benchmark(tree)
    cell = bench.cell(args.workload)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])

    def say(after: str, **more) -> None:
        stats = device.memory_stats() or {}
        print(json.dumps({"tree": args.tree, "after": after, **{k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "largest_alloc_size", "num_allocs")},
                          **more}), flush=True)

    def batch_of(index: int):
        return {k: jax.numpy.asarray(v) for k, v in make_batch(args.seed, 0, index, traffic, config["vocab_size"]).items()}

    weights = bench.reference(config["architecture"]).make_weights(args.seed, config)
    _, step = bench.program(config["architecture"]).train_step(config, device)
    jax.block_until_ready(weights)
    say("weights")
    loss, grads = step.grads(weights, batch_of(0))
    jax.block_until_ready(grads)
    say("gradient program, first call", loss=float(loss))
    opt = step.init_opt_state(weights)
    jax.block_until_ready(opt)
    del grads
    say("optimizer state, first gradients deleted")
    for i in range(1, 4):
        loss, grads = step.grads(weights, batch_of(i))
        jax.block_until_ready(grads)
        say(f"gradient program, step {i}", loss=float(loss))
        weights, opt = step.apply(weights, opt, grads)
        jax.block_until_ready(weights)
        del grads
        say(f"update, step {i}")
    for i in range(4, 7):
        loss, grads = step.grads(weights, batch_of(i))
        weights, opt = step.apply(weights, opt, grads)
        del grads
        say(f"step {i} dispatched, nothing waited for", loss=float(loss))
    jax.block_until_ready(weights)
    say("back-to-back steps done")


if __name__ == "__main__":
    main()
