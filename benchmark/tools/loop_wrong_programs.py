#!/usr/bin/env python3
"""Runs three wrong programs of a looped model once and reads what the comparison makes of each.

    python3 benchmark/tools/loop_wrong_programs.py --workload <cell> --seed <n> [--platform cpu]

The program's own model (`torchft_tpu`, kernels, the configuration's compute
type) with one piece of the looped mathematics wrong, in the program's place in
the comparison that decides `correct` (`compare.against_reference`, the float32
reference as published on the seed's weights and first batch):

- `one_pass`: the layers run once and the one state's mean loss is the loss;
- `no_entropy_term`: the exit-weighted loss with beta = 0;
- `last_pass_loss_alone`: every pass runs, and the last state's mean loss is the loss.

The first and the last have no gate: its two leaves get a zero gradient, as such
a program would give them, and `grad_rel_without_the_gate` is the same number over
the other leaves alone.  One JSON line a program, the sound program's first; each
wrong one has to fail the cell's limit.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

WRONG = {
    "as_published": {},
    "one_pass": dict(loop_steps=1, exit_beta=None),
    "no_entropy_term": dict(exit_beta=0.0),
    "last_pass_loss_alone": dict(exit_beta=None),
}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--platform", default="tpu")
    args = parser.parse_args()

    from benchmark.spec import Benchmark
    from torchft_tpu.launch import export_compile_cache

    export_compile_cache()  # before JAX is imported: the place the benchmark's runs use
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import compare
    from torchft_tpu.models.transformer import loss_and_counters

    device = jax.devices()[0]
    if device.platform != args.platform:
        raise RuntimeError(f"JAX found {device.platform!r}, not {args.platform!r} — no reading")
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    reference, program = bench.reference(config["architecture"]), bench.program(config["architecture"])
    job = bench.job(traffic["job"])
    cfg = program.transformer_config(config)
    weights = reference.make_weights(args.seed, config)
    batch = {k: jnp.asarray(v) for k, v in job.make_batch(args.seed, 0, 0, traffic, config["vocab_size"]).items()}
    indices = compare.sample_indices(args.seed, weights)
    ref_loss, ref_sample = compare.sequence_by_sequence(reference, config, weights, batch, indices)
    gate_leaves = [key for key in ref_sample if "exit_gate" in key]
    limit, all_fail = config["correct"]["grad_rel_limit"], True
    for name, change in WRONG.items():
        wrong = dataclasses.replace(cfg, **change)
        gated = wrong.exit_beta is not None
        tree = weights if gated else {k: v for k, v in weights.items() if k != "exit_gate"}
        (loss, _), grads = jax.jit(jax.value_and_grad(lambda p, w=wrong: loss_and_counters(p, batch, w), has_aux=True))(tree)
        if not gated:
            grads = dict(grads, exit_gate=jax.tree.map(jnp.zeros_like, weights["exit_gate"]))
        got = compare.sample(grads, indices)
        del grads
        rel, per_leaf = compare.grad_rel(got, ref_sample)
        rest = [v for k, v in per_leaf.items() if k not in gate_leaves]
        worst = max(per_leaf, key=per_leaf.get)
        fails = rel > limit
        all_fail = all_fail and (fails or name == "as_published")
        print(json.dumps({
            "program": name, "seed": args.seed, "loss": float(loss), "loss_reference": ref_loss, "grad_rel": rel,
            "grad_rel_limit": limit, "fails": fails, "grad_rel_without_the_gate": float(np.sqrt(np.mean(np.square(rest)))),
            "grad_rel_worst_leaf": worst, "grad_rel_worst": per_leaf[worst],
            "gate": {k: per_leaf[k] for k in gate_leaves}}), flush=True)
    print(json.dumps({"workload": args.workload, "device": device.device_kind, "every_wrong_program_fails": all_fail}), flush=True)
    return 0 if all_fail else 1


if __name__ == "__main__":
    sys.exit(main())
