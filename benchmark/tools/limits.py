#!/usr/bin/env python3
"""Reads the two numbers every limit of `correct` is set from, in one process on the chip.

    python3 benchmark/tools/limits.py --workload <cell> --seeds 1,2,...  [--control-seeds 1,2,3]

For each seed: the seed's weights and first batch, the program's gradient
program (kernels, bf16) against the plain float32 reference — a sound run's
`loss_rel` and `grad_rel`; and for the control seeds the reference itself
computed in the next precision down (fp8 operands) put in the program's place.
Prints one JSON line a seed and, last, the largest sound and the smallest
control reading of each number.  A limit belongs above the first and below
the second (contract: "How `correct` is decided", steps 4 and 5).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    args = parser.parse_args()

    from benchmark.spec import Benchmark
    from torchft_tpu.launch import export_compile_cache

    export_compile_cache()  # before JAX is imported: the place the benchmark's runs use
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax

    from benchmark import compare

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(f"JAX found {device.platform!r}, not a TPU — no reading")
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    reference, program = bench.reference(config["architecture"]), bench.program(config["architecture"])
    job = bench.job(traffic["job"])
    _, step = program.train_step(config, device)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    sound, control = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = reference.make_weights(seed, config)
        batch = {k: jax.numpy.asarray(v) for k, v in job.make_batch(seed, 0, 0, traffic, config["vocab_size"]).items()}
        indices = compare.sample_indices(seed, weights)
        loss, grads = step.grads(weights, batch)
        got = compare.sample(grads, indices)
        del grads
        s = compare.against_reference(reference, config, weights, batch, loss, got, indices)
        sound.append(s)
        print(json.dumps({"seed": seed, "kind": "sound", **s}), flush=True)
        if seed in controls:
            closs, csample = compare.sequence_by_sequence(reference, config, weights, batch, indices, "float8")
            c = compare.against_reference(reference, config, weights, batch, closs, csample, indices)
            control.append(c)
            print(json.dumps({"seed": seed, "kind": "control_float8", **c}), flush=True)
        del weights
    out = {"workload": args.workload, "device": device.device_kind, "sound_seeds": len(sound),
           "control_seeds": len(control)}
    for key in ("loss_rel", "grad_rel"):
        out[key] = {"sound_max": max(s[key] for s in sound), "sound_min": min(s[key] for s in sound),
                    "control_min": min((c[key] for c in control), default=None)}
    out["grad_rel"]["limit"] = sound[0]["grad_rel_limit"]
    out["control_all_failed"] = all(not c["ok"] for c in control)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
