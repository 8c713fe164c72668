#!/usr/bin/env python3
"""`routing_ties.py` for a configuration whose router is a function with a
carried state, takes a bias and chooses ONE expert or none (`architecture:
cca_moe_lm`), in one process on the chip.

    python3 benchmark/tools/routing_ties_cca.py --workload zaya1-8b.steady-1g-16k --seeds 1,2,3 [--left-out 1]

For each seed — the seed's weights and the cell's first batch — the share of
each layer's positions whose choice (an expert, or the one that takes none) in
the program differs from the float32 reference's, and the same share for the
reference's own bfloat16 and float8 (the control's) arithmetic; beside them how
many of the choices the router's bias decides, and the share of positions that
take no expert.  A top-1 choice that falls the other way swaps a position's
whole expert, so these shares are the floor under the sound `grad_rel`.

With `--left-out <seed>`: for that seed, `grad_rel` of the reference computed
WITHOUT one piece of the mathematics (the value shift, either convolution, the
q-k mean, the carried state) put in the program's place — each has to fail the
cell's limit.  One JSON line a seed, the ranges last.
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
    parser.add_argument("--left-out", type=int, default=None, help="a seed to read the left-out pieces' grad_rel at")
    parser.add_argument("--platform", default="tpu", help="what the readings are taken on (tests: cpu)")
    args = parser.parse_args()

    from benchmark.spec import Benchmark, _module
    from torchft_tpu.launch import export_compile_cache

    export_compile_cache()  # before JAX is imported: the place the benchmark's runs use
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import compare

    device = jax.devices()[0]
    if device.platform != args.platform:
        raise RuntimeError(f"JAX found {device.platform!r}, not {args.platform!r} — no reading")
    bench = Benchmark(ROOT)
    share_that_differs = _module("tools", "routing_ties", bench.bench_dir).share_that_differs
    cell = bench.cell(args.workload)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    reference, program = bench.reference(config["architecture"]), bench.program(config["architecture"])
    job = bench.job(traffic["job"])
    from torchft_tpu.models.transformer import _decoder

    cfg, bias = program.transformer_config(config), jnp.asarray(program.router_bias(config))
    chosen_by_program = jax.jit(lambda w, tokens: _decoder(w, tokens, cfg, router_bias=bias)[1]["chosen"])
    unbiased = dict(config, router_bias={"seed": 0, "scale": 0.0})
    chosen_by_reference = {
        name: jax.jit(lambda w, t, precision=precision, c=c: reference.routing(w, t, c, precision))
        for name, precision, c in (("float32", "float32", config), ("bfloat16", "bfloat16", config),
                                   ("float8", "float8", config), ("float32_no_bias", "float32", unbiased))
    }
    skip = reference.sizes_of(config)["experts"]
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = reference.make_weights(seed, config)
        batch = {k: jnp.asarray(v) for k, v in job.make_batch(seed, 0, 0, traffic, config["vocab_size"]).items()}
        tokens = batch["tokens"]
        sequences, seq_len = tokens.shape
        got = np.asarray(chosen_by_program(weights, tokens))
        got = got.reshape(got.shape[0], sequences * seq_len, 1)
        by = {
            name: np.concatenate([np.asarray(one(weights, tokens[i])) for i in range(sequences)], axis=1)
            for name, one in chosen_by_reference.items()
        }
        line = {
            "seed": seed, "choices_a_layer": sequences * seq_len,
            "program_vs_float32": share_that_differs(got, by["float32"]),
            "reference_bfloat16_vs_float32": share_that_differs(by["bfloat16"], by["float32"]),
            "reference_float8_vs_float32": share_that_differs(by["float8"], by["float32"]),
            "decided_by_the_bias": share_that_differs(by["float32_no_bias"], by["float32"]),
            "takes_no_expert": [float(np.mean(layer == skip)) for layer in by["float32"]],
        }
        if seed == args.left_out:
            indices = compare.sample_indices(seed, weights)
            line["grad_rel_limit"] = config["correct"]["grad_rel_limit"]
            line["grad_rel_without"] = {}
            for piece in reference.LEFT_OUT:
                one = reference.one_sequence_fn(config, "float32", piece)
                loss, grads = one(weights, tokens[0], batch["targets"][0])
                sample = compare.sample(grads, indices)
                del grads
                line["grad_rel_without"][piece] = compare.against_reference(
                    reference, config, weights, {k: v[:1] for k, v in batch.items()}, loss, sample, indices)["grad_rel"]
        lines.append(line)
        print(json.dumps(line), flush=True)
        del weights
    out = {"workload": args.workload, "device": device.device_kind, "seeds": len(lines)}
    for key in ("program_vs_float32", "reference_bfloat16_vs_float32", "reference_float8_vs_float32",
                "decided_by_the_bias", "takes_no_expert"):
        values = [v for line in lines for v in line[key]]
        out[key] = {"min": min(values), "max": max(values)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
