#!/usr/bin/env python3
"""Counts the routing choices that fall differently in the program and in the
float32 reference, in one process on the chip.

    python3 benchmark/tools/routing_ties.py --workload olmoe-1b-7b.steady-1g --seeds 1,2,3

A sparse-expert layer takes the k largest of its router's probabilities.  At
random weights the k-th and the next lie close for some positions, and the
program's bf16 residual stream then puts another expert among the k than the
float32 reference does: a discrete difference that `grad_rel` sees on the
router and expert leaves.  For each seed — the seed's weights and the cell's
first batch — this prints, per layer, the share of the tokens * k
(token, expert) choices in which the program's set differs from the
reference's, and the same share for the reference's own bfloat16 and float8
(the control's) arithmetic.  One JSON line a seed, the ranges last.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def share_that_differs(a, b) -> list:
    """a, b: [layers, tokens, k] expert ids, each position's k sorted.  Per
    layer, the share of choices of `a` that are not among `b`'s."""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    same = (a[..., :, None] == b[..., None, :]).any(axis=-1)  # [layers, tokens, k]
    return [float(1.0 - layer.mean()) for layer in same]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--platform", default="tpu", help="what the readings are taken on (tests: cpu)")
    args = parser.parse_args()

    from benchmark.spec import Benchmark
    from torchft_tpu.launch import export_compile_cache

    export_compile_cache()  # before JAX is imported: the place the benchmark's runs use
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0]
    if device.platform != args.platform:
        raise RuntimeError(f"JAX found {device.platform!r}, not {args.platform!r} — no reading")
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    reference, program = bench.reference(config["architecture"]), bench.program(config["architecture"])
    job = bench.job(traffic["job"])
    from torchft_tpu.models.transformer import _decoder

    cfg = program.transformer_config(config)
    chosen_by_program = jax.jit(lambda w, tokens: jnp.sort(_decoder(w, tokens, cfg)[1]["chosen"], axis=-1))
    chosen_by_reference = {
        precision: jax.jit(lambda w, t, precision=precision: reference.routing(w, t, config, precision))
        for precision in ("float32", "bfloat16", "float8")
    }
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = reference.make_weights(seed, config)
        tokens = jnp.asarray(job.make_batch(seed, 0, 0, traffic, config["vocab_size"])["tokens"])
        layers, sequences, seq_len, k = (config["num_hidden_layers"], *tokens.shape, config["num_experts_per_tok"])
        got = np.asarray(chosen_by_program(weights, tokens)).reshape(layers, sequences * seq_len, k)
        by_precision = {
            precision: np.concatenate([np.asarray(one(weights, tokens[i])) for i in range(sequences)], axis=1)
            for precision, one in chosen_by_reference.items()
        }
        line = {
            "seed": seed, "choices_a_layer": sequences * seq_len * k,
            "program_vs_float32": share_that_differs(got, by_precision["float32"]),
            "reference_bfloat16_vs_float32": share_that_differs(by_precision["bfloat16"], by_precision["float32"]),
            "reference_float8_vs_float32": share_that_differs(by_precision["float8"], by_precision["float32"]),
        }
        lines.append(line)
        print(json.dumps(line), flush=True)
        del weights
    out = {"workload": args.workload, "device": device.device_kind, "seeds": len(lines)}
    for key in ("program_vs_float32", "reference_bfloat16_vs_float32", "reference_float8_vs_float32"):
        values = [v for line in lines for v in line[key]]
        out[key] = {"min": min(values), "max": max(values)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
