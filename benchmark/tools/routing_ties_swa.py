#!/usr/bin/env python3
"""`routing_ties.py` for a configuration whose first layer's feed-forward is
dense and whose router takes no bias (`architecture: swa_moe_lm`), in one
process on the chip.

    python3 benchmark/tools/routing_ties_swa.py --workload laguna-xs.2.steady-1g-16k --seeds 1,2,3

For each seed — the seed's weights and the cell's first batch — the share of
the tokens * k (token, expert) choices of each SPARSE layer in which the
program's set differs from the float32 reference's, and the same share for the
reference's own bfloat16 and float8 (the control's) arithmetic.  One JSON line a
seed, the ranges last.
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
    parser.add_argument("--platform", default="tpu", help="what the readings are taken on (tests: cpu)")
    args = parser.parse_args()

    from benchmark.spec import Benchmark, _module
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
    share_that_differs = _module("tools", "routing_ties", bench.bench_dir).share_that_differs
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
        sequences, seq_len = tokens.shape
        got = np.asarray(chosen_by_program(weights, tokens))
        got = got.reshape(got.shape[0], sequences * seq_len, config["num_experts_per_tok"])
        by_precision = {
            precision: np.concatenate([np.asarray(one(weights, tokens[i])) for i in range(sequences)], axis=1)
            for precision, one in chosen_by_reference.items()
        }
        line = {
            "seed": seed, "sparse_layers": got.shape[0], "choices_a_layer": sequences * seq_len * got.shape[2],
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
