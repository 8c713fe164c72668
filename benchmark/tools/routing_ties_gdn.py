#!/usr/bin/env python3
"""`routing_ties_kda.py` for a configuration whose mixers are Gated DeltaNet
and output-gated attention (`architecture: gdn_moe_lm`), in one process on the
chip.

    python3 benchmark/tools/routing_ties_gdn.py --workload qwen3-next-80b-a3b.steady-1g-16k --seeds 1,2,3 [--left-out 1]

For each seed — the seed's weights and the cell's first batch — the share of
the tokens * k (token, expert) choices of each layer in which the program's set
differs from the float32 reference's, and the same share for the reference's
own bfloat16 and float8 (the control's) arithmetic; beside them the seeded
distribution of the decay alpha = exp(g) over the Gated DeltaNet layers (its
mean, the share under 0.5 and under 0.01) and what the held experts' row buffer
saw (the busiest router output over the mean, the held rows over the buffer).
One JSON line a seed, the ranges last.

With `--left-out 1`, for the FIRST seed: the float32 reference WITHOUT one
piece of the mathematics, or with the wrong mechanism in its place
(`reference.LEFT_OUT`: the decay, the beta k k^T term, the convolution's
earlier taps, SiLU(z) over the head norm, key head j % 16 for j // 2,
attention's column gate, all 256 columns rotated, the norms' `+ 1`, the shared
expert's gate) put in the program's place and judged by the cell's own
comparison: each must fail the limit.
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
    parser.add_argument("--left-out", type=int, default=0)
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
    from torchft_tpu.models.moe import HELD_ROWS_FACTOR, held_rows
    from torchft_tpu.models.transformer import _decoder

    cfg = program.transformer_config(config)
    statistics = jax.jit(lambda w, tokens: {k: v for k, v in _decoder(w, tokens, cfg)[1].items()
                                            if k in ("chosen", "tokens_per_expert", "rows_held", "dropped")})
    chosen_by_reference = {
        precision: jax.jit(lambda w, t, precision=precision: reference.routing(w, t, config, precision))
        for precision in ("float32", "bfloat16", "float8")
    }
    seeds = [int(s) for s in args.seeds.split(",")]
    lines = []
    for seed in seeds:
        weights = reference.make_weights(seed, config)
        tokens = jnp.asarray(job.make_batch(seed, 0, 0, traffic, config["vocab_size"])["tokens"])
        sequences, seq_len = tokens.shape
        k, layers = config["num_experts_per_tok"], config["num_hidden_layers"]
        stats = statistics(weights, tokens)
        got = np.sort(np.asarray(stats["chosen"]), axis=-1).reshape(layers, sequences * seq_len, k)
        by = {
            name: np.concatenate([np.asarray(one(weights, tokens[i])) for i in range(sequences)], axis=1)
            for name, one in chosen_by_reference.items()
        }
        sent = np.asarray(stats["tokens_per_expert"], np.float64)
        buffer = held_rows(sequences * seq_len * k, cfg.moe_experts, cfg.n_held_experts, HELD_ROWS_FACTOR)
        line = {
            "seed": seed, "choices_a_layer": sequences * seq_len * k,
            "program_vs_float32": share_that_differs(got, by["float32"]),
            "reference_bfloat16_vs_float32": share_that_differs(by["bfloat16"], by["float32"]),
            "reference_float8_vs_float32": share_that_differs(by["float8"], by["float32"]),
            "alpha": reference.decay_statistics(weights, tokens[0], config),
            "load_max_over_mean": [float(row.max() / row.mean()) for row in sent],
            "held_rows_over_buffer": float(stats["rows_held"]) / layers / buffer, "dropped": int(stats["dropped"]),
        }
        lines.append(line)
        print(json.dumps(line), flush=True)
        del weights
    out = {"workload": args.workload, "device": device.device_kind, "seeds": len(lines)}
    for key in ("program_vs_float32", "reference_bfloat16_vs_float32", "reference_float8_vs_float32", "load_max_over_mean"):
        values = [v for line in lines for v in line[key]]
        out[key] = {"min": min(values), "max": max(values)}
    for key in ("mean", "share_under_half", "share_under_a_hundredth"):
        out["alpha_" + key] = {"min": min(l["alpha"][key] for l in lines), "max": max(l["alpha"][key] for l in lines)}
    print(json.dumps(out), flush=True)
    if args.left_out:
        seed = seeds[0]
        weights = reference.make_weights(seed, config)
        batch = {k: jnp.asarray(v) for k, v in job.make_batch(seed, 0, 0, traffic, config["vocab_size"]).items()}
        indices = compare.sample_indices(seed, weights)
        want_loss, want = compare.sequence_by_sequence(reference, config, weights, batch, indices)
        limit = config["correct"]["grad_rel_limit"]
        for piece in reference.LEFT_OUT:
            one = reference.one_sequence_fn(config, "float32", left_out=piece)
            loss, grads = one(weights, batch["tokens"][0], batch["targets"][0])
            rel, per_leaf = compare.grad_rel(compare.sample(grads, indices), want)
            del grads
            worst = max(per_leaf, key=per_leaf.get)
            print(json.dumps({"seed": seed, "left_out": piece, "grad_rel": rel, "grad_rel_limit": limit,
                              "fails": not rel <= limit,  # not finite fails too
                              "worst_leaf": worst, "worst": per_leaf[worst],
                              "loss_rel": abs(float(loss) - want_loss) / abs(want_loss)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
