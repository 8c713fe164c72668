#!/usr/bin/env python3
"""`routing_ties.py` for a configuration trained by block diffusion
(`architecture: bd_moe_lm`), in one process on the chip: what gives
`correct.readings` of its file their numbers.

    python3 benchmark/tools/routing_ties_bd.py --workload sdar-30b-a3b.steady-1g-16k --seeds 1,2,3 [--left-out 1]

For each seed — the seed's weights and the cell's first batch — per layer: the
share of the 2 L * k (position, expert) choices in which the program's set
differs from the float32 reference's, and the same share for the reference's
own bfloat16 and float8 (the control's) arithmetic; what the masked rows do to
the load (about a quarter of the 2 L rows carry the ONE [MASK] embedding into
the first layer and keep most of it after): the busiest of the router's outputs
over the mean, how many of the [MASK] row's own k experts are held here, the
rows that fell on held experts as a share of the program's row buffer, and the
assignments dropped; and the objective's counters (`bd_masked_share`,
`bd_weight_mean`, `bd_live_pairs_share`).  One JSON line a seed, the ranges last.

With `--left-out 1`, for the FIRST seed: the float32 reference with one
mechanism WRONG (`reference.LEFT_OUT`: a causal mask over 2 L, the clean half
dropped, no 1 / t weight, the shift, RoPE positions 0 .. 2 L - 1) put in the
program's place and judged by the cell's own comparison: each must fail the limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

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
    from torchft_tpu.models.moe import HELD_ROWS_FACTOR
    from torchft_tpu.models.transformer import _decoder, block_diffusion_stream
    from torchft_tpu.ops.attention import bd_pairs_walked

    cfg = program.transformer_config(config)
    first, held = cfg.moe_held or (0, cfg.moe_experts)

    @jax.jit
    def by_program(w, tokens):
        stream, masked, weight = block_diffusion_stream(tokens, cfg)
        aux = _decoder(w, stream, cfg)[1]
        return (jnp.sort(aux["chosen"], axis=-1), aux["tokens_per_expert"], aux["rows_held"], aux["dropped"],
                stream, jnp.mean(masked.astype(jnp.float32)), jnp.mean(weight))

    seeds = [int(s) for s in args.seeds.split(",")]
    lines = []
    for seed in seeds:
        weights = reference.make_weights(seed, config)
        tokens = jnp.asarray(job.make_batch(seed, 0, 0, traffic, config["vocab_size"])["tokens"])
        sequences, seq_len = tokens.shape
        k, layers = config["num_experts_per_tok"], config["num_hidden_layers"]
        got, per_expert, rows_held, dropped, stream, masked_share, weight_mean = by_program(weights, tokens)
        got = np.asarray(got).reshape(layers, sequences * 2 * seq_len, k)
        by = {precision: np.concatenate([np.asarray(reference.chosen(weights, tokens[i], config, precision))
                                         for i in range(sequences)], axis=1)
              for precision in ("float32", "bfloat16", "float8")}
        per_expert = np.asarray(per_expert)
        # the first [MASK] row of the stream (a noised position whose id is the mask's), and which of its experts are held
        at = int(np.argmax(np.asarray(stream).reshape(-1) == cfg.vocab_size - 1))
        mask_held = [int(((got[layer, at] >= first) & (got[layer, at] < first + held)).sum()) for layer in range(layers)]
        buffer_rows = HELD_ROWS_FACTOR * sequences * 2 * seq_len * k * held / cfg.moe_experts
        line = {
            "seed": seed, "choices_a_layer": sequences * 2 * seq_len * k,
            "program_vs_float32": share_that_differs(got, by["float32"]),
            "reference_bfloat16_vs_float32": share_that_differs(by["bfloat16"], by["float32"]),
            "reference_float8_vs_float32": share_that_differs(by["float8"], by["float32"]),
            "load_max_over_mean": [float(row.max() / row.mean()) for row in per_expert],
            "held_load_max_over_mean": [float(row[first:first + held].max() / row.mean()) for row in per_expert],
            "mask_rows_experts_held": mask_held,
            "rows_held_over_buffer": float(rows_held) / (layers * buffer_rows), "dropped": int(dropped),
            "bd_masked_share": float(masked_share), "bd_weight_mean": float(weight_mean),
            "bd_live_pairs_share": bd_pairs_walked(2 * seq_len, cfg.bd_block_length) / (2 * seq_len) ** 2,
        }
        lines.append(line)
        print(json.dumps(line), flush=True)
        del weights
    out = {"workload": args.workload, "device": device.device_kind, "seeds": len(lines)}
    for key in ("program_vs_float32", "reference_bfloat16_vs_float32", "reference_float8_vs_float32",
                "load_max_over_mean", "held_load_max_over_mean", "mask_rows_experts_held"):
        values = [v for line in lines for v in line[key]]
        out[key] = {"min": min(values), "max": max(values)}
    for key in ("rows_held_over_buffer", "dropped", "bd_masked_share", "bd_weight_mean"):
        out[key] = {"min": min(l[key] for l in lines), "max": max(l[key] for l in lines)}
    print(json.dumps(out), flush=True)
    if args.left_out:
        seed = seeds[0]
        weights = reference.make_weights(seed, config)
        batch = {k: jnp.asarray(v) for k, v in job.make_batch(seed, 0, 0, traffic, config["vocab_size"]).items()}
        indices = compare.sample_indices(seed, weights)
        want_loss, want = compare.sequence_by_sequence(reference, config, weights, batch, indices)
        limit = config["correct"]["grad_rel_limit"]
        for piece in reference.LEFT_OUT:
            # the reference with the piece wrong, in the program's place in the cell's own comparison
            wrong = types.SimpleNamespace(
                one_sequence_fn=lambda c, precision, piece=piece: reference.one_sequence_fn(c, precision, piece))
            total_loss, total = compare.sequence_by_sequence(wrong, config, weights, batch, indices)
            rel, per_leaf = compare.grad_rel(total, want)
            worst = max(per_leaf, key=per_leaf.get)
            print(json.dumps({"seed": seed, "left_out": piece, "grad_rel": rel, "grad_rel_limit": limit,
                              "fails": not rel <= limit,  # not finite fails too
                              "worst_leaf": worst, "worst": per_leaf[worst],
                              "loss_rel": abs(total_loss - want_loss) / abs(want_loss)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
