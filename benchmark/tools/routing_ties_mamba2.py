#!/usr/bin/env python3
"""`routing_ties_kda.py` for a configuration whose blocks are Mamba-2 mixers,
un-rotated attention and un-gated ReLU^2 experts (`architecture:
mamba2_moe_lm`), in one process on the chip.

    python3 benchmark/tools/routing_ties_mamba2.py --workload nemotron-twotower-30b-a3b.steady-1g-16k --seeds 1,2,3 [--left-out 1]

For each seed — the seed's weights and the cell's first batch — the share of
the tokens * k (token, expert) choices of each EXPERT block (in the blocks'
order) in which the program's set differs from the float32 reference's, and the
same share for the reference's own bfloat16 and float8 (the control's)
arithmetic; beside them how many of the choices the router's bias decides, and
the seeded distribution of the decay a = exp(dt A) over the Mamba-2 blocks (its
mean, the share under 0.5 and under 0.01).  One JSON line a seed, the ranges
last.

With `--left-out 1`, for the FIRST seed: the float32 reference WITHOUT one
piece of the mathematics (`reference.LEFT_OUT`: the decay, the D skip, the
convolution's earlier taps, the gate SiLU(z), the group norm, the square, the
router's scale, the shared expert) put in the program's place and judged by the
cell's own comparison: each must fail the limit.
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
    from torchft_tpu.models.transformer import _decoder

    cfg, bias = program.transformer_config(config), jnp.asarray(program.router_bias(config))
    chosen_by_program = jax.jit(
        lambda w, tokens: jnp.sort(_decoder(w, tokens, cfg, router_bias=bias)[1]["chosen"], axis=-1))
    unbiased = dict(config, router_bias={"seed": 0, "scale": 0.0})
    chosen_by_reference = {
        name: jax.jit(lambda w, t, precision=precision, c=c: reference.routing(w, t, c, precision))
        for name, precision, c in (("float32", "float32", config), ("bfloat16", "bfloat16", config),
                                   ("float8", "float8", config), ("float32_no_bias", "float32", unbiased))
    }
    seeds = [int(s) for s in args.seeds.split(",")]
    lines = []
    for seed in seeds:
        weights = reference.make_weights(seed, config)
        tokens = jnp.asarray(job.make_batch(seed, 0, 0, traffic, config["vocab_size"])["tokens"])
        sequences, seq_len = tokens.shape
        k = config["num_experts_per_tok"]
        got = np.asarray(chosen_by_program(weights, tokens))
        got = got.reshape(got.shape[0], sequences * seq_len, k)
        by = {
            name: np.concatenate([np.asarray(one(weights, tokens[i])) for i in range(sequences)], axis=1)
            for name, one in chosen_by_reference.items()
        }
        line = {
            "seed": seed, "choices_a_layer": sequences * seq_len * k,
            "program_vs_float32": share_that_differs(got, by["float32"]),
            "reference_bfloat16_vs_float32": share_that_differs(by["bfloat16"], by["float32"]),
            "reference_float8_vs_float32": share_that_differs(by["float8"], by["float32"]),
            "decided_by_the_bias": share_that_differs(by["float32_no_bias"], by["float32"]),
            "alpha": reference.decay_statistics(weights, tokens[0], config),
        }
        lines.append(line)
        print(json.dumps(line), flush=True)
        del weights
    out = {"workload": args.workload, "device": device.device_kind, "seeds": len(lines)}
    for key in ("program_vs_float32", "reference_bfloat16_vs_float32", "reference_float8_vs_float32", "decided_by_the_bias"):
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
        for piece in reference.LEFT_OUT:
            one = reference.one_sequence_fn(config, "float32", left_out=piece)
            loss, grads = one(weights, batch["tokens"][0], batch["targets"][0])
            rel, per_leaf = compare.grad_rel(compare.sample(grads, indices), want)
            del grads
            worst = max(per_leaf, key=per_leaf.get)
            limit = config["correct"]["grad_rel_limit"]
            print(json.dumps({"seed": seed, "left_out": piece, "grad_rel": rel, "grad_rel_limit": limit,
                              "fails": not rel <= limit,  # not finite fails too
                              "worst_leaf": worst, "worst": per_leaf[worst],
                              "loss_rel": abs(float(loss) - want_loss) / abs(want_loss)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
