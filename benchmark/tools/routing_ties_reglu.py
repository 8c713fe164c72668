#!/usr/bin/env python3
"""`routing_ties_swa.py` for a configuration whose router reads the layer's
input and whose experts are ReGLU (`architecture: early_router_moe_lm`), in one
process on the chip.

    python3 benchmark/tools/routing_ties_reglu.py --workload smallthinker-21b-a3b.steady-1g-16k --seeds 1,2,3 [--wrong 1]

For each seed — the seed's weights and the cell's first batch — the two things
that put a floor under a sound `grad_rel`, each counted once:

- the share of the tokens * k (token, expert) choices of each layer in which
  the program's set differs from the float32 reference's (a near-tie between
  the 6th and 7th expert), and the same share for the reference's own bfloat16
  and float8 (the control's) arithmetic;
- the share of the held experts' (position, hidden unit) pairs, over the
  positions routed to the expert in both computations, whose gate pre-activation
  lies on the other side of ReLU's mask in the reference's bfloat16 and float8
  arithmetic than in its float32 (a pre-activation within rounding of zero).
  The program hands out no mask, only its count (`moe_active_units`): the
  bfloat16 reference stands in for it, as it does in the line above.

One JSON line a seed, the ranges last.

With `--wrong 1`, for the FIRST seed: the PROGRAM with a wrong mechanism in a
kind of layer — the window layers over the whole triangle, the full layers under
the window, RoPE on the full layers, the router on the experts' input h2, SiLU
for ReLU — judged by the cell's own comparison against the reference as
published: each must fail the limit.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _kinds(cfg, **changes):
    """cfg with every layer kind changed by `changes[stack]` (a dict of fields)."""
    return dataclasses.replace(cfg, pattern=tuple(
        dataclasses.replace(kind, **changes.get(kind.stack, {})) for kind in cfg.pattern))


def wrong_programs(cfg, window: int):
    """name -> the program's settings with one mechanism wrong."""
    return {
        "window_layers_over_the_whole_triangle": _kinds(cfg, window_layers=dict(window=None)),
        "full_layers_under_the_window": _kinds(cfg, layers=dict(window=window)),
        "rope_on_the_full_layers": _kinds(cfg, layers=dict(rotary_fraction=1.0)),
        "router_on_the_experts_input": dataclasses.replace(cfg, moe_router_early=False),
        "silu_for_relu": dataclasses.replace(cfg, moe_activation="silu"),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--wrong", type=int, default=0)
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
    from torchft_tpu.models.transformer import _decoder, loss_and_counters
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    cfg = program.transformer_config(config)
    k, first, held = cfg.moe_top_k, config["expert_parallel"]["first_expert_held"], config["moe_num_primary_experts"]
    chosen_by_program = jax.jit(lambda w, tokens: jnp.sort(_decoder(w, tokens, cfg)[1]["chosen"], axis=-1))
    by_reference = {
        precision: jax.jit(lambda w, t, precision=precision: reference.routing(w, t, config, precision, units=True))
        for precision in ("float32", "bfloat16", "float8")
    }

    @jax.jit
    def flipped(lit, chosen, lit32, chosen32):
        """Per layer, of the (position, unit) pairs of held experts that both routings sent the position to, the
        share on the other side of ReLU's mask."""
        experts = first + jnp.arange(held)
        both = jnp.stack([jnp.any(c[:, :, :, None] == experts, axis=2) for c in (chosen, chosen32)]).all(axis=0)
        both = both.transpose(0, 2, 1)[..., None]  # [layers, held, S, 1]
        return jnp.sum((lit != lit32) & both, axis=(1, 2, 3)) / (jnp.sum(both, axis=(1, 2, 3)) * lit.shape[-1])

    seeds = [int(s) for s in args.seeds.split(",")]
    lines = []
    for seed in seeds:
        weights = reference.make_weights(seed, config)
        tokens = jnp.asarray(job.make_batch(seed, 0, 0, traffic, config["vocab_size"])["tokens"])
        sequences, seq_len = tokens.shape

        def of_reference(precision):
            """(chosen [layers, tokens, k], lit [layers, held, tokens, F]), the sequences one after the other."""
            parts = [by_reference[precision](weights, tokens[i]) for i in range(sequences)]
            return jnp.concatenate([c for c, _ in parts], axis=1), jnp.concatenate([l for _, l in parts], axis=2)

        got = np.asarray(chosen_by_program(weights, tokens)).reshape(-1, sequences * seq_len, k)
        chosen32, lit32 = of_reference("float32")
        line = {"seed": seed, "layers": got.shape[0], "choices_a_layer": sequences * seq_len * k,
                "program_vs_float32": share_that_differs(got, np.asarray(chosen32)),
                "active_share_float32": float(jnp.mean(lit32))}
        for precision in ("bfloat16", "float8"):
            chosen, lit = of_reference(precision)
            line[f"reference_{precision}_vs_float32"] = share_that_differs(np.asarray(chosen), np.asarray(chosen32))
            line[f"mask_{precision}_vs_float32"] = [float(v) for v in flipped(lit, chosen, lit32, chosen32)]
            del chosen, lit
        del chosen32, lit32, weights
        lines.append(line)
        print(json.dumps(line), flush=True)
    out = {"workload": args.workload, "device": device.device_kind, "seeds": len(lines)}
    for key in ("program_vs_float32", "reference_bfloat16_vs_float32", "reference_float8_vs_float32",
                "mask_bfloat16_vs_float32", "mask_float8_vs_float32"):
        values = [v for line in lines for v in line[key]]
        out[key] = {"min": min(values), "max": max(values)}
    print(json.dumps(out), flush=True)
    if args.wrong:
        seed = seeds[0]
        weights = reference.make_weights(seed, config)
        batch = {name: jnp.asarray(v) for name, v in job.make_batch(seed, 0, 0, traffic, config["vocab_size"]).items()}
        indices = compare.sample_indices(seed, weights)
        want_loss, want = compare.sequence_by_sequence(reference, config, weights, batch, indices)
        limit = config["correct"]["grad_rel_limit"]
        ftmesh = ft_init_mesh({"data": 1}, devices=[device])
        for name, wrong in dict(as_published=cfg, **wrong_programs(cfg, config["sliding_window_size"])).items():
            step = TrainStep(ftmesh, program.optimizer(config), lambda p, b, c=wrong: loss_and_counters(p, b, c),
                             loss_has_counters=True)
            loss, grads = step.grads(weights, batch)
            rel, per_leaf = compare.grad_rel(compare.sample(grads, indices), want)
            del grads, step
            worst = max(per_leaf, key=per_leaf.get)
            print(json.dumps({"seed": seed, "program": name, "grad_rel": rel, "grad_rel_limit": limit,
                              "fails": not rel <= limit, "worst_leaf": worst, "worst": per_leaf[worst],
                              "loss_rel": abs(float(loss) - want_loss) / abs(want_loss)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
