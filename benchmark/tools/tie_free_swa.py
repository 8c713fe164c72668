#!/usr/bin/env python3
"""`limits.py`'s two readings with and without the near-ties of top-k routing,
for `architecture: swa_moe_lm`, in one process on the chip.

    python3 benchmark/tools/tie_free_swa.py --workload laguna-xs.2.steady-1g-16k --seeds 1,2,... [--control-seeds 1,2,...]

`grad_rel`, the number that decides `correct`, has a floor under its sound
readings: a share of a percent of the (token, expert) choices falls
differently in the program and in the float32 reference (`routing_ties_swa.py`),
and an expert that one side runs for a token and the other does not is a
difference of the size of the expert's whole contribution.  This tool reads
the same comparison with the choices taken out of it.  For each seed — the
seed's weights and the cell's first batch:

- `sound`: the program's sampled gradient against the float32 reference's, as
  a run's `reference_check` has it;
- `sound_tie_free`: the same against the float32 reference GIVEN the program's
  choices (`reference.one_sequence_fn`'s `forced`): what is left is the
  program's arithmetic — as far as the choices of the program's forward pass,
  compiled alone, are those of its gradient program;
- `ties_alone`: the float32 reference given the program's choices against
  itself choosing: what the choices that fell differently are worth;
- for the control seeds `control`: the reference in fp8 against itself in
  float32, each choosing for itself (`limits.py`'s control), and
  `control_tie_free`: the fp8 computation given the float32 reference's
  choices.

Each as `grad_rel` over every leaf and over the leaves that are no router's.
One JSON line a seed, the ranges last.
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
    parser.add_argument("--platform", default="tpu", help="what the readings are taken on (tests: cpu)")
    args = parser.parse_args()

    from benchmark.spec import Benchmark
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
    cell = bench.cell(args.workload)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    reference, program = bench.reference(config["architecture"]), bench.program(config["architecture"])
    job = bench.job(traffic["job"])
    from torchft_tpu.models.transformer import _decoder

    cfg = program.transformer_config(config)
    _, step = program.train_step(config, device)
    chosen_by_program = jax.jit(lambda w, tokens: _decoder(w, tokens, cfg)[1]["chosen"])  # [sparse layers, B, S, k]
    chosen_by_reference = jax.jit(lambda w, t: reference.routing(w, t, config, "float32"))

    def of_reference(weights, batch, indices, precision, forced=None):
        """The reference's sampled gradient, a sequence at a time (`compare.sequence_by_sequence` with `forced`)."""
        one = reference.one_sequence_fn(config, precision)
        n, total = batch["tokens"].shape[0], None
        for i in range(n):
            more = () if forced is None else (forced[:, i],)
            _, grads = one(weights, batch["tokens"][i], batch["targets"][i], *more)
            part = compare.sample(grads, indices)
            del grads
            total = {k: v / n for k, v in part.items()} if total is None else {k: total[k] + v / n for k, v in part.items()}
        return total

    def both(got, want):
        """`grad_rel` over every leaf, and over those that are no router's."""
        rel, per_leaf = compare.grad_rel(got, want)
        rest = [v for k, v in per_leaf.items() if "router" not in k]
        return {"all": rel, "no_router": float(np.sqrt(np.mean(np.square(rest)))), "worst": max(per_leaf.values()),
                "worst_leaf": max(per_leaf, key=per_leaf.get)}

    controls = {int(s) for s in args.control_seeds.split(",") if s}
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = reference.make_weights(seed, config)
        batch = {k: jnp.asarray(v) for k, v in job.make_batch(seed, 0, 0, traffic, config["vocab_size"]).items()}
        indices = compare.sample_indices(seed, weights)
        _, grads = step.grads(weights, batch)
        got = compare.sample(grads, indices)
        del grads
        float32 = of_reference(weights, batch, indices, "float32")
        line = {"seed": seed, "sound": both(got, float32)}
        forced = chosen_by_program(weights, batch["tokens"])
        given = of_reference(weights, batch, indices, "float32", forced)
        line["sound_tie_free"], line["ties_alone"] = both(got, given), both(given, float32)
        if seed in controls:
            line["control"] = both(of_reference(weights, batch, indices, "float8"), float32)
            forced = jnp.stack([chosen_by_reference(weights, t) for t in batch["tokens"]], axis=1)
            line["control_tie_free"] = both(of_reference(weights, batch, indices, "float8", forced), float32)
        lines.append(line)
        print(json.dumps(line), flush=True)
        del weights
    out = {"workload": args.workload, "device": device.device_kind, "seeds": len(lines), "control_seeds": len(controls)}
    for key in ("sound", "sound_tie_free", "ties_alone", "control", "control_tie_free"):
        have = [line[key] for line in lines if key in line]
        if have:
            out[key] = {over: {"min": min(h[over] for h in have), "max": max(h[over] for h in have)}
                        for over in ("all", "no_router")}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
