#!/usr/bin/env python3
"""How many of the keys a sparse-attention configuration's indexer selects
differ between the program and the float32 reference, in one process on the chip.

    python3 benchmark/tools/selection_ties.py --workload keye-vl-2.0-30b-a3b.steady-1g-32k --seeds 1,2,3

For each seed — the seed's weights and the first sequence of the cell's first
batch — and each layer: the share of the selected (query, key) pairs that the
program (bf16 activations, its kernels' exact top-k of ITS scores) selects and
the float32 reference does not (both keep min(position + 1, topk) keys a query,
so as many fall the other way), and the same share for the reference's own
bfloat16 and float8 (the control's) arithmetic.  A pair differs where the
topk-th and the next index score are closer than the arithmetic's rounding: a
property of top-k over 16,384 candidates, which puts a floor under `grad_rel`.
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
    parser.add_argument("--platform", default="tpu", help="what the readings are taken on (tests: cpu)")
    args = parser.parse_args()

    from benchmark.spec import Benchmark
    from torchft_tpu.launch import export_compile_cache

    export_compile_cache()  # before JAX is imported: the place the benchmark's runs use
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != args.platform:
        raise RuntimeError(f"JAX found {device.platform!r}, not {args.platform!r} — no reading")
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    reference, program = bench.reference(config["architecture"]), bench.program(config["architecture"])
    job = bench.job(traffic["job"])
    from torchft_tpu.models.transformer import _index_operands, _layer
    from torchft_tpu.ops import rms_norm
    from torchft_tpu.ops.sparse_attention import packed_lower_triangle, selection
    from torchft_tpu.parallel.sharding import ShardingRules

    cfg = program.transformer_config(config)

    @jax.jit
    def program_selects(x, w, positions):
        picked = selection(*_index_operands(cfg, rms_norm(x, w["attn_norm"], cfg.rms_eps), w, positions),
                           topk=cfg.dsa_topk)
        return picked if picked.ndim == 4 else packed_lower_triangle(picked)

    advance = jax.jit(lambda x, w, positions: _layer(cfg, None, ShardingRules(), x, w, positions)[0])

    @jax.jit
    def lost(mine, theirs):
        """Share of `theirs`'s pairs that `mine` lacks (packed int8 | bool against dense bool)."""
        theirs = packed_lower_triangle(theirs[None])
        return jnp.sum((theirs != 0) & (mine == 0)) / jnp.sum(theirs != 0)

    pack = jax.jit(lambda dense: packed_lower_triangle(dense[None]))
    keys = ("program_vs_float32", "reference_bfloat16_vs_float32", "reference_float8_vs_float32")
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = reference.make_weights(seed, config)
        tokens = jnp.asarray(job.make_batch(seed, 0, 0, traffic, config["vocab_size"])["tokens"])[:1]
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
        x = weights["embed"].astype(cfg.dtype)[tokens]
        line = {"seed": seed, "layers": config["num_hidden_layers"], **{k: [] for k in keys}}
        by = {p: reference.selection(weights, tokens[0], config, p) for p in ("float32", "bfloat16", "float8")}
        for i in range(config["num_hidden_layers"]):
            w = jax.tree.map(lambda leaf, i=i: leaf[i], weights["layers"])
            want = next(by["float32"])
            line[keys[0]].append(float(lost(program_selects(x, w, positions), want)))
            for key, precision in zip(keys[1:], ("bfloat16", "float8")):
                line[key].append(float(lost(pack(next(by[precision])), want)))
            x = advance(x, w, positions)
        lines.append(line)
        print(json.dumps(line), flush=True)
        del weights
    out = {"workload": args.workload, "device": device.device_kind, "seeds": len(lines)}
    for key in keys:
        values = [v for line in lines for v in line[key]]
        out[key] = {"min": min(values), "max": max(values)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
