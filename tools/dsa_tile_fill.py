#!/usr/bin/env python3
"""How full the selection leaves the attention kernels' tiles, in one process on the chip.

    chiprun -- python tools/dsa_tile_fill.py --workload keye-vl-2.0-30b-a3b.steady-1g-32k --seeds 1,2

A standalone probe: no cell runs it.  For each seed — the seed's weights and
the first sequence of the cell's first batch, as `benchmark/tools/selection_ties.py`
makes them — the FIRST layer's selection (`ops.sparse_attention.selection`: the
packed int8 mask the masked flash kernels read) counted tile by tile: of the
n (n + 1) / 2 tiles of 512 x 512 that the kernels walk, how many lie past the
dense prefix (query tiles whose queries all keep `topk` keys of more than
`topk` visible), and of those how many the selection left empty, how many
under 1% full (fewer than 2,622 of a tile's 262,144 pairs), and the least,
median and largest count.  A walk can skip a tile only if it is empty.  One
JSON line a seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--platform", default="tpu", help="what the readings are taken on (tests: cpu)")
    args = parser.parse_args()

    from benchmark.spec import Benchmark
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
    from torchft_tpu.models.attention import _index_operands
    from torchft_tpu.ops import rms_norm
    from torchft_tpu.ops.sparse_attention import packed_lower_triangle, selection

    cfg = program.transformer_config(config)

    @jax.jit
    def pairs_a_tile(x, w, positions):
        picked = selection(*_index_operands(cfg, rms_norm(x, w["attn_norm"], cfg.rms_eps), w, positions),
                           topk=cfg.dsa_topk)
        picked = picked if picked.ndim == 4 else packed_lower_triangle(picked)
        return jnp.sum(picked != 0, axis=(2, 3), dtype=jnp.int32)[0]

    for seed in (int(s) for s in args.seeds.split(",")):
        weights = reference.make_weights(seed, config)
        tokens = jnp.asarray(job.make_batch(seed, 0, 0, traffic, config["vocab_size"])["tokens"])[:1]
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
        x = weights["embed"].astype(cfg.dtype)[tokens]
        counts = np.asarray(pairs_a_tile(x, jax.tree.map(lambda leaf: leaf[0], weights["layers"]), positions))
        seq = tokens.shape[1]
        tile = min(512, seq)
        n = seq // tile
        rows = np.asarray([i for i in range(n) for _ in range(i + 1)])  # `ops.attention._tri`'s order
        sparse = counts[rows * tile >= cfg.dsa_topk]
        print(json.dumps({
            "workload": args.workload, "device": device.device_kind, "seed": seed, "layer": 0, "tile": tile,
            "tiles": int(counts.size), "pairs_selected": int(counts.sum()),
            "tiles_past_the_dense_prefix": int(sparse.size),
            "empty": int((sparse == 0).sum()), "under_1_percent": int((sparse < 0.01 * tile * tile).sum()),
            **({"least": int(sparse.min()), "median": float(np.median(sparse)), "largest": int(sparse.max())}
               if sparse.size else {}),
        }), flush=True)
        del weights
    return 0


if __name__ == "__main__":
    sys.exit(main())
