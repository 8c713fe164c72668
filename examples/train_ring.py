"""Fault-tolerant LONG-CONTEXT training: ring attention inside the group,
replicate across groups, heal sequence-sharded state live.

Each process is one replica group whose activations are sharded along the
sequence axis of its own (data x sequence) mesh; attention runs as a
K/V-rotation ring over that axis (ops/ring_attention.py — ppermute hops,
online log-sum-exp merges), optionally in the work-balanced zigzag layout.
Groups average gradients through the Manager's fault-tolerant allreduce; a
killed group restarts and heals in place from a healthy peer.  The
reference has neither sequence parallelism nor this composition
(SURVEY.md §2.3); the FT mechanics mirror its DDP recovery story
(torchft/manager_integ_test.py:281).

Run (two supervised groups, each simulating a slice on virtual CPU devices;
JAX_PLATFORMS=cpu because a chip belongs to one process and these two would
both take it)::

    JAX_PLATFORMS=cpu python -m torchft_tpu.launch --groups 2 \
        --max-restarts 3 -- python examples/train_ring.py --steps 200 \
        --layout zigzag
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from _common import (
    TrainGate,
    make_manager,
    params_digest,
    prepare_jax_env,
    replica_env,
)


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--lr", type=float, default=3e-3)
    parser.add_argument(
        "--layout", choices=["contiguous", "zigzag"], default="contiguous",
        help="sequence layout for the causal ring (zigzag balances work)",
    )
    parser.add_argument(
        "--sequence", type=int, default=4,
        help="ring size: sequence-axis shards per group",
    )
    parser.add_argument(
        "--devices", type=int, default=4,
        help="virtual devices forming this group's (data x sequence) mesh",
    )
    parser.add_argument(
        "--require-merged-final", type=int, default=0,
        help="keep stepping past --steps until a committed step ran with "
        "at least this many participating groups (deterministic merged "
        "finish for the kill/heal tests)",
    )
    parser.add_argument(
        "--steps-cap", type=int, default=0,
        help="hard step bound when --require-merged-final can never be met",
    )
    args = parser.parse_args()

    if args.devices % args.sequence:
        parser.error(
            f"--devices {args.devices} not divisible by --sequence {args.sequence}"
        )

    prepare_jax_env(virtual_devices=args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu import GradientAverager, Optimizer
    from torchft_tpu.checkpointing.serialization import sharding_restorer
    from torchft_tpu.data import DistributedSampler
    from torchft_tpu.models import TransformerConfig, init_params, loss_fn
    from torchft_tpu.models.transformer import param_axes
    from torchft_tpu.ops.ring_attention import to_zigzag
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    replica_group, num_groups = replica_env()

    seq = 64
    cfg = TransformerConfig(
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        d_ff=256,
        max_seq=seq,
        dtype=jnp.float32,  # exact cross-group convergence for the demo
        attention="ring",
        ring_layout=args.layout,
    )

    data = args.devices // args.sequence
    ftmesh = ft_init_mesh({"data": data, "sequence": args.sequence})
    step_fn = TrainStep(
        ftmesh, optax.sgd(args.lr),
        lambda p, b: loss_fn(p, b, cfg, ftmesh.mesh, ftmesh.rules),
    )

    rng = np.random.default_rng(0)
    dataset = rng.integers(0, cfg.vocab_size, size=(4096, seq)).astype(np.int32)

    state = {}

    def save():
        return {"params": state["opt"].params, "opt_state": state["opt"].opt_state}

    def load(sd):
        state["opt"].params = sd["params"]
        state["opt"].opt_state = sd["opt_state"]

    manager = make_manager(
        save, load, replica_group, restore_sharding=sharding_restorer(save)
    )
    ftmesh.manager = manager

    params = ftmesh.shard_params(
        init_params(jax.random.PRNGKey(7), cfg), param_axes(cfg)
    )
    state["opt"] = Optimizer(manager, optax.sgd(args.lr), params)
    averager = GradientAverager(manager)

    sampler = DistributedSampler(
        len(dataset),
        replica_group=replica_group,
        num_replica_groups=num_groups,
        shuffle=True,
    )

    gate = TrainGate(
        manager, args.steps,
        require_merged=args.require_merged_final, steps_cap=args.steps_cap,
    )
    try:
        while gate.should_continue():
            state["opt"].step_begin()
            step = manager.current_step()
            sampler.set_epoch(step)
            idx = [i for _, i in zip(range(args.batch), iter(sampler))]
            tokens = jnp.asarray(dataset[idx])
            targets = jnp.roll(tokens, -1, axis=1)
            if args.layout == "zigzag":
                # One host-side permutation pair; rope positions follow
                # inside the model (TransformerConfig.ring_layout).
                tokens = to_zigzag(tokens, args.sequence, axis=1)
                targets = to_zigzag(targets, args.sequence, axis=1)
            batch = {
                "tokens": jax.device_put(tokens, ftmesh.sharding("batch", "seq")),
                "targets": jax.device_put(targets, ftmesh.sharding("batch", "seq")),
            }
            loss, grads = step_fn.grads(state["opt"].params, batch)
            grads = averager.allreduce(grads)
            committed = state["opt"].step(grads)
            gate.note_commit(committed)
            print(
                f"[group {replica_group}] step={step} loss={float(loss):.4f} "
                f"participants={manager.num_participants()} committed={committed}",
                flush=True,
            )

        if not gate.finish(replica_group):
            sample = jax.tree_util.tree_leaves_with_path(
                state["opt"].params["layers"]
            )[0]
            print(
                f"[group {replica_group}] FINAL step={manager.current_step()} "
                f"params_sha256={params_digest(state['opt'].params)} "
                f"ring_layout={args.layout} "
                f"sample_sharding={sample[1].sharding.spec}",
                flush=True,
            )
    finally:
        manager.shutdown()


if __name__ == "__main__":
    main()
