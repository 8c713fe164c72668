"""Fault-tolerant pipeline-parallel training: GPipe inside the group,
replicate across groups, heal pipeline-sharded state live.

The composition the reference describes for FSDP/TP ("fault tolerance
across the replicated dimension with any mix of ... across the other
dimensions", reference README) — demonstrated here for PIPELINE
parallelism, which the reference does not have at all (SURVEY.md §2.3).
Each process is one replica group whose transformer layer stack is sharded
across a pipeline mesh axis (stage-to-stage ppermute hops inside the jit
step, parallel/pipeline.py); groups average gradients through the
Manager's fault-tolerant allreduce; a killed group restarts and heals its
PIPELINE-SHARDED state in place (NamedShardings restored onto its own
mesh) from a healthy peer.

Run (two supervised groups; each simulates a pipeline x data slice on
virtual CPU devices — JAX_PLATFORMS=cpu because a chip belongs to one
process and these two would both take it)::

    JAX_PLATFORMS=cpu python -m torchft_tpu.launch --groups 2 \
        --max-restarts 3 -- python examples/train_pipeline.py --steps 200
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
    parser.add_argument("--microbatches", type=int, default=2)
    parser.add_argument("--lr", type=float, default=3e-3)
    parser.add_argument(
        "--schedule", choices=["gpipe", "1f1b"], default="gpipe",
        help="gpipe: forward pipeline + autodiff reverse; 1f1b: loss and "
        "backward inside the pipeline, activation memory bounded by the "
        "pipe depth",
    )
    parser.add_argument(
        "--pipe", type=int, default=2, help="pipeline stages per group"
    )
    parser.add_argument(
        "--devices", type=int, default=4,
        help="virtual devices forming this group's (pipeline x data) mesh",
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

    n_layers = 4
    if args.devices % args.pipe:
        parser.error(f"--devices {args.devices} not divisible by --pipe {args.pipe}")
    data = args.devices // args.pipe
    if n_layers % args.pipe:
        parser.error(f"{n_layers} layers not divisible over --pipe {args.pipe}")
    if args.batch % data or (args.batch // data) % args.microbatches:
        parser.error(
            f"--batch {args.batch} must divide over data axis {data} and "
            f"then into --microbatches {args.microbatches}"
        )

    prepare_jax_env(virtual_devices=args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu import GradientAverager, Optimizer
    from torchft_tpu.checkpointing.serialization import sharding_restorer
    from torchft_tpu.data import DistributedSampler
    from torchft_tpu.models import TransformerConfig, init_params
    from torchft_tpu.models.transformer import param_axes
    from torchft_tpu.parallel import TrainStep, ft_init_mesh
    from torchft_tpu.parallel.pipeline import (
        pipeline_1f1b_value_and_grad,
        pipeline_loss_fn,
    )

    replica_group, num_groups = replica_env()

    cfg = TransformerConfig(
        vocab_size=512,
        d_model=128,
        n_layers=n_layers,
        n_heads=4,
        n_kv_heads=4,
        d_ff=256,
        max_seq=64,
        dtype=jnp.float32,  # exact cross-group convergence for the demo
        remat=False,
    )
    seq = 64

    ftmesh = ft_init_mesh({"pipeline": args.pipe, "data": data})
    schedule_kwargs = (
        {
            "value_and_grad_fn": lambda p, b: pipeline_1f1b_value_and_grad(
                p, b, cfg, ftmesh.mesh, num_microbatches=args.microbatches
            )
        }
        if args.schedule == "1f1b"
        else {
            "loss_fn": lambda p, b: pipeline_loss_fn(
                p, b, cfg, ftmesh.mesh, num_microbatches=args.microbatches
            )
        }
    )
    step_fn = TrainStep(ftmesh, optax.sgd(args.lr), **schedule_kwargs)

    # Synthetic token stream, identical in every process (seeded).
    rng = np.random.default_rng(0)
    dataset = rng.integers(0, cfg.vocab_size, size=(4096, seq)).astype(np.int32)

    state = {}

    def save():
        return {"params": state["opt"].params, "opt_state": state["opt"].opt_state}

    def load(sd):
        # The transport restored NamedShardings onto THIS group's mesh —
        # the layer stack lands back sharded over the pipeline axis.
        state["opt"].params = sd["params"]
        state["opt"].opt_state = sd["opt_state"]

    manager = make_manager(
        save, load, replica_group, restore_sharding=sharding_restorer(save)
    )
    ftmesh.manager = manager

    params = ftmesh.shard_params(init_params(jax.random.PRNGKey(7), cfg), param_axes(cfg))
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
            batch = {
                "tokens": jax.device_put(tokens, ftmesh.sharding("batch", "seq")),
                "targets": jax.device_put(
                    jnp.roll(tokens, -1, axis=1), ftmesh.sharding("batch", "seq")
                ),
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
            layer_spec = str(
                jax.tree_util.tree_leaves(
                    state["opt"].params["layers"]
                )[0].sharding.spec
            )
            print(
                f"[group {replica_group}] FINAL step={manager.current_step()} "
                f"params_sha256={params_digest(state['opt'].params)} "
                f"layer_sharding={layer_spec}",
                flush=True,
            )
    finally:
        manager.shutdown()


if __name__ == "__main__":
    main()
