"""Fault-tolerant HSDP training example: shard inside the group, replicate
across groups, heal sharded state live.

Reference parity: the reference's HSDP story is torch FSDP2 over a
ManagedDeviceMesh (torchft/device_mesh.py:290-323, torchft/fsdp_test.py) —
fault tolerance across the replicated dimension with FSDP/TP inside each
replica group.  Here each process is one replica group whose transformer
params are sharded over the group's own (fsdp x tensor) device mesh; groups
average gradients through the Manager's fault-tolerant allreduce; a killed
group restarts, heals its SHARDED state in place (NamedShardings restored on
its own mesh) from a healthy peer, and rejoins.

Run (two supervised groups; each simulates a 4-device slice on CPU)::

    python -m torchft_tpu.launch --groups 2 --max-restarts 3 -- \
        python examples/train_hsdp.py --steps 200

On real hardware drop the virtual-device flag: the group mesh is the TPU
slice's ICI devices and the cross-group dimension rides DCN unchanged.
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
        "--devices", type=int, default=4,
        help="virtual devices forming this group's (fsdp x tensor) mesh",
    )
    parser.add_argument(
        "--ckpt_dir",
        default=os.environ.get("TPUFT_CKPT_DIR", ""),
        help="durable checkpoint directory; empty disables disk checkpoints",
    )
    parser.add_argument("--ckpt_every", type=int, default=20)
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
    from torchft_tpu.parallel import TrainStep, ft_init_mesh

    replica_group, num_groups = replica_env()

    cfg = TransformerConfig(
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        d_ff=256,
        max_seq=64,
        dtype=jnp.float32,  # exact cross-group convergence for the demo
    )
    seq = 64

    fsdp = max(1, args.devices // 2)
    tensor = max(1, args.devices // fsdp)
    ftmesh = ft_init_mesh({"fsdp": fsdp, "tensor": tensor})
    step_fn = TrainStep(
        ftmesh, optax.sgd(args.lr),
        lambda p, b: loss_fn(p, b, cfg, ftmesh.mesh, ftmesh.rules),
    )

    # Synthetic token stream, identical in every process (seeded).
    rng = np.random.default_rng(0)
    dataset = rng.integers(0, cfg.vocab_size, size=(4096, seq)).astype(np.int32)

    state = {}

    def save():
        return {"params": state["opt"].params, "opt_state": state["opt"].opt_state}

    def load(sd):
        # The transport restored NamedShardings onto THIS group's mesh
        # (in-place sharded receive); adopt the healed trees as-is.
        state["opt"].params = sd["params"]
        state["opt"].opt_state = sd["opt_state"]

    manager = make_manager(
        save, load, replica_group, restore_sharding=sharding_restorer(save)
    )
    ftmesh.manager = manager

    params = ftmesh.shard_params(init_params(jax.random.PRNGKey(7), cfg), param_axes(cfg))
    state["opt"] = Optimizer(manager, optax.sgd(args.lr), params)
    averager = GradientAverager(manager)

    # Durable SHARDED checkpoints: the disk format records NamedShardings,
    # and restore places every leaf back onto this group's own
    # (fsdp x tensor) mesh via the live tree's shardings — cold-start
    # resume for a whole HSDP job, where peer healing has no live peer.
    ckpt = None
    if args.ckpt_dir:
        from torchft_tpu.checkpointing import ManagedDiskCheckpoint

        ckpt = ManagedDiskCheckpoint(
            manager, save, load,
            os.path.join(args.ckpt_dir, f"group_{replica_group}"),
            every=args.ckpt_every,
        )
        ckpt_step = ckpt.restore()
        if ckpt_step is not None:
            print(
                f"[group {replica_group}] resumed from disk checkpoint "
                f"step={ckpt_step}",
                flush=True,
            )

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
            # One sampler, re-seeded per step: a restarted group resumes the
            # same shard permutation at the healed step.
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
            if ckpt is not None:
                ckpt.maybe_save(committed)
            print(
                f"[group {replica_group}] step={step} loss={float(loss):.4f} "
                f"participants={manager.num_participants()} committed={committed}",
                flush=True,
            )

        if not gate.finish(replica_group):
            shardings = {
                path[-1].key if hasattr(path[-1], "key") else str(path[-1]):
                    str(leaf.sharding.spec)
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                    state["opt"].params["layers"]
                )[:2]
            }
            print(
                f"[group {replica_group}] FINAL step={manager.current_step()} "
                f"params_sha256={params_digest(state['opt'].params)} "
                f"sample_shardings={shardings}",
                flush=True,
            )
    finally:
        if ckpt is not None:
            ckpt.shutdown()
        manager.shutdown()


if __name__ == "__main__":
    main()
