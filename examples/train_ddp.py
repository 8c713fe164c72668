"""Fault-tolerant data-parallel training example.

Reference parity: train_ddp.py at the reference root — one process is one
replica group; gradients are averaged across groups through the Manager's
fault-tolerant allreduce; a killed process is restarted by the launcher's
supervisor (torchft_tpu/launch.py), heals live weights from a peer, and
rejoins without stopping the others.

Run (two supervised replica groups + embedded Lighthouse, one command)::

    python -m torchft_tpu.launch --groups 2 -- \
        python examples/train_ddp.py --steps 20

or by hand against an external Lighthouse::

    python -m torchft_tpu.lighthouse_cli --bind [::]:29510 --min_replicas 1 &
    TPUFT_LIGHTHOUSE=localhost:29510 REPLICA_GROUP_ID=0 NUM_REPLICA_GROUPS=2 \
        python examples/train_ddp.py --steps 20 &
    TPUFT_LIGHTHOUSE=localhost:29510 REPLICA_GROUP_ID=1 NUM_REPLICA_GROUPS=2 \
        python examples/train_ddp.py --steps 20

The model is a small conv net on synthetic CIFAR-shaped data (the reference
uses CIFAR-10; synthetic keeps the example hermetic).  At exit each process
prints a params checksum — after any number of mid-run kills, all groups
print the same checksum.
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
    maybe_straggle,
    params_digest,
    prepare_jax_env,
    replica_env,
)


def main() -> None:
    # INFO so the manager's lifecycle lines ("healing from replica ...",
    # reconfigures) land in the log — the FT demo's evidence trail.
    logging.basicConfig(level=logging.INFO)
    # SIGUSR1 dumps all thread stacks: `kill -USR1 <pid>` is the first move
    # when a replica looks wedged.
    import faulthandler
    import signal as _signal

    faulthandler.register(_signal.SIGUSR1)
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--batch", type=int, default=32)
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--min_replicas", type=int, default=1)
    parser.add_argument(
        "--ckpt_dir",
        default=os.environ.get("TPUFT_CKPT_DIR", ""),
        help="durable checkpoint directory; empty disables disk checkpoints",
    )
    parser.add_argument("--ckpt_every", type=int, default=10)
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

    prepare_jax_env()

    import jax
    import numpy as np
    import optax

    from torchft_tpu import GradientAverager, Optimizer
    from torchft_tpu.data import DistributedSampler

    # -- model: tiny convnet on 32x32x3 inputs (CIFAR shaped) ----------------
    # Everything here is GROUP-INDEPENDENT, so it runs before the group id
    # resolves: a hot spare (launch --spares) pays params init + the JIT
    # compile while idling, and adoption costs only Manager setup + rejoin.
    from torchft_tpu.models import convnet_loss, init_convnet_params

    init_params = init_convnet_params
    grad_fn = jax.jit(jax.value_and_grad(convnet_loss))
    params0 = init_params(jax.random.PRNGKey(42))

    # Synthetic dataset, identical in every process (seeded).
    rng = np.random.default_rng(0)
    dataset_x = rng.standard_normal((2048, 32, 32, 3)).astype(np.float32)
    dataset_y = rng.integers(0, 10, size=(2048,)).astype(np.int32)
    # Warm the compiled step (from the shared cache when available).
    jax.block_until_ready(
        grad_fn(params0, dataset_x[: args.batch], dataset_y[: args.batch])[0]
    )

    replica_group, num_groups = replica_env()

    # -- manager wiring ------------------------------------------------------
    state = {}

    def save():
        return {"params": state["opt"].params, "opt_state": state["opt"].opt_state}

    def load(sd):
        state["opt"].params = sd["params"]
        state["opt"].opt_state = sd["opt_state"]

    manager = make_manager(
        save, load, replica_group, min_replicas=args.min_replicas
    )

    state["opt"] = Optimizer(manager, optax.sgd(args.lr), params0)
    averager = GradientAverager(manager)

    # Durable disk checkpoints: peer transports heal a restarted group from
    # a live one, but a cold start (every group gone) would otherwise begin
    # at step 0.
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

    gate = TrainGate(
        manager, args.steps,
        require_merged=args.require_merged_final, steps_cap=args.steps_cap,
    )
    try:
        while gate.should_continue():
            state["opt"].step_begin()
            step = manager.current_step()

            # Shard by the *static* replica group id (reference train_ddp.py
            # does the same): dynamic quorum state would shift every group's
            # shard on each membership change, and a healing group
            # (participating_rank None) would alias group 0's shard.
            sampler = DistributedSampler(
                len(dataset_x),
                replica_group=replica_group,
                num_replica_groups=num_groups,
                shuffle=True,
                seed=step,
            )
            idx = [i for _, i in zip(range(args.batch), iter(sampler))]
            x, y = dataset_x[idx], dataset_y[idx]

            loss, grads = grad_fn(state["opt"].params, x, y)
            # Straggler-bench injection point (no-op outside the scenario):
            # extra per-step sleep here models slow compute on this host.
            maybe_straggle(replica_group)
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
            print(f"[group {replica_group}] FINAL step={manager.current_step()} "
                  f"params_sha256={params_digest(state['opt'].params)}", flush=True)
    finally:
        if ckpt is not None:
            ckpt.shutdown()
        manager.shutdown()


if __name__ == "__main__":
    main()
