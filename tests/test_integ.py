"""End-to-end fault-tolerance integration tests (the v0 milestone slice).

Reference parity: torchft/manager_integ_test.py:239-462 — replica groups run
as threads against a real native Lighthouse + per-group Manager servers, with
gradients averaged through manager.allreduce and commit-gated optax updates.
Tests assert replicas converge to bitwise-identical parameters after healthy
runs and after injected mid-run failures (healing via HTTPTransport), and
that quorum timeouts surface quickly.
"""

import logging
import threading
import time
from datetime import timedelta
from typing import Any, Dict

import numpy as np
import pytest

from torchft_tpu._native import LighthouseServer
from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.collectives import TCPCollective
from torchft_tpu.ddp import GradientAverager
from torchft_tpu.manager import Manager
from torchft_tpu.optim import Optimizer

from harness import FailureInjector, Runner, run_replicas

logging.basicConfig(level=logging.INFO)


def _init_params():
    import jax.numpy as jnp

    return {
        "w1": jnp.full((4, 8), 0.1, dtype=jnp.float32),
        "b1": jnp.zeros((8,), dtype=jnp.float32),
        "w2": jnp.full((8, 2), -0.05, dtype=jnp.float32),
    }


def _batch(step: int, replica_rank: int):
    """Deterministic per-(step, participating-rank) synthetic batch."""
    rng = np.random.default_rng(1000 * step + replica_rank)
    x = rng.standard_normal((16, 4)).astype(np.float32)
    y = rng.standard_normal((16, 2)).astype(np.float32)
    return x, y


def _loss_fn(params, x, y):
    import jax.numpy as jnp

    h = jnp.tanh(x @ params["w1"] + params["b1"])
    pred = h @ params["w2"]
    return jnp.mean((pred - y) ** 2)


def ddp_train_loop(runner: Runner, rank: int) -> Dict[str, Any]:
    """One replica group's train loop (reference:
    torchft/manager_integ_test.py:157-237 train_loop)."""
    import jax
    import optax

    total_steps = runner.train_loop_args.get("total_steps", 6)
    use_async_quorum = runner.train_loop_args.get("use_async_quorum", True)

    collective = TCPCollective(timeout=20.0)
    transport = HTTPTransport(timeout=20.0)

    state: Dict[str, Any] = {}

    def save():
        return {"params": state["opt"].params, "opt_state": state["opt"].opt_state}

    def load(sd):
        state["opt"].params = sd["params"]
        state["opt"].opt_state = sd["opt_state"]

    manager = Manager(
        collective=collective,
        load_state_dict=load,
        state_dict=save,
        min_replica_size=1,
        use_async_quorum=use_async_quorum,
        timeout=timedelta(seconds=20),
        quorum_timeout=timedelta(seconds=20),
        rank=0,
        world_size=1,
        replica_id=str(runner.replica_id),
        lighthouse_addr=runner.lighthouse_address,
        checkpoint_transport=transport,
    )
    state["opt"] = Optimizer(manager, optax.sgd(0.05), _init_params())
    averager = GradientAverager(manager)
    grad_fn = jax.jit(jax.grad(_loss_fn))

    # Optional scale-up-test knobs: ``keep_going`` keeps this group training
    # past its target until the event is set (a finished group that merely
    # heartbeats would starve a late joiner's collectives — real jobs train
    # indefinitely, so the window never closes there);
    # ``extra_steps_after_join`` makes the target RELATIVE to wherever this
    # group lands after its first quorum/heal (a late joiner cannot know the
    # leader's step in advance).  The first step seen and the max
    # participant count observed are reported as evidence.
    keep_going = runner.train_loop_args.get("keep_going")
    extra_after_join = runner.train_loop_args.get("extra_steps_after_join")
    progress_event = runner.train_loop_args.get("progress_event")
    first_observed_step = None
    max_participants = 0
    target = None if extra_after_join is not None else total_steps

    try:
        while (
            target is None
            or manager.current_step() < target
            or (keep_going is not None and not keep_going.is_set())
        ):
            state["opt"].step_begin()
            step = manager.current_step()
            rrank = manager.participating_rank() or 0
            x, y = _batch(step, rrank)
            grads = grad_fn(state["opt"].params, x, y)
            grads = averager.allreduce(grads)
            committed = state["opt"].step(grads)
            if committed and first_observed_step is None:
                # Latched only on a COMMITTED step (a transient first-step
                # fault must not poison the relative target), read
                # post-commit: with async quorum the heal fast-forward only
                # lands by should_commit, so the pre-step counter still
                # shows 0 on a healing joiner's first iteration.
                first_observed_step = manager.current_step()
                if target is None:
                    target = first_observed_step + extra_after_join - 1
            if progress_event is not None and manager.current_step() >= 3:
                progress_event.set()
            max_participants = max(max_participants, manager.num_participants())
            runner.failure_injector.check(runner.replica_id, manager.current_step())
        # Keep serving heals until every group is done: a replica that exits
        # early would strand a healing peer (its manager stops answering).
        barrier = runner.train_loop_args.get("barrier")
        if barrier is not None:
            barrier.wait(timeout=60)
        return {
            "params": {k: np.asarray(v) for k, v in state["opt"].params.items()},
            "step": manager.current_step(),
            "batches_committed": manager.batches_committed(),
            "first_observed_step": first_observed_step,
            "max_participants": max_participants,
        }
    finally:
        manager.shutdown()


def multi_rank_train_loop(runner: Runner, rank: int, store_addr: str) -> Dict[str, Any]:
    """One local rank of a world_size>1 replica group.  Both local ranks see
    the same batch (TP-style: in-group gradients are replicated), so every
    rank of every group must end bitwise-identical — while exercising the
    ManagerServer's world_size barriers: quorum aggregation across local
    ranks, the all-ranks commit vote, and rank-striped heal metadata
    (reference: test_ddp_recovery_multi_rank,
    torchft/manager_integ_test.py:375-417)."""
    import jax
    import optax

    total_steps = runner.train_loop_args.get("total_steps", 6)

    collective = TCPCollective(timeout=20.0)
    transport = HTTPTransport(timeout=20.0)
    state: Dict[str, Any] = {}

    def save():
        return {"params": state["opt"].params, "opt_state": state["opt"].opt_state}

    def load(sd):
        state["opt"].params = sd["params"]
        state["opt"].opt_state = sd["opt_state"]

    manager = Manager(
        collective=collective,
        load_state_dict=load,
        state_dict=save,
        min_replica_size=1,
        timeout=timedelta(seconds=20),
        quorum_timeout=timedelta(seconds=20),
        rank=rank,
        world_size=runner.world_size,
        external_store_addr=store_addr,
        replica_id=str(runner.replica_id),
        lighthouse_addr=runner.lighthouse_address,
        checkpoint_transport=transport,
    )
    state["opt"] = Optimizer(manager, optax.sgd(0.05), _init_params())
    averager = GradientAverager(manager)
    grad_fn = jax.jit(jax.grad(_loss_fn))

    try:
        while manager.current_step() < total_steps:
            state["opt"].step_begin()
            step = manager.current_step()
            rrank = manager.participating_rank() or 0
            x, y = _batch(step, rrank)
            grads = grad_fn(state["opt"].params, x, y)
            grads = averager.allreduce(grads)
            state["opt"].step(grads)
            # Keyed by LOCAL rank: a multi-rank group must fail every rank at
            # the same step so the whole group dies as a unit (the reference
            # scripts .fail_at(0, s).fail_at(1, s) likewise).
            runner.failure_injector.check(rank, manager.current_step())
        barrier = runner.train_loop_args.get("barrier")
        if barrier is not None:
            barrier.wait(timeout=60)
        return {
            "params": {k: np.asarray(v) for k, v in state["opt"].params.items()},
            "step": manager.current_step(),
            "rank": rank,
        }
    finally:
        manager.shutdown()


class _DoneBarrier:
    """Barrier that only waits for *finishing* participants: restarted
    replicas re-register, so parties is dynamic."""

    def __init__(self, parties: int) -> None:
        self._parties = parties
        self._done = 0
        self._cond = threading.Condition()

    def wait(self, timeout: float = 60) -> None:
        with self._cond:
            self._done += 1
            self._cond.notify_all()
            deadline = time.monotonic() + timeout
            while self._done < self._parties:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return
                self._cond.wait(timeout=remaining)


@pytest.fixture
def lighthouse():
    lh = LighthouseServer(bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=100)
    yield lh
    lh.shutdown()


def _make_runners(lighthouse, injectors, total_steps=6, **kwargs):
    barrier = _DoneBarrier(len(injectors))
    return [
        Runner(
            replica_id=i,
            lighthouse_address=lighthouse.address(),
            failure_injector=inj,
            train_loop=ddp_train_loop,
            num_replicas=len(injectors),
            train_loop_args={"total_steps": total_steps, "barrier": barrier, **kwargs},
        )
        for i, inj in enumerate(injectors)
    ]


def _assert_params_equal(results) -> None:
    base = results[0][0]["params"]
    for res in results[1:]:
        for k in base:
            np.testing.assert_array_equal(base[k], res[0]["params"][k])


def test_ddp_healthy(lighthouse) -> None:
    """Two healthy replicas train in lockstep and end bitwise-identical
    (reference: test_ddp_healthy, torchft/manager_integ_test.py:239-263)."""
    runners = _make_runners(lighthouse, [FailureInjector(), FailureInjector()])
    results = run_replicas(runners)
    assert all(r[0]["step"] >= 6 for r in results)
    _assert_params_equal(results)


@pytest.mark.parametrize("use_async_quorum", [True, False])
def test_ddp_recovery(lighthouse, use_async_quorum, caplog) -> None:
    """One replica dies mid-run, restarts, heals from the survivor, and both
    converge bitwise (reference: test_ddp_recovery,
    torchft/manager_integ_test.py:281-321)."""
    import logging

    injector = FailureInjector().fail_at(1, 3)
    runners = _make_runners(
        lighthouse,
        [FailureInjector(), injector],
        total_steps=7,
        use_async_quorum=use_async_quorum,
    )
    with caplog.at_level(logging.INFO, logger="torchft_tpu.manager"):
        results = run_replicas(runners)
    assert injector.count == 1
    _assert_params_equal(results)
    assert all(r[0]["step"] >= 7 for r in results)
    # Process-level drives (tests/test_examples_killed.py, the verify recipe)
    # grep a group's log for this exact phrase to see that the heal path ran.
    assert any("healing from replica" in m for m in caplog.messages)


def test_ddp_recovery_multiple_failures(lighthouse) -> None:
    """Both replicas fail at different steps; every failure heals
    (reference: test_ddp_recovery_multi_rank, torchft/manager_integ_test.py:323-360)."""
    inj0 = FailureInjector().fail_at(0, 2)
    inj1 = FailureInjector().fail_at(1, 4)
    runners = _make_runners(lighthouse, [inj0, inj1], total_steps=8)
    results = run_replicas(runners)
    assert inj0.count == 1 and inj1.count == 1
    _assert_params_equal(results)


def test_ddp_simultaneous_failure_both_groups(lighthouse) -> None:
    """TOTAL failure: both groups die at the same step, so no live peer
    holds newer state and no heal is possible.  The restarts must re-form
    a quorum from scratch without deadlocking on stale rendezvous state
    (uuid-suffixed replica ids keep the restarted incarnations distinct),
    whichever group restarts first trains ahead alone, the second heals
    from it, and the job converges bitwise again."""
    inj0 = FailureInjector().fail_at(0, 3)
    inj1 = FailureInjector().fail_at(1, 3)
    runners = _make_runners(lighthouse, [inj0, inj1], total_steps=8)
    results = run_replicas(runners)
    assert inj0.count == 1 and inj1.count == 1
    _assert_params_equal(results)


def _make_multi_rank_runners(lighthouse, injectors, world_size=2, total_steps=6):
    barrier = _DoneBarrier(len(injectors) * world_size)
    return [
        Runner(
            replica_id=i,
            lighthouse_address=lighthouse.address(),
            failure_injector=inj,
            train_loop=multi_rank_train_loop,
            num_replicas=len(injectors),
            world_size=world_size,
            train_loop_args={"total_steps": total_steps, "barrier": barrier},
        )
        for i, inj in enumerate(injectors)
    ]


def _assert_all_rank_params_equal(results) -> None:
    base = results[0][0]["params"]
    for group in results:
        for rank_result in group:
            for k in base:
                np.testing.assert_array_equal(base[k], rank_result["params"][k])


def test_multi_rank_healthy(lighthouse) -> None:
    """2 groups x 2 local ranks: quorum aggregation and the commit vote wait
    for every local rank; all four rank states end bitwise-identical."""
    runners = _make_multi_rank_runners(lighthouse, [FailureInjector(), FailureInjector()])
    results = run_replicas(runners)
    assert all(len(group) == 2 for group in results)
    assert all(r["step"] >= 6 for group in results for r in group)
    _assert_all_rank_params_equal(results)


def test_multi_rank_recovery(lighthouse) -> None:
    """A 2-rank group dies as a unit mid-run, restarts, and both its ranks
    heal from the survivor's matching ranks (rank-striped recovery); all four
    rank states converge bitwise (reference: test_ddp_recovery_multi_rank,
    torchft/manager_integ_test.py:375-417)."""
    injector = FailureInjector().fail_at(0, 3).fail_at(1, 3)
    runners = _make_multi_rank_runners(
        lighthouse, [FailureInjector(), injector], total_steps=7
    )
    results = run_replicas(runners)
    assert injector.count == 2
    assert all(r["step"] >= 7 for group in results for r in group)
    _assert_all_rank_params_equal(results)


def test_elastic_scale_up_late_joiner() -> None:
    """A BRAND-NEW group (not a restart) joins a running quorum mid-train:
    the quorum grows, the joiner heals the leader's live state from behind
    and trains merged to the target (the elasticity half of the reference's
    membership model — the recovery tests only cover rejoin-after-kill).

    The leader trains until the joiner is done (keep_going): a finished
    group that merely heartbeats stays in the quorum and would starve the
    joiner's collectives — real jobs train indefinitely, so the merged
    window never closes there."""
    lh = LighthouseServer(bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200)
    try:
        total = 12
        joiner_done = threading.Event()

        def make_runner(rid: int, args: Dict[str, Any]) -> Runner:
            return Runner(
                replica_id=rid,
                lighthouse_address=lh.address(),
                failure_injector=FailureInjector(),
                train_loop=ddp_train_loop,
                num_replicas=2,
                train_loop_args=args,
            )

        results: Dict[int, Any] = {}
        errors: List[BaseException] = []

        def run(rid: int, args: Dict[str, Any]) -> None:
            try:
                results[rid] = make_runner(rid, args).run_replica()[0]
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
            finally:
                if rid == 1:
                    joiner_done.set()  # never strand the leader

        leader_progressed = threading.Event()
        t0 = threading.Thread(
            target=run,
            args=(0, {
                "total_steps": total,
                "keep_going": joiner_done,
                "progress_event": leader_progressed,
            }),
        )
        t0.start()
        # The newcomer must not exist until the leader has real progress
        # (polling, not a fixed sleep — first-compile time varies with load).
        assert leader_progressed.wait(timeout=60), "leader never reached step 3"
        # The joiner's target is relative: heal to wherever the free-running
        # leader is, then train `total` MERGED steps.
        t1 = threading.Thread(
            target=run, args=(1, {"extra_steps_after_join": total})
        )
        t1.start()
        t1.join(timeout=120)
        if t1.is_alive():
            joiner_done.set()  # release the leader even on a wedged joiner
        t0.join(timeout=120)
        assert not t1.is_alive() and not t0.is_alive(), "threads still running"
        assert not errors, errors
        assert sorted(results) == [0, 1]

        joiner = results[1]
        # Scale-up evidence: the joiner healed forward instead of training
        # from step 0 (a from-scratch group's first commit lands at step 1,
        # and the leader was at >= 3 before the joiner existed)...
        assert joiner["first_observed_step"] > 1
        # ...and the window it trained was genuinely MERGED: the leader was
        # present throughout (keep_going), so committed batches accumulate
        # ~2 per step, which a solo run of the same steps cannot reach.
        assert joiner["max_participants"] == 2
        solo_max = joiner["step"] - joiner["first_observed_step"] + 1
        assert joiner["batches_committed"] > solo_max + total // 2
    finally:
        lh.shutdown()


def test_quorum_timeout(lighthouse) -> None:
    """A lone replica (min_replicas=2) times out quickly rather than hanging
    (reference: test_quorum_timeout, torchft/manager_integ_test.py:419-462)."""
    collective = TCPCollective(timeout=5.0)
    manager = Manager(
        collective=collective,
        load_state_dict=lambda sd: None,
        state_dict=lambda: {},
        min_replica_size=2,
        use_async_quorum=False,
        quorum_timeout=timedelta(seconds=1),
        rank=0,
        world_size=1,
        replica_id="lonely",
        lighthouse_addr=lighthouse.address(),
    )
    try:
        t0 = time.monotonic()
        manager.start_quorum()  # sync: waits, fails, latches
        assert manager.errored() is not None
        assert time.monotonic() - t0 < 5.0
    finally:
        manager.shutdown()
