"""TrainStep: the compiled training step over an FTMesh.

One object owns the pjit-compiled compute for a step:

  - ``full_step``: loss -> grad -> optax update, one XLA program (used when
    there is no cross-group dimension, and by the multichip dry run);
  - ``grads``/``apply``: the split form for fault-tolerant training — the
    gradient program ends at (loss, grads) so the Manager's host-level
    replica allreduce (DCN) can run between compute and update, exactly
    where the reference's DDP comm hook sits in the backward
    (torchft/ddp.py:47-71, torchft/manager.py:262-323).

All intra-group parallelism (data/fsdp/tensor/sequence) is carried by the
arrays' shardings + the model's with_sharding_constraint annotations; XLA
inserts the ICI collectives.  Donation keeps params/opt_state in place in
HBM across steps.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Optional

import jax

from torchft_tpu.parallel.mesh import FTMesh

logger = logging.getLogger(__name__)

# Fraction of the remaining HBM the speculative apply may claim; the rest
# is headroom for XLA temporaries inside the update program.
_SPECULATION_HEADROOM = 0.9


def tree_device_bytes(tree: Any) -> int:
    """PER-DEVICE resident bytes of a pytree of (possibly sharded) arrays.

    A sharded leaf costs each device only its shard; a replicated leaf
    costs every device the full array.  Using global sizes here would
    overestimate the speculative-apply cost by the shard factor on
    FSDP-style meshes and wrongly disable the overlap."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
        if itemsize is None:
            continue
        shape = getattr(leaf, "shape", ())
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            try:
                shape = sharding.shard_shape(shape)
            except Exception:  # noqa: BLE001
                pass
        count = 1
        for dim in shape:
            count *= int(dim)
        total += count * int(itemsize)
    return total


def speculation_fits(extra_bytes: int, device: Any, floor: int = 0) -> Optional[bool]:
    """Whether an extra `extra_bytes` fits the device's free HBM.

    Budgets against the step's HIGH-WATER mark, not the current
    bytes_in_use: under async dispatch the speculative apply is enqueued
    while the gradient program may still hold its whole footprint, so the
    two coexist.  The mark is the largest of bytes_in_use, the allocator's
    peak (when reported) and `floor` — the caller's own lower bound, e.g.
    the compiler's footprint of the step's programs: on a v5e the
    allocator's peak read ~1 GB under what the 1B-width gradient program
    holds (CHANGES.md PR 21), which green-lit a speculative apply that then
    could not be allocated.  Returns None when the runtime exposes no
    memory statistics (CPU devices) — the caller decides the default."""
    stats = device.memory_stats()
    if not stats:
        return None
    limit = stats.get("bytes_limit")
    in_use = stats.get("bytes_in_use")
    if limit is None or in_use is None:
        return None
    high_water = max(in_use, stats.get("peak_bytes_in_use") or 0, floor)
    return extra_bytes <= (limit - high_water) * _SPECULATION_HEADROOM


@dataclasses.dataclass
class TrainStep:
    """Compiled train step.

    Args:
        ftmesh: mesh + rules (+ optional manager for the replica dim).
        tx: optax GradientTransformation.
        loss_fn: (params, batch) -> scalar loss (model closure); with
            ``loss_has_counters`` it returns ``(loss, counters)``, counters
            a dict of small arrays the model counted inside the gradient
            program (tokens per expert, say).  They leave the program
            beside the loss: ``last_counters`` holds the newest (on the
            device), and ``ft_step`` lands them in the Manager's
            ``step_summary`` — a scalar under its name, an array as
            ``<name>_max`` and ``<name>_mean``, a vector of at most 16
            entries whole under its name too — one step late, with
            ``counters_step`` naming the step they were counted in: by then
            they are on the host and the hand-over waits for nothing.  A
            loss without counters compiles to the program it always did.
        bucket_bytes: DCN bucket size for the cross-group averaging path.
        overlap_commit: hide the commit-vote RPC behind a speculatively
            dispatched update (see ft_step).  MEMORY TRADE: the speculative
            apply cannot donate its inputs, so params+opt_state residency
            transiently doubles during the update.  Default None = run the
            FIRST ft_step non-overlapped, then decide from the device's
            post-step memory stats (allocator peak, so the measurement
            includes the step's activation/workspace footprint): overlap
            iff an extra params+opt_state copy fits above the observed
            peak with 10% headroom; when the runtime exposes no
            memory statistics the overlap is kept (its failure mode — an
            allocator OOM — is loud, while silently serializing the vote
            would be an invisible perf cliff).  Pass True/False to force.
    """

    ftmesh: FTMesh
    tx: Any
    # Exactly one of loss_fn / value_and_grad_fn must be provided:
    # value_and_grad_fn replaces jax.value_and_grad(loss_fn) for losses
    # that compute their own backward, e.g. the 1F1B pipeline schedule
    # (parallel.pipeline.pipeline_1f1b_value_and_grad).
    loss_fn: Optional[Callable[[Any, Any], jax.Array]] = None
    bucket_bytes: int = 25 << 20
    overlap_commit: Optional[bool] = None
    value_and_grad_fn: Optional[Callable[[Any, Any], Any]] = None
    loss_has_counters: bool = False

    def __post_init__(self) -> None:
        if (self.loss_fn is None) == (self.value_and_grad_fn is None):
            raise ValueError(
                "TrainStep needs exactly one of loss_fn / value_and_grad_fn"
            )
        if self.loss_has_counters and self.loss_fn is None:
            raise ValueError("loss_has_counters goes with loss_fn")
        mesh = self.ftmesh.mesh

        def value_and_grad(params, batch):
            self._traced("value_and_grad")
            # Shardings are explicit NamedShardings; the abstract mesh is
            # set only so the kernel gate (ops/_pallas_util.kernels_apply)
            # sees the mesh this program is traced for even when the loss
            # closure does not pass it down.
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                if self.value_and_grad_fn is not None:
                    return self.value_and_grad_fn(params, batch)
                if self.loss_has_counters:
                    # ((loss, counters), grads)
                    return jax.value_and_grad(self.loss_fn, has_aux=True)(params, batch)
                return jax.value_and_grad(self.loss_fn)(params, batch)

        def apply(params, opt_state, grads):
            import optax

            self._traced("apply")
            updates, opt_state = self.tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        def full(params, opt_state, batch):
            self._traced("full")  # first: the stage keeps the name the outermost function gives it
            loss, grads = value_and_grad(params, batch)
            if self.loss_has_counters:
                loss = loss[0]
            params, opt_state = apply(params, opt_state, grads)
            return params, opt_state, loss

        self._grads_fn = jax.jit(value_and_grad)
        self._apply_fn = jax.jit(apply, donate_argnums=(0, 1))
        # Speculative variant for the overlapped commit path: the old
        # params/opt_state must survive a failed vote, so nothing is donated
        # (transiently doubles params+opt residency — disable overlap_commit
        # if that doesn't fit).
        self._apply_spec_fn = jax.jit(apply)
        self._full_fn = jax.jit(full, donate_argnums=(0, 1))
        self._averager = None  # lazy: the manager may be attached post-init
        self._overlap_resolved: Optional[bool] = self.overlap_commit
        # The newest gradient program's counters (device arrays), and the
        # Manager step ft_step dispatched it in (None: the split form).
        self.last_counters: Any = None
        self._counters_step: Optional[int] = None
        # What `op_map` lowers again: per traced function the jitted program
        # that ran and its arguments' shapes, types and placements, noted on
        # the call after a trace (`_retraced`) and on no other.
        self._retraced: set = set()
        self._ran: dict = {}
        self._texts: Optional[dict] = None
        from torchft_tpu.obs import builds, opmap

        opmap.register(self)
        builds.register()

    # -- pure compute --------------------------------------------------------

    def init_opt_state(self, params: Any) -> Any:
        return self.tx.init(params)

    def full_step(self, params, opt_state, batch):
        """Fused loss+grad+update; no cross-group averaging."""
        return self._full_fn(params, opt_state, batch)

    def grads(self, params, batch):
        """(loss, grads); a loss with counters leaves them in
        ``last_counters``."""
        return self._loss_and_grads(params, batch)

    def _traced(self, name: str) -> None:
        """Called by the function `name` as its first line, so it runs when
        JAX traces it and never in a step: the next `_note_run` keeps the
        call's arguments, and the build's records (obs/builds.py) carry the
        program's name — `jit_<name>`, as `op_map` and a profile have it."""
        from torchft_tpu.obs import builds

        self._retraced.add(name)
        builds.tag("jit_" + name)

    def _note_run(self, fn, *args) -> None:
        """After a call of the jitted `fn`: where it was traced anew since
        the last note, keeps what `op_map` needs to lower it again (a donated
        argument still knows its shape, type and placement)."""
        if fn.__name__ in self._retraced:
            self._retraced.discard(fn.__name__)
            self._texts = None
            leaves, treedef = jax.tree.flatten(args)
            self._ran[fn.__name__] = (fn, treedef, [
                # an uncommitted array compiles as an argument without a placement
                (x.shape, x.dtype, x.sharding if getattr(x, "committed", False) else None) for x in leaves])

    def op_map(self, detail: bool = False) -> dict:
        """{program: {instruction name: op_name}} of the gradient and the
        update program as they last ran (`jit_value_and_grad`, `jit_apply`:
        the names a profile's `XLA Modules` line gives their executions), read
        from the compiled executables' text (`obs.opmap.op_names`; `detail`
        as there).  A device operation of a profile is named by its
        instruction, and the op_name says which part of the model it came
        from and in which direction (`obs.opmap.part_of`, `direction_of`).
        Lowers and compiles again — from the jit and compile caches — so it
        is for after the steps of interest, never inside one."""
        from torchft_tpu.obs import opmap

        return {name: opmap.op_names(text, detail=detail) for name, text in self.compiled_texts().items()}

    def compiled_texts(self) -> dict:
        """{program: the compiled executable's text} of the programs as they
        last ran: every instruction with its metadata, and a pallas kernel's
        custom call with its grid (`iteration_bounds` in the body) and its
        operands' shapes.  Lowers and compiles again — from the jit and
        compile caches — once for a set of programs, so it is for after the
        steps of interest, never inside one."""
        from torchft_tpu.obs import opmap

        if self._texts is None:
            self._texts = {}
            for fn, treedef, leaves in self._ran.values():
                args = jax.tree.unflatten(treedef, [
                    jax.ShapeDtypeStruct(shape, dtype, sharding=sharding) for shape, dtype, sharding in leaves])
                text = fn.lower(*args).compile().as_text()
                self._texts[opmap.module_name(text)] = text
        return self._texts

    def _loss_and_grads(self, params, batch, step: Optional[int] = None):
        out, grads = self._grads_fn(params, batch)
        self._note_run(self._grads_fn, params, batch)
        if not self.loss_has_counters:
            return out, grads
        loss, self.last_counters = out
        self._counters_step = step
        for leaf in jax.tree.leaves(self.last_counters):
            leaf.copy_to_host_async()  # on the host by the time the next step asks
        return loss, grads

    def _note_counters(self, manager, step: int) -> None:
        """Lands the last ft_step's counters in the step_summary of the step
        in flight (``Manager.note_summary_fields``)."""
        if self._counters_step is None:  # no counters, or only the split form ran
            return
        import numpy as np

        with manager.spans.sub("counters_note", step=step):
            fields: dict = {"counters_step": self._counters_step}
            for name, value in self.last_counters.items():
                value = np.asarray(value)
                if value.ndim == 0:
                    fields[name] = value.item()
                else:
                    fields[name + "_max"] = value.max().item()
                    fields[name + "_mean"] = float(value.mean())
                    if value.ndim == 1 and value.size <= 16:  # a number a pass of a looped model: whole, too
                        fields[name] = value.tolist()
            manager.note_summary_fields(**fields)
        self._counters_step = None

    def lower_grads(self, params, batch):
        """The gradient program, lowered for these arguments (arrays or
        ``jax.ShapeDtypeStruct``s with shardings) and not run: ``.compile()``
        gives ``as_text()`` (is a kernel in it?) and ``memory_analysis()``."""
        return self._grads_fn.lower(params, batch)

    def apply(self, params, opt_state, grads):
        return self._apply(self._apply_fn, params, opt_state, grads)

    def _apply(self, fn, params, opt_state, grads):
        out = fn(params, opt_state, grads)
        self._note_run(fn, params, opt_state, grads)
        return out

    # -- fault-tolerant step -------------------------------------------------

    @property
    def overlap_resolved(self) -> Optional[bool]:
        """Which way ``overlap_commit`` went: the forced value, or — for the
        default None — what the first committed ``ft_step`` decided from the
        device's memory statistics (None until then)."""
        return self._overlap_resolved

    def _resolve_overlap(self, params: Any, opt_state: Any, batch: Any) -> None:
        """Decide overlap_commit from post-step device memory stats and the
        compiler's footprint of the gradient program that just ran."""
        opt_bytes = tree_device_bytes(opt_state)
        extra = tree_device_bytes(params) + opt_bytes
        device = None
        for leaf in jax.tree.leaves(params):
            devs = getattr(leaf, "devices", None)
            if callable(devs):
                ds = devs()
                if ds:
                    device = next(iter(ds))
                    break
        fits = speculation_fits(extra, device) if device is not None else None
        if fits:
            # The program is in the jit cache, so this neither traces nor
            # compiles again.  Its arguments hold params and batch, its
            # outputs the gradients; whatever else lives on the device
            # (another replica's state, say) is in_use minus our own.
            ma = self._grads_fn.lower(params, batch).compile().memory_analysis()
            if ma is not None:
                other = max(0, device.memory_stats()["bytes_in_use"] - extra)
                floor = (
                    other
                    + opt_bytes
                    + ma.argument_size_in_bytes
                    + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes
                )
                fits = speculation_fits(extra, device, floor)
        self._overlap_resolved = True if fits is None else fits
        logger.info(
            "overlap_commit auto: %s (extra %.2f GB for the speculative "
            "apply, post-step device stats %s)",
            self._overlap_resolved,
            extra / 1e9,
            "unavailable" if fits is None else "available",
        )

    def ft_step(self, params, opt_state, batch):
        """One FT step: local grads -> Manager DCN allreduce -> commit-gated
        update.  Returns (params, opt_state, loss, committed).

        Requires ftmesh.manager.  The caller must have called
        manager.start_quorum() (the Optimizer wrapper's step_begin does).

        State-ownership note: a HEALED step delivers weights through the
        Manager's load_state_dict callback, not through this function's
        return value — the (params, opt_state) returned on a step where the
        manager healed are computed from the pre-heal inputs.  Loops that
        enable healing should hold state behind the Manager's state-dict
        callbacks and re-read it after such a step (the Optimizer wrapper's
        pattern; see examples/train_hsdp.py), or run ft_step only on
        up-to-date groups.

        The commit vote (a host RPC barrier across the group's local ranks,
        reference torchft/manager.py:587-663) is hidden behind device work:
        the update is dispatched *speculatively* before the vote — XLA async
        dispatch returns immediately and the device crunches the apply while
        the host blocks in ``should_commit`` — and the new state is adopted
        only when the vote passes.  The reference hides its quorum under
        backward the same way (torchft/manager.py:420); votes are rare-fail,
        so speculation wastes work only on genuinely broken steps.
        """
        manager = self.ftmesh.manager
        assert manager is not None, "ft_step requires an FTMesh with a Manager"
        from torchft_tpu.ddp import GradientAverager

        if self._averager is None or self._averager.manager is not manager:
            self._averager = GradientAverager(manager, self.bucket_bytes)

        # overlap_commit=None: the FIRST step runs non-overlapped, and the
        # decision is made from the device's memory stats AFTER it — deciding
        # before any step executed would read a bytes_in_use that excludes
        # the step's activation/workspace footprint and could green-light a
        # speculative apply that OOMs; after one full step the allocator's
        # peak covers compute + resident state.
        resolve_after = self._overlap_resolved is None

        # The frame and the host time of the two dispatches, as sub-spans of
        # the Manager's tracker (obs/spans.SUBSPANS): `speculative` says
        # which update program this step dispatched.
        spans, step = manager.spans, manager.current_step()
        with spans.sub(
            "ft_step", step=step, speculative=bool(self._overlap_resolved)
        ) as frame:
            self._note_counters(manager, step)
            with spans.sub("grads_dispatch", step=step):
                loss, grads = self._loss_and_grads(params, batch, step)
            grads = self._averager.allreduce(grads)
            if self._overlap_resolved:
                with spans.sub("apply_dispatch", step=step):
                    new_params, new_opt = self._apply(
                        self._apply_spec_fn, params, opt_state, grads
                    )
                committed = frame.fields["committed"] = manager.should_commit()
                if committed:
                    return new_params, new_opt, loss, True
                return params, opt_state, loss, False
            committed = frame.fields["committed"] = manager.should_commit()
            if committed:
                with spans.sub("apply_dispatch", step=step):
                    params, opt_state = self._apply(self._apply_fn, params, opt_state, grads)
            # Only a COMMITTED step resolves the decision: an aborted vote
            # means _apply_fn never ran, so the allocator peak would exclude
            # the optimizer-apply footprint the budget must cover.
            if resolve_after and committed:
                jax.block_until_ready(jax.tree.leaves(params))
                self._resolve_overlap(params, opt_state, batch)
            return params, opt_state, loss, committed
