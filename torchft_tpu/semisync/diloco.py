"""Streaming semi-sync DiLoCo: fragment-synced outer rounds that overlap
inner steps.

The blocking DiLoCo port synchronized like DDP: at every ``sync_every``-th
inner step the whole pytree was hosted, pushed through a synchronous
allreduce, and the train loop stalled for the full cross-region
round-trip.  This class is the WAN-native rebuild (DiLoCo,
arXiv:2311.08105; Streaming DiLoCo, arXiv:2501.18512):

  - the outer state is fragmented on the shared bucket planner
    (semisync/fragments.py — ``ddp.plan_buckets`` underneath);
  - each round's quorum is started at the ROUND boundary (sync quorum:
    a healing group has the committed weights before any pseudogradient);
  - each fragment's pseudogradient round — codec encode (int8+EF / bf16 /
    f32, semisync/codec.py) then a quorum-scoped reduce-scatter+allgather
    over the striped multi-lane ring (``ring2d`` at high group counts) —
    runs on the engine's background worker at a staggered inner-step slot,
    so wire time hides behind the remaining inner compute;
  - the per-fragment outer optimizer (one optax state per fragment) is
    applied ONLY after the round's commit vote passes, so a failed sync
    never corrupts the model, the backup, or the outer state — and the
    backup + outer states travel with heals through the same
    ``register_state_dict_fn`` channel the blocking port used;
  - with a ``set_fragment_params`` hook, a committed fragment's outer
    step lands on device the moment it is computed (device transfer
    overlapping the next fragment's outer math) instead of the whole
    tree re-landing at the round boundary.

``torchft_tpu.local_sgd.DiLoCo`` remains as a thin wrapper (stream=False,
codec="auto"): the old API and blocking semantics, now running on this
engine.

The ``tpuft_semisync_*`` series are a section of the worker's ``/metrics``
(``TPUFT_WORKER_METRICS_PORT``, obs/prom.py).
"""

from __future__ import annotations

from types import TracebackType
from typing import Any, Callable, Dict, List, Optional, Type

import numpy as np

from torchft_tpu.semisync.codec import CODECS, make_codec
from torchft_tpu.semisync.engine import SyncEngine
from torchft_tpu.semisync.fragments import DEFAULT_FRAGMENT_BYTES, FragmentPlan
from torchft_tpu.semisync.metrics import SemiSyncMetrics

__all__ = ["StreamingDiLoCo"]


class StreamingDiLoCo:
    """Fragment-streamed DiLoCo (see module docstring).

    Usage matches the blocking port::

        with StreamingDiLoCo(manager, get_params, set_params,
                             outer_tx=optax.sgd(0.7, momentum=0.9,
                                                nesterov=True),
                             sync_every=100) as diloco:
            for batch in data:
                params = inner_update(params, batch)
                diloco.step()        # counts, streams fragments, maybe syncs

    Requires synchronous quorum (``use_async_quorum=False``) exactly like
    the blocking port: a healing group must hold the committed weights
    before computing its pseudogradient.
    """

    def __init__(
        self,
        manager,
        get_params: Callable[[], Any],
        set_params: Callable[[Any], None],
        outer_tx: Any,
        sync_every: int,
        fragment_bytes: int = DEFAULT_FRAGMENT_BYTES,
        codec: str = "int8",
        stream: bool = True,
        outer_scope: str = "fragment",
        state_dict_key: str = "diloco",
        set_fragment_params: Optional[
            Callable[[List[int], List[np.ndarray]], None]
        ] = None,
        fragment_commit: bool = False,
    ) -> None:
        """``outer_scope``: "fragment" (default) keeps one optax state per
        fragment and applies the outer update fragment-locally — the
        Streaming DiLoCo shape, required so fragments can eventually apply
        independently.  "tree" runs ONE outer_tx over the whole
        pseudogradient tree at the round boundary — the blocking port's
        exact semantics (and its state-dict format), which outer
        transforms with CROSS-LEAF coupling (global-norm clipping) depend
        on; the legacy ``DiLoCo`` wrapper uses this.

        ``set_fragment_params``: optional partial write-back hook,
        ``(leaf_indices, new_leaves) -> None``, landing ONE fragment's
        leaves on device.  When provided (fragment scope only), a
        committed round writes each fragment back the moment its outer
        step is computed — device transfer of fragment ``k`` overlaps the
        outer math of fragment ``k+1``, and the round-boundary whole-tree
        ``set_params`` reset is skipped entirely (it would re-land every
        byte a second time).  Aborted rounds still reset through the
        whole-tree ``set_params`` — inner steps moved ALL leaves, and the
        backup they roll back to predates this round's fragments.

        ``fragment_commit`` (default off): fragment-granular fault
        containment for elastic fleets.  Each fragment's pseudogradient
        round becomes its OWN Manager step — quorum armed at the fragment's issue slot on the
        train thread (heals and elastic reconfiguration stay off the
        worker), the reduce overlaps inner steps as usual, and the vote +
        outer apply land at the NEXT fragment's slot.  A resize or peer
        death mid-round therefore fails exactly one fragment's vote: that
        fragment's backup stands and its live leaves roll back through the
        write-back hook, while every fragment whose vote already passed
        keeps its outer step (the Streaming DiLoCo partial-updates shape)
        — the round-level default would discard the whole round's wire
        traffic.  Costs one quorum + vote per FRAGMENT instead of per
        round; requires ``set_fragment_params`` (fragment scope).  Replica
        consistency is preserved: votes are collective and write-backs
        land at schedule-identical slots, so all groups' live params stay
        bitwise identical."""
        if manager._use_async_quorum:
            raise ValueError(
                "StreamingDiLoCo requires synchronous quorum: construct the "
                "Manager with use_async_quorum=False"
            )
        assert sync_every >= 1, "sync_every must be >= 1"
        self._manager = manager
        self._get_params = get_params
        self._set_params = set_params
        self._outer_tx = outer_tx
        self._sync_every = sync_every
        self._local_step = 0
        self._armed = False
        self._issued: set = set()
        self._arm_attempted = False
        self._round_closed = False
        self._voted = False
        self._vote_passed = False

        # A typo'd codec name must NOT fall back silently: the default is
        # LOSSY, so "fp32" quietly becoming int8 would be the exact encoding
        # the user tried to disable.
        if codec not in CODECS:
            raise ValueError(
                f"unknown semisync codec {codec!r}; expected one of {CODECS}"
            )
        self._codec_name = codec
        self._stream = bool(stream)

        # Host backup of the last-synced params; the flat leaf list is the
        # canonical copy, the tree is derived.  The one jax import here is
        # construction-time, not hot-path.
        import jax

        self._jax = jax
        leaves, self._treedef = jax.tree.flatten(get_params())
        self._leaves: List[np.ndarray] = [
            l if isinstance(l, np.ndarray) else np.asarray(l) for l in leaves
        ]
        metas = [(tuple(l.shape), np.dtype(l.dtype)) for l in self._leaves]
        self._plan = FragmentPlan(metas, fragment_bytes)
        self._schedule = self._plan.schedule(sync_every)

        self._codecs = [
            make_codec(self._codec_name, f) for f in self._plan.fragments
        ]
        for frag, c in zip(self._plan.fragments, self._codecs):
            c.set_backup(frag.pack(self._leaves))

        # One outer optimizer state PER FRAGMENT (a fragment's leaf list is
        # its own optax pytree) in "fragment" scope: the outer update
        # applies fragment-locally after the commit vote, so a
        # partially-failed round can never leave the optimizer state
        # half-advanced.  "tree" scope keeps the blocking port's single
        # whole-tree state.
        if outer_scope not in ("fragment", "tree"):
            raise ValueError(
                f"outer_scope must be 'fragment' or 'tree', got {outer_scope!r}"
            )
        self._outer_scope = outer_scope
        if set_fragment_params is not None and outer_scope != "fragment":
            raise ValueError(
                "set_fragment_params requires outer_scope='fragment' — a "
                "whole-tree outer update has no per-fragment commit moment"
            )
        self._set_fragment_params = set_fragment_params
        self._fragment_commit = bool(fragment_commit)
        if self._fragment_commit and set_fragment_params is None:
            raise ValueError(
                "fragment_commit requires set_fragment_params: a failed "
                "fragment vote rolls back ONLY that fragment's leaves, "
                "which needs the partial write-back hook"
            )
        # Fragment-commit round state: the fragment whose vote is still
        # outstanding, and how many votes failed this round.
        self._pending_fragment = None
        self._round_failed = 0
        self._round_open = False
        self._post_vote = False
        if outer_scope == "fragment":
            self._outer_states: Any = [
                outer_tx.init([self._leaves[i] for i in f.bucket.indices])
                for f in self._plan.fragments
            ]
        else:
            self._outer_states = outer_tx.init(self.backup_params)

        replica_id = ""
        try:
            replica_id = manager.replica_id()
        except Exception:  # noqa: BLE001 — mocked managers
            pass
        self.metrics = SemiSyncMetrics(
            codec=self._codec_name, replica_id=str(replica_id)
        )
        # Unified worker exposition (obs/prom.py): when the Manager runs
        # the worker /metrics endpoint, the tpuft_semisync_* section folds
        # into it instead of opening a second port.
        worker_metrics = getattr(manager, "worker_metrics", None)
        if worker_metrics is not None and getattr(worker_metrics, "serving", False):
            worker_metrics.add_section(self.metrics.render_prometheus)
        self._engine = SyncEngine(
            manager, self._codecs, stream=self._stream, metrics=self.metrics
        )

        # The outer-loop state must travel with the model when a restarted
        # group heals from a peer: a fresh-init backup would make the next
        # sync compute pseudogradients against the wrong base and silently
        # diverge (the divergence mode tests/test_semisync.py pins with a
        # mid-round kill).
        manager.register_state_dict_fn(
            state_dict_key, self._load_outer_state, self._save_outer_state
        )

    # -- context manager ----------------------------------------------------

    def __enter__(self) -> "StreamingDiLoCo":
        return self

    def __exit__(
        self,
        exc_type: Optional[Type[BaseException]],
        exc_value: Optional[BaseException],
        traceback: Optional[TracebackType],
    ) -> bool:
        self._engine.shutdown()
        return False

    # -- introspection ------------------------------------------------------

    @property
    def backup_params(self) -> Any:
        return self._jax.tree.unflatten(self._treedef, list(self._leaves))

    @backup_params.setter
    def backup_params(self, value: Any) -> None:
        leaves, _ = self._jax.tree.flatten(value)
        self._leaves = [
            l if isinstance(l, np.ndarray) else np.asarray(l) for l in leaves
        ]
        self._refresh_codec_backups()

    @property
    def codec_name(self) -> str:
        return self._codec_name

    @property
    def num_fragments(self) -> int:
        return len(self._plan)

    @property
    def plan(self) -> FragmentPlan:
        return self._plan

    def _refresh_codec_backups(self) -> None:
        for frag, c in zip(self._plan.fragments, self._codecs):
            c.set_backup(frag.pack(self._leaves))

    # -- heal-consistency state ---------------------------------------------

    def _save_outer_state(self) -> Any:
        from torchft_tpu.local_sgd import _tree_to_host

        return {
            "backup": self.backup_params,
            "outer_state": _tree_to_host(self._outer_states),
            # Explicit format marker: a heuristic over the state's pytree
            # shape cannot distinguish a whole-tree optax tuple from a
            # per-fragment list reliably (a 2-transform chain state IS a
            # 2-tuple).  Absent key = a legacy (pre-semisync) checkpoint,
            # which was always whole-tree.
            "outer_scope": self._outer_scope,
        }

    def _load_outer_state(self, state: Any) -> None:
        # Validate BEFORE mutating anything: a mismatched format indexed by
        # the other scope's apply path would raise a confusing optax pytree
        # error at the NEXT commit, after the vote already passed — and a
        # half-applied load (new backup, old outer states) must never be
        # left behind.  The raise latches at the heal site, fails every
        # commit until the deployment mismatch is fixed (or max_retries
        # terminates the loop) — degraded-loud, never silently divergent.
        saved_scope = state.get("outer_scope", "tree")
        if saved_scope != self._outer_scope:
            raise ValueError(
                f"diloco state dict carries outer_scope={saved_scope!r} "
                f"outer state but this instance runs "
                f"outer_scope={self._outer_scope!r}; construct with the "
                "matching scope (the legacy DiLoCo wrapper is 'tree') or "
                "re-checkpoint"
            )
        self.backup_params = state["backup"]
        self._outer_states = state["outer_state"]
        # EF residuals are replica-local transmission state, not model
        # state: a healed group starts with clean residuals (its peers'
        # residuals describe THEIR untransmitted remainders).
        for c in self._codecs:
            c.on_abort()

    # -- train-loop API -----------------------------------------------------

    def step(self) -> None:
        """Call after each inner optimizer step.  In stream mode this arms
        the round's quorum at the first inner step and issues fragments at
        their scheduled slots; the final step of the round runs
        :meth:`sync`."""
        if self._fragment_commit:
            self._step_fragment_commit()
            return
        if (
            self._stream
            and not self._armed
            and not self._arm_attempted
            and len(self._plan)
        ):
            # Arm the round before any fragment leaves: sync quorum applies
            # heals eagerly, so every pseudogradient this round is computed
            # against committed weights.  Latched like every other
            # sync-path error — a transient quorum failure here must not
            # crash the train loop when the same failure at sync() time
            # would not.  ONE attempt per round (_arm_attempted): retrying
            # on every inner step would turn a lighthouse outage into up
            # to sync_every x quorum_timeout of train-thread stall per
            # round; sync() makes the round's second (and last) attempt
            # inside its own latch.
            self._arm_attempted = True
            try:
                self._manager.start_quorum()
                self._armed = True
                self._engine.begin_round()
            except Exception as e:  # noqa: BLE001 — latch, keep cadence
                try:
                    self._manager.report_error(e)
                except Exception:  # noqa: BLE001 — mocked managers
                    pass
        self._local_step += 1
        if self._stream and self._armed:
            due = [
                f
                for f in self._schedule.get(self._local_step, ())
                if f.index not in self._issued
            ]
            if due:
                # One flatten per slot, however many fragments share it —
                # this runs on the train-thread hot path.
                leaves = self._jax.tree.flatten(self._get_params())[0]
                for frag in due:
                    self._issued.add(frag.index)
                    self._engine.submit(frag, leaves)
        if self._local_step >= self._sync_every:
            self.sync()

    def sync(self) -> None:
        """Finishes the round: drains in-flight fragments, votes, and
        applies the per-fragment outer updates only on a passed vote.
        Errors anywhere in the round LATCH on the manager and the counter
        resets in a ``finally`` — every group re-enters the next round on
        the same cadence even when a sync dies mid-quorum."""
        from torchft_tpu.manager import ExceededMaxRetriesError

        if self._fragment_commit:
            self._sync_fragment_commit()
            return
        self._round_closed = False
        self._voted = False
        self._vote_passed = False
        try:
            self._sync_inner()
        except ExceededMaxRetriesError:
            # The give-up contract must still propagate: a loop configured
            # with max_retries relies on this exception to terminate.
            raise
        except Exception as e:  # noqa: BLE001 — latch, never desync cadence
            if self._vote_passed:
                # Peers were already told this round committed; swallowing
                # a post-vote apply failure would leave THIS group on
                # different weights with every later vote passing — crash
                # instead, and heal back to the committed state.
                raise
            try:
                self._manager.report_error(e)
            except Exception:  # noqa: BLE001 — mocked managers
                pass
            # Quiesce the worker BEFORE touching round state: an in-flight
            # fragment round re-sets pending residuals and writes results;
            # aborting under it would race, and a stale result could bleed
            # into the next round's result map.
            try:
                self._engine.drain()
            except Exception:  # noqa: BLE001 — mocked managers
                pass
            if not self._voted:
                # Sibling local ranks are already in the two-phase commit
                # barrier; vote (False, via the latched error) instead of
                # leaving them to time out round after round.
                try:
                    self._manager.should_commit()
                except Exception:  # noqa: BLE001 — vote itself failing
                    pass
            if not self._round_closed:
                self._engine.end_round(committed=False)
            try:
                self._set_params(self.backup_params)
            except Exception:  # noqa: BLE001 — leave local params standing
                pass
        finally:
            self._local_step = 0
            self._armed = False
            self._arm_attempted = False
            self._issued = set()

    def _sync_inner(self) -> None:
        if not self._armed:
            self._manager.start_quorum()
            self._armed = True
            self._engine.begin_round()
        # Any fragment not yet streamed goes now (all of them in blocking
        # mode; stragglers whose slot never ticked in stream mode).
        leaves = None
        for frag in self._plan.fragments:
            if frag.index not in self._issued:
                self._issued.add(frag.index)
                if leaves is None:
                    leaves = self._jax.tree.flatten(self._get_params())[0]
                self._engine.submit(frag, leaves)

        results = self._engine.drain()
        # Summary fields must land BEFORE the vote: should_commit flushes
        # this step's step_summary record.  The round's step is captured
        # here too — a committed vote advances current_step(), and the
        # semisync_round event must join against the SAME step the round's
        # spans and commit records carry.
        stats = self._engine.round_stats()
        self._note_summary(stats)
        try:
            round_step = int(self._manager.current_step())
        except (TypeError, ValueError):  # mocked managers
            round_step = -1
        self._voted = True
        committed = bool(self._manager.should_commit())
        self._vote_passed = committed
        applied_inplace = self._apply(results) if committed else False
        self._engine.end_round(committed=committed)
        self._round_closed = True
        self._emit_round(stats, committed, round_step)
        # Commit or not, the live params reset to the (possibly updated)
        # last-committed weights — the blocking port's contract.  When the
        # per-fragment write-back already landed every leaf as its outer
        # step committed, the whole-tree reset would only re-send the same
        # bytes; skip it.
        if not applied_inplace:
            self._set_params(self.backup_params)

    # -- fragment-granular commit (see __init__ docstring) -------------------

    def _step_fragment_commit(self) -> None:
        """Inner-step tick in fragment-commit mode: at a fragment's slot,
        settle the previous fragment's vote first (its reduce has been
        overlapping inner steps since its own slot), then arm this
        fragment's quorum and issue its reduce."""
        self._local_step += 1
        due = [
            f
            for f in self._schedule.get(self._local_step, ())
            if f.index not in self._issued
        ]
        for frag in due:
            self._finish_pending_fragment()
            self._issue_fragment(frag)
        if self._local_step >= self._sync_every:
            self.sync()

    def _issue_fragment(self, frag) -> None:
        """Arms one fragment's quorum (train thread — heals and elastic
        reconfiguration happen here, never on the worker) and submits its
        reduce.  An arm failure latches; the fragment's vote then fails at
        settle time and only ITS leaves roll back."""
        self._issued.add(frag.index)
        self._pending_fragment = frag
        try:
            self._manager.start_quorum()
            self._armed = True
        except Exception as e:  # noqa: BLE001 — latch, keep cadence
            try:
                self._manager.report_error(e)
            except Exception:  # noqa: BLE001 — mocked managers
                pass
            return
        if not self._round_open:
            self._engine.begin_round()
            self._round_open = True
        leaves = self._jax.tree.flatten(self._get_params())[0]
        self._engine.submit(frag, leaves)

    def _finish_pending_fragment(self) -> None:
        """Settles the outstanding fragment: drain its reduce, vote, and
        apply-or-rollback just that fragment.  A post-vote apply failure
        raises (peers were told the fragment committed — heal back rather
        than diverge silently), same contract as the round-level path."""
        frag = self._pending_fragment
        if frag is None:
            return
        self._pending_fragment = None
        results: Dict[int, np.ndarray] = {}
        if self._armed:
            try:
                results = self._engine.drain()
            except Exception as e:  # noqa: BLE001 — mocked managers
                try:
                    self._manager.report_error(e)
                except Exception:  # noqa: BLE001
                    pass
            # Running round accounting lands on THIS fragment's step
            # record before its vote flushes it.
            self._note_summary(self._engine.round_stats())
        committed = False
        if self._armed:
            self._armed = False
            try:
                committed = bool(self._manager.should_commit())
            except Exception as e:  # noqa: BLE001 — vote itself failing
                from torchft_tpu.manager import ExceededMaxRetriesError

                if isinstance(e, ExceededMaxRetriesError):
                    raise
                try:
                    self._manager.report_error(e)
                except Exception:  # noqa: BLE001
                    pass
        if not committed:
            self._round_failed += 1
        flat = results.get(frag.index) if committed else None
        if committed and flat is not None:
            # Post-vote apply: peers were told this fragment committed, so
            # a failure here must RAISE (heal back to the committed state)
            # — _post_vote marks the window for the sync-level handler.
            self._post_vote = True
            self._apply_one_fragment(frag, flat)
            self._post_vote = False
        else:
            try:
                self._apply_one_fragment(frag, None)
            except Exception:  # noqa: BLE001 — leave local params standing
                pass
        self._engine.promote_fragment(frag, committed)

    def _apply_one_fragment(self, frag, flat: Optional[np.ndarray]) -> None:
        """One fragment's outer step (vote passed, ``flat`` is its averaged
        pseudogradient) or rollback (``flat`` is None): either way exactly
        this fragment's leaves land on device through the write-back hook —
        the surrounding fragments are untouched."""
        import optax

        write_back = self._set_fragment_params
        assert write_back is not None  # enforced at construction
        if flat is None:
            # Failed vote: the backup stands; roll only this fragment's
            # live leaves back to it (inner steps moved them).
            write_back(
                list(frag.bucket.indices),
                [self._leaves[i] for i in frag.bucket.indices],
            )
            return
        k = frag.index
        pg_leaves = [np.ascontiguousarray(arr) for _i, arr in frag.unpack(flat)]
        backup_leaves = [self._leaves[i] for i in frag.bucket.indices]
        updates, self._outer_states[k] = self._outer_tx.update(
            pg_leaves, self._outer_states[k], backup_leaves
        )
        new_leaves = optax.apply_updates(backup_leaves, updates)
        for i, nl in zip(frag.bucket.indices, new_leaves):
            self._leaves[i] = np.asarray(nl)
        write_back(
            list(frag.bucket.indices),
            [self._leaves[i] for i in frag.bucket.indices],
        )
        self._codecs[k].set_backup(frag.pack(self._leaves))

    def _sync_fragment_commit(self) -> None:
        """Round boundary in fragment-commit mode: settle the last
        outstanding fragment, run any never-issued stragglers (all of them
        in blocking mode) as their own mini-rounds, then emit the round's
        accounting.  There is no round-level vote and no whole-tree reset:
        every fragment already landed (or rolled back) at its own commit
        moment."""
        from torchft_tpu.manager import ExceededMaxRetriesError

        try:
            self._finish_pending_fragment()
            for frag in self._plan.fragments:
                if frag.index not in self._issued:
                    self._issue_fragment(frag)
                    self._finish_pending_fragment()
            stats = self._engine.round_stats()
            committed = self._round_failed == 0
            try:
                round_step = int(self._manager.current_step())
            except (TypeError, ValueError):  # mocked managers
                round_step = -1
            if self._round_open:
                self._engine.end_round(committed=committed, promote=False)
            self._emit_round(stats, committed, round_step)
        except ExceededMaxRetriesError:
            raise
        except Exception as e:  # noqa: BLE001 — latch, never desync cadence
            if self._post_vote:
                # A committed fragment's apply failed — peers already
                # advanced; crash and heal rather than silently diverge.
                raise
            try:
                self._manager.report_error(e)
            except Exception:  # noqa: BLE001 — mocked managers
                pass
        finally:
            self._local_step = 0
            self._armed = False
            self._arm_attempted = False
            self._issued = set()
            self._pending_fragment = None
            self._round_failed = 0
            self._round_open = False
            self._post_vote = False

    def _apply(self, results: Dict[int, np.ndarray]) -> bool:
        """Outer optimizer step on the averaged pseudogradients —
        per-fragment or whole-tree per ``outer_scope``.  Deterministic
        given identical inputs, and the ring guarantees bitwise-identical
        averages on every group — so all groups land bitwise-identical
        backups (the replica-consistency property the integration tests
        pin).  Returns True when the per-fragment write-back hook landed
        EVERY leaf on device already (the caller then skips the
        whole-tree reset)."""
        import optax

        if self._outer_scope == "tree":
            # Assemble the full pseudogradient tree and run ONE update —
            # the blocking port's semantics; outer transforms with
            # cross-leaf coupling (global-norm clipping) need this.
            pg_leaves: List[np.ndarray] = [
                np.zeros_like(l) for l in self._leaves
            ]
            for frag in self._plan.fragments:
                flat = results.get(frag.index)
                if flat is None:
                    continue
                for i, arr in frag.unpack(flat):
                    pg_leaves[i] = np.ascontiguousarray(arr)
            pg_tree = self._jax.tree.unflatten(self._treedef, pg_leaves)
            backup_tree = self.backup_params
            updates, self._outer_states = self._outer_tx.update(
                pg_tree, self._outer_states, backup_tree
            )
            new_tree = optax.apply_updates(backup_tree, updates)
            self._leaves = [
                np.asarray(l) for l in self._jax.tree.flatten(new_tree)[0]
            ]
            self._refresh_codec_backups()
            return False
        write_back = self._set_fragment_params
        for k, frag in enumerate(self._plan.fragments):
            flat = results.get(frag.index)
            if flat is None:
                # No averaged pseudogradient for this fragment: its backup
                # stands, but its LIVE leaves moved through sync_every
                # inner steps — the per-fragment path must still roll them
                # back, or skipping the whole-tree reset would leave this
                # fragment's device leaves uncommitted.
                if write_back is not None:
                    write_back(
                        list(frag.bucket.indices),
                        [self._leaves[i] for i in frag.bucket.indices],
                    )
                continue
            pg_leaves = [
                np.ascontiguousarray(arr) for _i, arr in frag.unpack(flat)
            ]
            backup_leaves = [self._leaves[i] for i in frag.bucket.indices]
            updates, self._outer_states[k] = self._outer_tx.update(
                pg_leaves, self._outer_states[k], backup_leaves
            )
            new_leaves = optax.apply_updates(backup_leaves, updates)
            for i, nl in zip(frag.bucket.indices, new_leaves):
                self._leaves[i] = np.asarray(nl)
            if write_back is not None:
                # Land this fragment the moment its outer step committed:
                # the device transfer overlaps fragment k+1's outer math
                # instead of queueing behind the whole tree at the round
                # boundary.
                write_back(
                    list(frag.bucket.indices),
                    [self._leaves[i] for i in frag.bucket.indices],
                )
        self._refresh_codec_backups()
        return write_back is not None

    def _note_summary(self, stats: Dict[str, int]) -> None:
        """Round accounting into the step in flight's step_summary — must
        run before the commit vote flushes that record."""
        note = getattr(self._manager, "note_summary_fields", None)
        if callable(note):
            try:
                note(
                    semisync_fragments=stats["fragments"],
                    semisync_wire_bytes=stats["wire_bytes"],
                    semisync_codec=self._codec_name,
                )
            except Exception:  # noqa: BLE001 — telemetry only
                pass

    def _emit_round(
        self, stats: Dict[str, int], committed: bool, round_step: int
    ) -> None:
        """The per-round metrics event; the int8 residual norm rides as a
        gauge."""
        manager = self._manager
        residual_l2 = 0.0
        # The residual norm costs a per-fragment device reduction; only
        # pay it when the JSONL stream will carry it.
        try:
            want_residual = bool(manager.metrics.enabled)
        except Exception:  # noqa: BLE001 — mocked managers
            want_residual = False
        if want_residual:
            for c in self._codecs:
                fn = getattr(c, "residual_l2", None)
                if callable(fn):
                    residual_l2 += float(fn())
            self.metrics.observe_residual(residual_l2)
        try:
            manager.metrics.emit(
                "semisync_round",
                step=round_step,
                committed=committed,
                fragments=stats["fragments"],
                wire_bytes=stats["wire_bytes"],
                d2h_bytes=stats["d2h_bytes"],
                codec=self._codec_name,
                streamed=self._stream,
                writeback=(
                    "fragment" if self._set_fragment_params is not None else "tree"
                ),
                residual_l2=round(residual_l2, 6),
            )
        except Exception:  # noqa: BLE001 — mocked managers / telemetry only
            pass
