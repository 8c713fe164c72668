"""Step-scoped tracing: begin/end spans for Manager step phases.

Turns the flat metrics event stream into a distributed trace without a
tracing dependency: each phase of a step runs inside ``SpanTracker.span``,
which measures a monotonic-clock duration and emits one ``span`` record
keyed by ``(slice_gen, step, replica_id)`` — ``replica_id`` comes from the
underlying :class:`~torchft_tpu.metrics.MetricsLogger`, ``slice_gen`` from
``TPUFT_SLICE_GEN`` (the scheduler's restart counter, see spec.py), so
records from every incarnation of every replica across restarts merge into
one unambiguous timeline.  ``obs/report.py`` is the matching consumer.

The known phase names are fixed in :data:`PHASES`; a span may use any name
(the record is self-describing) but report.py's attribution buckets are
built from these.

One tracker per Manager.  Phases of the same step may run on different
threads (the quorum thread vs the train loop), so the per-step breakdown
is lock-guarded; ``step_summary(step, committed=...)`` flushes the
accumulated phases as one record after the commit vote.

Below the phases sit *sub-spans* (:data:`SUBSPANS`): what a phase is made
of, measured where the work happens — on the materializer thread, the ring's
workers, the train thread.  They are kept in memory and leave in one
``subspan`` record, in the same ``write()`` as the step's ``step_summary``;
they never enter the phase accumulator, so attribution, the goodput ledger
and the straggler sentinel do not see them.  Every ``with``-style span and
sub-span also opens a ``jax.profiler.TraceAnnotation("tpuft:<name>")``, so a
profile shows them on the thread they ran on, on the device trace's clock.

The process's program builds (obs/builds.py: one record a stage of every
program JAX builds) leave the same way: a tracker with a stream takes what has
gathered and writes it as ``program_build`` records in that ``write()`` too.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from torchft_tpu.metrics import MetricsLogger
from torchft_tpu.obs import builds

__all__ = [
    "PHASES",
    "OVERLAPPED_PHASES",
    "SUBSPANS",
    "PARTS",
    "Span",
    "SubSpan",
    "SpanTracker",
    "StepTimeStats",
]

# The Manager step phases report.py attributes (docs/architecture.md
# "Observability").  quorum = blocking wait on the lighthouse round;
# configure = collective rebuild on quorum change; heal = peer weight
# fetch; allreduce_d2h = the GradientAverager's per-bucket device->host
# fetch into the persistent flat buffers (blocks the train thread, so it
# is FT-overhead time, NOT productive compute — report.py charges it to
# the other-FT bucket and the straggler sentinel subtracts it from busy
# time; fields: bucket, bytes, pos = the bucket's place in the plan's
# fetch order);
# allreduce_h2d = the matching result scatter-back (device_put of
# reduced buckets onto the leaves' devices/shardings — with device wire
# prep it moves wire-dtype bytes; charged exactly like allreduce_d2h so
# the FULL round-trip cost is attributed, not just the fetch).  It may
# occur SEVERAL times a step: the streamed exchange sends every resolved
# bucket home between two fetches, each harvest in a span of its own,
# and waits for all puts in a last one; the accumulator sums them;
# allreduce_merge = the wait for ring ops still in flight after the last
# fetch, and the drain of pending allreduce futures at commit
# time; commit_vote = the two-phase commit barrier RPC; snapshot = the
# donor-side device->host flatten on the HTTP transport's background
# snapshotter — an OVERLAPPED phase (it runs concurrently with the train
# step, so report.py shows it but does not charge it against productive
# time; a snapshot span on the critical path is exactly the regression the
# async pipeline exists to prevent); outer_sync = one fragment's
# background pseudogradient round on the semisync engine's worker
# (torchft_tpu/semisync) — OVERLAPPED for the same reason: it runs
# concurrent with inner steps, and only the round-end drain (charged as
# allreduce_merge) ever blocks the train thread; ec_encode = the k+m
# Reed-Solomon shard encode on the same background snapshotter
# (torchft_tpu/ec) — OVERLAPPED like snapshot, and the bench's
# donor-side-overhead cell exists to keep it that way; ec_reconstruct =
# the donor-free heal fallback assembling max-step state from surviving
# shard holders — blocks the healing group's quorum thread exactly like
# heal, and report.py folds it into the heal class.
PHASES = (
    "quorum",
    "configure",
    "heal",
    "ec_reconstruct",
    "allreduce_d2h",
    "allreduce_h2d",
    "allreduce_merge",
    "commit_vote",
    "snapshot",
    "ec_encode",
    "outer_sync",
)

# Phases that run on background threads concurrent with compute: report.py
# excludes these from per-step critical-path attribution.
OVERLAPPED_PHASES = ("snapshot", "ec_encode", "outer_sync")

# Sub-spans: name -> the phase (or frame) that caused it.  They take a phase
# apart and are NEVER attributed themselves: they stay out of the phase
# accumulator (phases_ms, ft_accounted_ms, the ledger and the sentinel read
# what they read without them) and leave as one ``subspan`` record a step,
# not as ``span`` records.  To see them: the ``subspan`` records of the
# metrics stream, or ``tpuft:<name>`` on the host threads of a profile.
#   d2h_ready / d2h_lease_wait / d2h_fetch / d2h_copy — one leaf's fetch on
#     the materializer thread: the wait for the gradient program, the wait
#     for the host's D2H lease (accelerator-resident leaves only; ``bytes``,
#     ``contended``: whether anyone was ahead), the DMA into PJRT's host
#     buffer (np.asarray alone), the second pass into the flat buffer;
#     allreduce_d2h minus the four is the hand-off to that thread.
#   ring_queue / ring_run — one ring op from Manager.allreduce's submission
#     to the moment a ring worker took it up, and from there to its end
#     (recorded from the collective's timestamps, so no TraceAnnotation).
#   normalize — the averaging continuation on the thread that resolved the
#     op's future: it divides in the op's own buffer (``in_place``: true)
#     where the result shows it may, else into a new array with a cast.
#   h2d_put — one bucket's way back (unpack + device_put, where its ring
#     op was harvested: possibly between two fetches), and without
#     ``bucket`` the one wait for every put of the call (``bytes``: all of
#     them).
#   quorum_wait — what the TRAIN thread waits for the quorum (the ``quorum``
#     phase is the quorum thread's RPC).
#   ft_step — the frame of one TrainStep.ft_step (speculative = which update
#     program was dispatched); grads_dispatch / apply_dispatch — the host
#     time of its two dispatches; counters_note — the hand-over of the last
#     step's loss counters (TrainStep(loss_has_counters=True)) to the
#     step_summary: a read of arrays already on the host.
#   manager_start — Manager.__init__ from its first line to its return: the
#     store's server and client, the native ManagerServer's bind and its
#     first word with the lighthouse, the Manager's client.  A sub-span and
#     not a span, so a start-up enters no step's phases or ledger.
SUBSPANS = {
    "d2h_ready": "allreduce_d2h",
    "d2h_lease_wait": "allreduce_d2h",
    "d2h_fetch": "allreduce_d2h",
    "d2h_copy": "allreduce_d2h",
    "ring_queue": "exchange",
    "ring_run": "exchange",
    "normalize": "exchange",
    "h2d_put": "allreduce_h2d",
    "quorum_wait": "ft_step",
    "ft_step": None,
    "grads_dispatch": "ft_step",
    "apply_dispatch": "ft_step",
    "counters_note": "ft_step",
    "manager_start": None,
}


# Parts of the gradient program: the ``jax.named_scope`` names the model
# (models/transformer.py and the mixers' files beside it, models/moe.py, ops/sparse_attention.py) writes around
# its forward computation, one vocabulary for every architecture.  Scopes nest,
# and JAX's transforms carry them into the backward pass
# (``transpose(jvp(ffn))``) and into what ``jax.checkpoint`` computes again
# (``checkpoint/rematted_computation/ffn``), so a compiled instruction's part
# is the innermost of these names on its ``op_name`` path (obs/opmap.py).
#   embed — the token gather (its backward is the scatter-add);
#   norm — every RMS / layer norm but the final one; attn_proj — the q/k/v/o
#     products, latent attention's low-rank path, RoPE, the reshapes and
#     transposes around the heads; cca_mix — what compressed attention puts
#     between its projections and RoPE: the value shift, both causal
#     convolutions, the q-k mean and the norm a head (the first work of a
#     layer outside the attention call that mixes positions); kda_mix — what
#     Kimi Delta Attention puts around its scan: the short convolutions with
#     SiLU, the L2 norms, the decay and beta before it, the gated head norm
#     after it (on a TPU's one-device program the `tpuft_kdamix_*` kernels,
#     one pass a direction, with beta's sigmoid and the decay's mean in XLA
#     beside them; elsewhere XLA fusions); kda_scan — the gated delta rule over the sequence (the
#     `tpuft_kda_*` kernels and whatever XLA puts around them);
#     ssm_mix — what a Mamba-2 block puts around its scan: the kernel-4
#     convolution with its bias and SiLU, softplus and the decay before it,
#     the skip D x, the gate SiLU(z) and the norm over groups after it (XLA
#     fusions; the in- and out-projections are `attn_proj`'s, as KDA's are);
#     ssm_scan — the state-space recurrence over the sequence (the
#     `tpuft_ssd_*` kernels, the running sums of the log decay and whatever
#     else XLA puts around them);
#     attn — the attention call: kernels and
#     whatever XLA puts around them; attn_window — the same call in a layer
#     that attends under a window (the `tpuft_swa_*` kernels), so that a
#     model of both kinds reads them apart; dsa_index — the indexer's operands and
#     its loss; dsa_select — the selection and the mask built from it;
#   ffn — the dense gate / up / down; router — scores, top-k and statistics
#     (where the router reads the layer's input, `moe_router_early`, it
#     stands BEFORE the layer's first norm and its attention);
#     experts — row table, row moves, grouped matmuls, gate weighting;
#     shared_expert — the SwiGLU every token passes beside the routed ones;
#   head_loss — final norm, head product, cross-entropy, the loss's terms
#     and counters; stack — a layer's slice of the stacked weights (in the
#     backward pass: the per-layer gradients padded and summed into the
#     stacked gradient) and the stacking of the layers' statistics;
#   exit_gate — a looped model's exit-weighted loss: the gate's product on
#     every pass's state, the exit distribution, the combination of the
#     passes' losses, the entropy term and the three counters (the passes'
#     heads and the final norm between passes stay head_loss);
#   bd_noise — block-diffusion training's noise: the sequences' keys, the
#     blocks' levels, the tokens' mask, the weights 1 / t and the doubled
#     stream of ids (`_block_diffusion_loss`); bd_attn — the attention call
#     over that stream (the `tpuft_bd_*` kernels: the live tiles of the
#     three-part block mask), so a trace tells it from `attn`.
#   gdn_mix — what Gated DeltaNet puts around its scan: the kernel-4
#     convolution with SiLU over q, k and v, the L2 norm a key head, the
#     decay's softplus and beta (a number a value head) before it, the head
#     norm times SiLU(z) after it (XLA fusions under a checkpoint a half; the
#     projections are `attn_proj`'s, as KDA's are); gdn_scan — the gated delta
#     rule with a decay a head over the sequence (`ops.delta_attention.kda`:
#     the `tpuft_kda_*` kernels and what XLA puts around them — today the
#     decay's broadcast over the key's channels and the key heads' repeat).
PARTS = (
    "embed", "norm", "attn_proj", "cca_mix", "kda_mix", "kda_scan", "attn", "attn_window", "dsa_index", "dsa_select",
    "ffn", "router", "experts", "shared_expert", "head_loss", "stack", "ssm_mix", "ssm_scan", "exit_gate",
    "bd_noise", "bd_attn", "gdn_mix", "gdn_scan",
)


@functools.cache
def _annotation_cls():
    """``jax.profiler.TraceAnnotation``, imported on first use (a no-op of
    about a microsecond while no profile is being taken)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class Span:
    """One in-flight phase measurement; ``duration_ms`` is valid after the
    ``with`` block exits (monotonic clock, NTP-immune)."""

    def __init__(self, tracker: "SpanTracker", phase: str, step: int, fields: dict):
        self._tracker = tracker
        self.phase = phase
        self.step = step
        self.fields = fields
        self.t_start = 0.0
        self.duration_ms: float = 0.0
        self._annotation: Any = None

    def __enter__(self) -> "Span":
        self._annotation = _annotation_cls()(f"tpuft:{self.phase}", step=self.step)
        self._annotation.__enter__()
        self.t_start = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.duration_ms = round((time.monotonic() - self.t_start) * 1e3, 3)
        self._annotation.__exit__(exc_type, exc, tb)
        self._tracker._finish(self, ok=exc_type is None)


class SubSpan:
    """One in-flight sub-span (see :data:`SUBSPANS`).  ``fields`` may be
    filled inside the ``with`` block; ``t0_ns`` / ``t1_ns`` are
    ``time.monotonic_ns()`` and valid after it exits."""

    __slots__ = ("_tracker", "name", "step", "fields", "t0_ns", "t1_ns", "_annotation")

    def __init__(self, tracker: "SpanTracker", name: str, step: int, fields: dict):
        self._tracker = tracker
        self.name = name
        self.step = step
        self.fields = fields
        self.t0_ns = self.t1_ns = 0
        self._annotation: Any = None

    def __enter__(self) -> "SubSpan":
        self._annotation = _annotation_cls()(f"tpuft:{self.name}", step=self.step, **self.fields)
        self._annotation.__enter__()
        self.t0_ns = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1_ns = time.monotonic_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        self._tracker.note_sub(self.name, self.step, self.t0_ns, self.t1_ns, **self.fields)


class SpanTracker:
    """Emits ``span`` / ``step_summary`` records through a MetricsLogger.

    Spans are emitted even for phases that raise (with ``ok: false``) so a
    hung-then-failed quorum still shows up in the trace with its real
    duration.
    """

    def __init__(
        self, metrics: MetricsLogger, slice_gen: Optional[int] = None
    ) -> None:
        self._metrics = metrics
        if slice_gen is None:
            try:
                slice_gen = int(os.environ.get("TPUFT_SLICE_GEN", "0"))
            except ValueError:
                slice_gen = 0
        self.slice_gen = slice_gen
        self._lock = threading.Lock()
        # phase -> accumulated ms since the last step_summary.  Keyed by
        # phase, NOT by step: a heal fast-forwards the step number mid-step
        # (quorum ran at the old step, heal at max_step, the vote at
        # max_step), yet all of it is one train-loop iteration — the
        # summary flushes everything since the previous vote.  Individual
        # span records still carry their own step.
        self._acc: Dict[str, float] = {}
        # Sub-spans since the last flush: (name, step, t0_ns, t1_ns, thread,
        # fields).  Appended from any thread, swapped out under the lock.
        self._subs: List[Tuple[str, int, int, int, str, dict]] = []

    @property
    def enabled(self) -> bool:
        return self._metrics.enabled

    def span(self, phase: str, step: int, **fields) -> Span:
        """Context manager measuring one phase of one step."""
        return Span(self, phase, step, fields)

    def sub(self, name: str, step: int, **fields) -> SubSpan:
        """Context manager measuring one sub-span (see :data:`SUBSPANS`) on
        the calling thread.  It is buffered, never accumulated: with no
        metrics path it costs its two clock reads and keeps nothing."""
        return SubSpan(self, name, step, fields)

    def note_sub(self, name: str, step: int, t0_ns: int, t1_ns: int, **fields) -> None:
        """Records a sub-span from its two ``time.monotonic_ns()`` stamps —
        for an interval that does not live in one ``with`` block on one
        thread (a ring op: submitted here, run there)."""
        if not self._metrics.enabled:
            return
        rec = (name, step, t0_ns, t1_ns, threading.current_thread().name, fields)
        with self._lock:
            self._subs.append(rec)

    def _take_subspans(self) -> Optional[dict]:
        """The buffered sub-spans as the fields of one ``subspan`` record
        (None when there are none); the buffer is left empty."""
        with self._lock:
            subs, self._subs = self._subs, []
        if not subs:
            return None
        spans = [
            dict(f, name=n, parent=SUBSPANS.get(n), step=st, t0_ns=a, t1_ns=b, thread=th)
            for n, st, a, b, th, f in subs
        ]
        return {"slice_gen": self.slice_gen, "spans": spans}

    def _take_builds(self) -> List[dict]:
        """The process's program builds gathered since anyone took them
        (obs/builds.py), as the fields of one ``program_build`` record each.
        Without a stream they stay in their ring."""
        if not self._metrics.enabled:
            return []
        return [dict(b, slice_gen=self.slice_gen) for b in builds.take()]

    def flush_subspans(self) -> None:
        """Writes what is buffered now — at shutdown and when an error is
        latched, so a crash loses at most the step in flight."""
        subs = self._take_subspans()
        if subs is not None:
            self._metrics.emit("subspan", **subs)
        for build in self._take_builds():
            self._metrics.emit("program_build", **build)

    def phases_ms(self) -> Dict[str, float]:
        """Copy of the per-phase accumulation since the last
        ``step_summary`` flush — the goodput ledger reads this at commit
        time (BEFORE the flush) to classify the step's wall interval into
        its cause taxonomy (torchft_tpu/obs/ledger.py)."""
        with self._lock:
            return dict(self._acc)

    def ft_accounted_ms(self) -> float:
        """Milliseconds accumulated in NON-overlapped phases since the last
        ``step_summary`` flush — the FT wait time of the step in flight.
        The Manager subtracts this from the commit-to-commit wall interval
        to get the step's BUSY time for the straggler sentinel: in lockstep
        training the raw commit interval equalizes across the quorum (the
        slow host delays everyone), so only wall-minus-waits distinguishes
        the replica that actually computed the whole time."""
        with self._lock:
            return sum(
                v for k, v in self._acc.items() if k not in OVERLAPPED_PHASES
            )

    def _finish(self, span: Span, ok: bool) -> None:
        with self._lock:
            self._acc[span.phase] = self._acc.get(span.phase, 0.0) + span.duration_ms
        rec = {
            "phase": span.phase,
            "step": span.step,
            "slice_gen": self.slice_gen,
            "duration_ms": span.duration_ms,
            "t_start_mono": span.t_start,
        }
        if not ok:
            rec["ok"] = False
        rec.update(span.fields)
        self._metrics.emit("span", **rec)

    def step_summary(self, step: int, committed: bool, **fields) -> None:
        """Emits the per-step phase breakdown and resets the accumulator;
        the sub-spans and program builds buffered since the last flush leave
        in the same ``write()``.  Call once per step, after the commit vote."""
        with self._lock:
            rec = {
                "step": step,
                "slice_gen": self.slice_gen,
                "committed": committed,
                "phases": {k: round(v, 3) for k, v in self._acc.items()},
                "accounted_ms": round(sum(self._acc.values()), 3),
            }
            self._acc = {}
        rec.update(fields)
        records = [("step_summary", rec)]
        subs = self._take_subspans()
        if subs is not None:
            records.append(("subspan", subs))
        records += [("program_build", build) for build in self._take_builds()]
        self._metrics.emit_many(records)


class StepTimeStats:
    """Rolling per-step wall-time statistics for the straggler sentinel.

    ``observe(ms)`` once per committed step with the step's BUSY
    milliseconds (commit-to-commit wall minus the FT wait phases; see
    ``SpanTracker.ft_accounted_ms``).  Maintains an EWMA — the smoothed
    pace the Manager pushes onto its lighthouse heartbeats — plus a sliding
    window for p50/p99, which ride in the ``step_summary`` record.

    Knobs: ``TPUFT_STEP_TIME_ALPHA`` (EWMA weight of the newest step,
    default 0.5 — heavy enough that a host going 2x slow crosses a 1.5x
    alert threshold on its first slow step, so detection latency is the
    sentinel's grace count, not the smoothing) and
    ``TPUFT_STEP_TIME_WINDOW`` (percentile window, default 64 steps).
    Thread-safe: observe runs on the train thread, snapshots may be read
    from anywhere.
    """

    def __init__(
        self, alpha: Optional[float] = None, window: Optional[int] = None
    ) -> None:
        if alpha is None:
            try:
                alpha = float(os.environ.get("TPUFT_STEP_TIME_ALPHA", "0.5"))
            except ValueError:
                alpha = 0.5
        if not (0.0 < alpha <= 1.0):
            alpha = 0.5
        if window is None:
            try:
                window = int(os.environ.get("TPUFT_STEP_TIME_WINDOW", "64"))
            except ValueError:
                window = 64
        self.alpha = alpha
        self._lock = threading.Lock()
        self._window: deque = deque(maxlen=max(2, window))
        self._ewma: Optional[float] = None
        self._last: float = 0.0
        self._n = 0

    def observe(self, ms: float) -> None:
        if ms < 0.0:
            return
        with self._lock:
            self._last = ms
            self._ewma = (
                ms
                if self._ewma is None
                else self.alpha * ms + (1.0 - self.alpha) * self._ewma
            )
            self._window.append(ms)
            self._n += 1

    @property
    def ewma_ms(self) -> float:
        with self._lock:
            return self._ewma or 0.0

    @property
    def last_ms(self) -> float:
        with self._lock:
            return self._last

    def snapshot(self) -> Dict[str, float]:
        """{ewma, last, p50, p99, max, n} in ms — the step_summary payload."""
        with self._lock:
            ordered = sorted(self._window)
            n = len(ordered)

            def pct(p: float) -> float:
                return ordered[min(n - 1, int(p / 100.0 * n))] if n else 0.0

            return {
                "ewma": round(self._ewma or 0.0, 3),
                "last": round(self._last, 3),
                "p50": round(pct(50.0), 3),
                "p99": round(pct(99.0), 3),
                "max": round(ordered[-1], 3) if n else 0.0,
                "n": self._n,
            }
