"""Observability: step-scoped tracing, goodput attribution, trace export.

Three halves plus the live exposition:

- :mod:`torchft_tpu.obs.spans` — the *producer* side.  ``SpanTracker``
  wraps each Manager step phase (quorum, configure, heal, allreduce-merge,
  commit vote) in begin/end spans keyed by ``(slice_gen, step,
  replica_id)`` with monotonic-clock durations, emitted through
  :class:`~torchft_tpu.metrics.MetricsLogger` as versioned ``span``
  records, plus one ``step_summary`` record per step carrying the full
  phase breakdown.  ``StepTimeStats`` keeps the rolling per-step busy-time
  EWMA + p50/p99 the Manager pushes onto heartbeats for the lighthouse's
  straggler sentinel.

- :mod:`torchft_tpu.obs.report` — the *consumer* side.  Merges every
  replica's JSONL stream into a per-step cluster timeline, classifies wall
  time into productive / quorum-wait / heal / drain / idle, names the
  critical-path phase per step, and computes the dead-window goodput
  fraction.  CLI::

      python -m torchft_tpu.obs.report metrics.jsonl [...]

- :mod:`torchft_tpu.obs.trace` — the *timeline* side.  Merges the same
  streams into one Chrome/Perfetto ``trace.json`` (one track per replica
  incarnation, phase slices, fault/drain/alert instants, commit-barrier
  clock alignment).  CLI::

      python tools/trace_export.py metrics.jsonl [...]

- :mod:`torchft_tpu.obs.flight` — the *control-plane* side.  Registry and
  consumers for the native servers' flight recorders (bounded RPC-span +
  state-transition rings, ``GET /debug/flight.json``, ``TPUFT_FLIGHT_DIR``
  shutdown dumps): causal trace ids, quorum-transition reconstruction,
  and conversion into the Perfetto control-plane track.

- :mod:`torchft_tpu.obs.ledger` — the *accounting* side.  Every committed
  step's wall classified into the pinned cause taxonomy (``CAUSES``),
  per-step vectors in ``step_summary.ledger``, cumulative counters on
  heartbeat fields 14-16, cluster rollup on the lighthouse's
  ``GET /goodput.json`` — plus the stream rollup and the bench's
  headline-vs-ledger cross-check.

- :mod:`torchft_tpu.obs.incident` — the *capture* side.  Polls the
  lighthouse's incident-trigger feed (``GET /incident.json``) and bundles
  flight rings + alerts + ledger + span tails + dumps into
  ``incident_<step>/`` with a machine-readable verdict.  CLI::

      python tools/incident.py capture <workdir> --lighthouse http://...

The live leg — cluster metrics, latency histograms, the sentinels, the
goodput ledger and the incident feed — is served by the native lighthouse
(``GET /metrics``, ``GET /alerts.json``, ``GET /goodput.json``,
``GET /incident.json``, ``GET /debug/flight.json``; the cross-plane map
and knob index live in docs/observability.md).
"""

from torchft_tpu.obs.flight import FLIGHT_EVENTS, mint_trace_id
from torchft_tpu.obs.ledger import CAUSES, LOST_CAUSES, StepLedger
from torchft_tpu.obs.spans import SpanTracker, StepTimeStats

__all__ = [
    "CAUSES",
    "FLIGHT_EVENTS",
    "LOST_CAUSES",
    "SpanTracker",
    "StepLedger",
    "StepTimeStats",
    "mint_trace_id",
]
