"""Prometheus-style exposition for the semi-sync plane: ``tpuft_semisync_*``.

The lighthouse's native ``GET /metrics`` covers the control plane; the
semi-sync data plane is per-worker and Python-side, so it exposes its own
gauges the same text-format way: a :class:`SemiSyncMetrics` accumulates
counters from the engine and ``render_prometheus`` produces the
exposition.

Counters are monotonic since construction (restart = reset, standard
Prometheus counter semantics); gauges are last-observation.

It opens no port of its own: the worker-side exposition is
:class:`torchft_tpu.obs.prom.WorkerMetrics` (one ``/metrics`` per worker,
``TPUFT_WORKER_METRICS_PORT``), where the semisync engine registers
``render_prometheus`` as a section when a Manager endpoint is serving.
"""

from __future__ import annotations

import threading

__all__ = ["SemiSyncMetrics"]


class SemiSyncMetrics:
    """Thread-safe counter/gauge set for one StreamingDiLoCo instance."""

    def __init__(self, codec: str = "", replica_id: str = "") -> None:
        self.codec = codec
        self.replica_id = replica_id
        self._lock = threading.Lock()
        self.fragments_total = 0
        self.rounds_total = 0
        self.commits_total = 0
        self.aborts_total = 0
        self.wire_bytes_total = 0
        self.d2h_bytes_total = 0
        self.last_residual_l2 = 0.0
        self.last_round_overlap_ms = 0.0

    def observe_fragment(self, wire_bytes: int, d2h_bytes: int) -> None:
        with self._lock:
            self.fragments_total += 1
            self.wire_bytes_total += int(wire_bytes)
            self.d2h_bytes_total += int(d2h_bytes)

    def observe_round(self, committed: bool) -> None:
        with self._lock:
            self.rounds_total += 1
            if committed:
                self.commits_total += 1
            else:
                self.aborts_total += 1

    def observe_residual(self, l2: float) -> None:
        with self._lock:
            self.last_residual_l2 = float(l2)

    def observe_overlap_ms(self, ms: float) -> None:
        with self._lock:
            self.last_round_overlap_ms = float(ms)

    def render_prometheus(self) -> str:
        """The ``tpuft_semisync_*`` exposition (Prometheus text format)."""
        with self._lock:
            label = ""
            if self.replica_id or self.codec:
                parts = []
                if self.replica_id:
                    parts.append(f'replica="{self.replica_id}"')
                if self.codec:
                    parts.append(f'codec="{self.codec}"')
                label = "{" + ",".join(parts) + "}"
            lines = []

            def metric(name: str, kind: str, help_: str, value) -> None:
                lines.append(f"# HELP {name} {help_}")
                lines.append(f"# TYPE {name} {kind}")
                lines.append(f"{name}{label} {value}")

            metric(
                "tpuft_semisync_fragments_total", "counter",
                "fragment pseudogradient rounds completed",
                self.fragments_total,
            )
            metric(
                "tpuft_semisync_rounds_total", "counter",
                "outer sync rounds finished (committed + aborted)",
                self.rounds_total,
            )
            metric(
                "tpuft_semisync_commits_total", "counter",
                "outer sync rounds that passed the commit vote",
                self.commits_total,
            )
            metric(
                "tpuft_semisync_aborts_total", "counter",
                "outer sync rounds discarded (error latched / vote lost)",
                self.aborts_total,
            )
            metric(
                "tpuft_semisync_wire_bytes_total", "counter",
                "per-hop wire bytes of fragment payloads (codec-encoded)",
                self.wire_bytes_total,
            )
            metric(
                "tpuft_semisync_d2h_bytes_total", "counter",
                "device->host fetch bytes of fragment payloads",
                self.d2h_bytes_total,
            )
            metric(
                "tpuft_semisync_residual_l2", "gauge",
                "L2 norm of the carried int8 error-feedback residual",
                self.last_residual_l2,
            )
            metric(
                "tpuft_semisync_round_overlap_ms", "gauge",
                "last round's background sync time overlapped with inner "
                "steps",
                self.last_round_overlap_ms,
            )
            return "\n".join(lines) + "\n"
