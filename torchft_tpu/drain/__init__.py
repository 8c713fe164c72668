"""Cooperative drain: preemption-aware graceful handoff.

torchft's fault model treats every departure as a crash discovered via
heartbeat timeout, but on TPU fleets the majority of departures are
ANNOUNCED in advance: GCE maintenance events, spot/preemptible 30 s
notices, Kubernetes SIGTERM + grace period.  This subsystem turns those
notices into a zero-dead-time handoff instead of a post-mortem:

  1. :class:`DrainWatcher` multiplexes the signal sources — SIGTERM, the
     GCE metadata server's maintenance/preemption endpoints, and an
     explicit file/programmatic trigger — into one "drain notice with
     deadline" event.
  2. The notice reaches the :class:`~torchft_tpu.manager.Manager`
     (``begin_drain``): it tells the Lighthouse immediately over the
     ``Drain`` wire method (docs/wire.md, method 5) so the NEXT quorum
     excludes the departing group with no join/heartbeat-timeout wait,
     then finishes the in-flight step, votes commit, and exits cleanly
     (``complete_drain``).
  3. The supervisor (``torchft_tpu.launch.Launcher.drain``) pre-warms a
     spare the moment the notice arrives and hands it the departing
     group's id, so the replacement's init overlaps the donor's last step
     and it heals live through the existing checkpoint transports.

Observability: ``drain_notice`` / ``drain_handoff`` / ``drain_complete``
events in the metrics stream (torchft_tpu/metrics.py); tests/test_drain.py
drives the path end to end.
"""

from torchft_tpu.drain.watcher import (
    DRAIN_DIR_ENV,
    GCE_METADATA_URL_ENV,
    GCE_POLL_ENV,
    DrainNotice,
    DrainWatcher,
)

__all__ = [
    "DRAIN_DIR_ENV",
    "GCE_METADATA_URL_ENV",
    "GCE_POLL_ENV",
    "DrainNotice",
    "DrainWatcher",
]
