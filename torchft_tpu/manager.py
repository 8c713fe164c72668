"""Fault-tolerance Manager: the per-replica-group training-loop state machine.

Reference parity: torchft/manager.py.  The Manager owns everything the train
loop needs for per-step fault tolerance:

  - async quorum: each step starts a quorum computation on a background
    thread that overlaps with the forward/backward pass
    (torchft/manager.py:385-438);
  - reconfiguration: when the quorum id changes, the cross-group collective
    is rebuilt against a fresh store prefix (torchft/manager.py:502-509);
  - healing: behind replicas stream weights from a healthy peer through a
    CheckpointTransport while the healthy groups keep training
    (torchft/manager.py:511-568);
  - error latching: collective failures never raise into the train loop;
    they mark the step failed and are resolved at commit time
    (torchft/manager.py:262-383);
  - commit protocol: an optimizer step lands only when every local rank of
    the group voted success (torchft/manager.py:587-663).

TPU adaptations: the unit of data is a pytree leaf (jax.Array / numpy array)
rather than a torch tensor; cross-group traffic runs on a host-level
Collective over the DCN path (see torchft_tpu/collectives.py) because XLA
programs cannot change their collective world at runtime; the reference's
dedicated CUDA recovery stream maps to performing transfers on the quorum
thread while JAX async dispatch keeps device compute running.
"""

from __future__ import annotations

import json
import logging
import os
import re
import socket
import threading
import time
import uuid
from concurrent.futures import Future, ThreadPoolExecutor
from datetime import timedelta
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, TypeVar, cast

import numpy as np

from torchft_tpu._native import ManagerClient, ManagerServer, StoreClient, StoreServer
from torchft_tpu.checkpointing.transport import CheckpointTransport
from torchft_tpu.collectives import Collective, _is_bf16
from torchft_tpu.futures import completed_future, future_timeout

T = TypeVar("T")

MANAGER_ADDR_KEY: str = "manager_addr"
REPLICA_ID_KEY: str = "replica_id"

# Environment knobs (reference: torchft/manager.py:50,166-205).
TPUFT_LIGHTHOUSE_ENV: str = "TPUFT_LIGHTHOUSE"
TPUFT_MANAGER_PORT_ENV: str = "TPUFT_MANAGER_PORT"
# Cap on how many donors one healer stripes a fetch across.  More donors =
# more aggregate bandwidth (each serves a disjoint byte range) but also more
# connections per heal; 4 saturates typical host NICs long before the donor
# pool does.
_MAX_HEAL_DONORS = 4
# Heal-retry pacing (docs/api.md): after a FAILED heal fetch the next
# quorum's retry waits a decorrelated-jitter backoff (ha/backoff.py) so a
# flapping donor — or a donor whose serving window is briefly busy — cannot
# turn every quorum round into a zero-delay heal storm.  base/cap seconds;
# reset on the first successful fetch.
TPUFT_HEAL_BACKOFF_BASE_ENV: str = "TPUFT_HEAL_BACKOFF_BASE_S"
TPUFT_HEAL_BACKOFF_CAP_ENV: str = "TPUFT_HEAL_BACKOFF_CAP_S"


class WorldSizeMode(Enum):
    """How the effective batch/world size behaves as replica groups come and
    go (reference: WorldSizeMode, torchft/manager.py:56-71)."""

    DYNAMIC = 0
    FIXED_WITH_SPARES = 1


class ExceededMaxRetriesError(RuntimeError):
    """Raised by should_commit after max_retries consecutive failed commits
    (reference: torchft/manager.py:652-661)."""


class Manager:
    """Fault tolerance manager for one local rank of one replica group."""

    def __init__(
        self,
        collective: Collective,
        load_state_dict: Optional[Callable[[T], None]],
        state_dict: Optional[Callable[[], T]],
        min_replica_size: int,
        use_async_quorum: bool = True,
        timeout: timedelta = timedelta(seconds=60),
        quorum_timeout: timedelta = timedelta(seconds=60),
        connect_timeout: timedelta = timedelta(seconds=10),
        rank: Optional[int] = None,
        world_size: Optional[int] = None,
        world_size_mode: WorldSizeMode = WorldSizeMode.DYNAMIC,
        fixed_world_size: Optional[int] = None,
        store_addr: Optional[str] = None,
        store_port: Optional[int] = None,
        external_store_addr: Optional[str] = None,
        lighthouse_addr: Optional[str] = None,
        replica_id: Optional[str] = None,
        manager_bind: Optional[str] = None,
        heartbeat_interval: timedelta = timedelta(milliseconds=100),
        checkpoint_transport: Optional[CheckpointTransport] = None,
        init_sync: bool = True,
        max_retries: Optional[int] = None,
    ) -> None:
        """
        Args:
            collective: reconfigurable cross-group collective (data plane).
            load_state_dict: applies a user state dict fetched from a peer.
            state_dict: captures the user state dict to serve to peers.
            min_replica_size: minimum replica groups for a committable step.
            use_async_quorum: overlap quorum with forward/backward.
            rank/world_size: local rank / ranks per group (env: RANK,
                WORLD_SIZE).
            store_addr/store_port: host + port for the group's rendezvous
                store, created by local rank 0 (env: MASTER_ADDR/MASTER_PORT).
            external_store_addr: use an existing store (tests / shared infra).
            lighthouse_addr: lighthouse RPC address (env: TPUFT_LIGHTHOUSE).
                A comma-separated list fails over across an HA replica
                set.  Under a federated control plane this names the
                REGION's child lighthouse(s) — byte-for-byte the same
                config as a flat deployment; managers never learn the
                root exists (docs/wire.md "Federation").
            replica_id: stable replica-group id; a ":uuid" suffix is added so
                fast restarts look like new members (torchft/manager.py:230-238).
            init_sync: sync weights from replica 0 at step 0.
            max_retries: consecutive failed commits before giving up.
        """
        start_ns = time.monotonic_ns()  # the `manager_start` sub-span opens here
        self._load_state_dict_fns: Dict[str, Callable] = {}
        self._user_state_dicts: Dict[str, Callable] = {}
        if load_state_dict is not None:
            self._load_state_dict_fns["default"] = load_state_dict
        if state_dict is not None:
            self._user_state_dicts["default"] = state_dict

        self._collective = collective
        self._min_replica_size = min_replica_size
        self._use_async_quorum = use_async_quorum
        self._timeout = timeout
        self._quorum_timeout = quorum_timeout
        self._connect_timeout = connect_timeout
        self._world_size_mode = world_size_mode
        self._init_sync = init_sync
        self._max_retries = max_retries
        self._commit_failures = 0

        self._rank: int = rank if rank is not None else int(os.environ.get("RANK", 0))
        group_world_size = world_size if world_size is not None else int(
            os.environ.get("WORLD_SIZE", 1)
        )
        self._group_world_size: int = group_world_size
        self._fixed_world_size = fixed_world_size

        lighthouse_addr = lighthouse_addr or os.environ.get(TPUFT_LIGHTHOUSE_ENV, "")
        # May be a comma-separated HA replica set ("host1:p,host2:p", see
        # docs/wire.md "HA lighthouse"): the native ManagerServer fails its
        # quorum/heartbeat calls over across the list and follows "not the
        # leader" redirects, and every Python-side dial below goes through
        # the failover-aware LighthouseClient.  Kept for the
        # cooperative-drain notice (begin_drain dials the lighthouse
        # directly with this group's exact incarnation id).
        self._lighthouse_addr = lighthouse_addr

        self._store_server: Optional[StoreServer] = None
        self._manager_server: Optional[ManagerServer] = None

        if external_store_addr is not None:
            store_address = external_store_addr
            self._store = StoreClient(store_address)
        else:
            store_host = store_addr or os.environ.get("MASTER_ADDR", "localhost")
            port = store_port if store_port is not None else int(
                os.environ.get("MASTER_PORT", 0)
            )
            if self._rank == 0:
                self._store_server = StoreServer(bind=f"[::]:{port}")
                actual_port = self._store_server.address().rsplit(":", 1)[1]
                store_address = f"{store_host}:{actual_port}"
            else:
                if port == 0:
                    raise ValueError(
                        "non-zero store_port (or MASTER_PORT) required for rank > 0"
                    )
                store_address = f"{store_host}:{port}"
            self._store = StoreClient(
                store_address, connect_timeout_ms=int(connect_timeout.total_seconds() * 1000)
            )
        self._store_address = store_address

        if self._rank == 0:
            if replica_id is None:
                replica_id = os.environ.get("REPLICA_GROUP_ID", socket.gethostname())
            # Suffix survives fast restarts: a restarted group must look like
            # a brand-new member to the lighthouse (torchft/manager.py:230-238).
            new_uuid = str(uuid.uuid4())
            replica_id = f"{replica_id}:{new_uuid}" if replica_id else new_uuid
            bind = manager_bind or "[::]:" + os.environ.get(TPUFT_MANAGER_PORT_ENV, "0")
            if not lighthouse_addr:
                raise ValueError(
                    f"lighthouse_addr or ${TPUFT_LIGHTHOUSE_ENV} must be set"
                )
            self._manager_server = ManagerServer(
                replica_id=replica_id,
                lighthouse_addr=lighthouse_addr,
                bind=bind,
                store_addr=store_address,
                world_size=group_world_size,
                heartbeat_interval_ms=int(heartbeat_interval.total_seconds() * 1000),
                connect_timeout_ms=int(connect_timeout.total_seconds() * 1000),
            )
            self._store.set(MANAGER_ADDR_KEY, self._manager_server.address().encode())
            self._store.set(REPLICA_ID_KEY, replica_id.encode())

        addr = self._store.get(
            MANAGER_ADDR_KEY, wait=True,
            timeout_ms=int(connect_timeout.total_seconds() * 1000),
        )
        assert addr is not None
        # Captured so the healing path dials peer managers through the same
        # (mockable) factory.
        self._manager_client_factory = ManagerClient
        self._client = self._manager_client_factory(
            addr.decode(), connect_timeout_ms=int(connect_timeout.total_seconds() * 1000)
        )
        rid = self._store.get(REPLICA_ID_KEY, wait=True)
        assert rid is not None
        self._replica_id = rid.decode()

        self._checkpoint_transport = checkpoint_transport

        self._step = 0
        self._quorum_id = -1
        self._batches_committed = 0
        self._healing = False
        self._errored: Optional[Exception] = None
        self._pending_work: List[Future] = []
        self._pending_state_dict: Optional[Dict[str, object]] = None
        self._quorum_future: Optional[Future] = None
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="tpuft_quorum"
        )

        self._participating_replica_rank: Optional[int] = None
        self._participating_replica_world_size: int = 0

        # Causal trace id of the step in flight (docs/wire.md "Causal trace
        # ids"): minted once per quorum round and carried on every control
        # RPC — Quorum (via the native ManagerServer to the lighthouse),
        # CheckpointMetadata, ShouldCommit, Drain — so the server-side
        # flight recorders can be joined to this replica's span stream.
        self._trace_id: str = ""

        # Cooperative-drain state (torchft_tpu/drain): set once by
        # begin_drain, observed by the train loop between steps.
        self._drain_notice = None
        self._drain_watcher = None
        self._drain_lock = threading.Lock()

        self._logger = _ManagerLogger(self, self._replica_id, self._rank)
        # JSONL event stream when TPUFT_METRICS_PATH is set (no-op otherwise).
        from torchft_tpu.metrics import MetricsLogger
        from torchft_tpu.obs.spans import SpanTracker, StepTimeStats

        self._metrics = MetricsLogger.from_env(self._replica_id)
        # Step-scoped trace spans over the same stream (obs/spans.py): each
        # phase below runs inside a span, and the span's single monotonic
        # measurement also feeds the legacy *_ms fields.
        self._spans = SpanTracker(self._metrics)
        # The process's program builds (obs/builds.py) carry this Manager's
        # step from here on, and leave through this tracker's stream.
        from torchft_tpu.obs import builds

        builds.register(step_of=self.current_step)
        # Goodput ledger (obs/ledger.py): every committed step's wall time
        # classified into the pinned cause taxonomy at the commit vote —
        # the per-step vector rides step_summary, the cumulative counters
        # ride heartbeat fields 14-16 into the lighthouse's cluster ledger
        # (/goodput.json, tpuft_goodput_ratio, tpuft_lost_seconds_total).
        from torchft_tpu.obs.ledger import StepLedger

        self._ledger = StepLedger()
        # The ledger's own commit clock + failed-attempt phase buffer: a
        # failed vote resets the STEP-TIME clock (_last_commit_mono —
        # a retry-spanning interval would misread as slowness) but the
        # ledger must still charge the retried interval, so it keeps its
        # own last-commit mark and accumulates the failed attempts'
        # phases until the step finally commits (the documented rule in
        # obs/ledger.py: the retries' charges land in the eventual
        # committed interval).
        self._ledger_prev_commit_mono: Optional[float] = None
        self._ledger_pending_phases: Dict[str, float] = {}
        # Straggler-sentinel telemetry: rolling busy-time per committed step
        # (EWMA + p50/p99), pushed onto lighthouse heartbeats via SetStatus
        # so the cluster-level health scoring sees this replica's pace.
        self._step_stats = StepTimeStats()
        self._last_commit_mono: Optional[float] = None
        # Allreduce data-plane throughput: payload bytes and the first-issue
        # timestamp of the step in flight, summarized at commit time as
        # allreduce_gb_per_s (step_summary field + the lighthouse's
        # tpuft_allreduce_gb_per_s heartbeat gauge).  End-to-end rate — from
        # first issue to drain — so overlap wins (bucket pipelining, ring
        # lanes) show up here, not just in microbenchmarks.
        self._ar_lock = threading.Lock()
        self._ar_bytes = 0
        self._ar_t_first: Optional[float] = None
        self._ar_t_last: Optional[float] = None
        self._ar_gbps = 0.0
        # Device<->host transfer bytes for the step in flight, noted by the
        # data-plane layers above (GradientAverager's note_d2h/note_h2d)
        # and flushed into step_summary — with device wire prep the D2H
        # side should read ~wire bytes (half of f32), and the H2D side
        # shows the scatter-back cost the allreduce_h2d span charges.
        self._d2h_bytes = 0
        self._h2d_bytes = 0
        # Lifetime (monotonic) transfer totals for the worker /metrics
        # endpoint — the per-step fields above reset at start_quorum.
        self._d2h_bytes_total = 0
        self._h2d_bytes_total = 0
        # Per-neighbor link health (docs/architecture.md "Data-plane
        # observability"): EWMA goodput + hop RTT derived at each commit
        # from the ring engines' hop-telemetry deltas, pushed on heartbeat
        # fields 11-13 for the lighthouse's slow-link sentinel.  The
        # previous cumulative snapshot closes each step's delta window;
        # reset-aware (lane counters zero per configure()).
        self._link_prev: Optional[Dict[str, float]] = None
        self._link_ewma: Dict[str, float] = {}
        # Extra fields wrappers note onto the step in flight's step_summary
        # (note_summary_fields) — the semisync engine's per-round fragment
        # counts and wire bytes ride here.  Cleared with the other per-step
        # accounting at start_quorum and flushed at the commit vote.
        self._summary_extra: Dict[str, object] = {}

        # Elastic batch engine (docs/architecture.md "Elastic scale"): when
        # TPUFT_ELASTIC_GLOBAL_BATCH is set, every quorum transition rescales
        # this group's batch share so the GLOBAL batch stays constant across
        # membership churn — join/leave changes throughput, never the
        # training trajectory's effective batch.  The plan for the current
        # participant count is exposed via elastic_plan() (train loops read
        # group_batch/accum_steps from it) and stamped into every committed
        # step record.  Membership callbacks fire on the quorum thread after
        # the collective reconfigures, before the step proceeds — data
        # loaders re-shard there.  Lazy import: ddp imports Manager at
        # module level, so the reverse import must happen at runtime.
        self._elastic = None
        self._elastic_plan: Optional[Dict[str, object]] = None
        try:
            from torchft_tpu.ddp import ElasticBatchScaler

            self._elastic = ElasticBatchScaler.from_env()
        except Exception:  # noqa: BLE001 — elastic must not break startup
            self._elastic = None
        self._membership_callbacks: List[Callable[[Dict[str, object]], None]] = []
        self._last_participants: Optional[List[int]] = None

        # Erasure-coded peer state (torchft_tpu/ec, docs/architecture.md
        # "Donor-free healing"): when TPUFT_EC_K > 0 and the checkpoint
        # transport can host a shard store, every committed step's state is
        # additionally encoded into k+m Reed-Solomon shards on the
        # transport's background snapshotter, and a heal whose donors are
        # unreachable reconstructs from any k surviving shard holders.
        self._ec = None
        from torchft_tpu.ec import ECConfig

        ec_cfg = ECConfig.from_env()
        if (
            ec_cfg.enabled
            and self._checkpoint_transport is not None
            and hasattr(self._checkpoint_transport, "attach_shard_store")
        ):
            from torchft_tpu.ec import ECPlane

            self._ec = ECPlane(
                ec_cfg,
                spans=self._spans,
                metrics=self._metrics,
                resolve_peer=self._dial_peer_transport,
                push_timeout=self._timeout.total_seconds(),
            )

        # Heal-retry pacing: decorrelated jitter between consecutive heal
        # attempts after a failure (satellite of the EC work; see the env
        # docs above).  _heal_failures counts consecutive failed fetches.
        from torchft_tpu.ha.backoff import DecorrelatedBackoff

        heal_base_s = _env_float(TPUFT_HEAL_BACKOFF_BASE_ENV, 0.2)
        if heal_base_s <= 0:
            # Same loud-but-safe policy as _env_float: a bad tuning value
            # must never abort recovery (DecorrelatedBackoff rejects <= 0).
            self._logger.warn(
                f"ignoring non-positive {TPUFT_HEAL_BACKOFF_BASE_ENV}="
                f"{heal_base_s}; using default 0.2"
            )
            heal_base_s = 0.2
        self._heal_backoff = DecorrelatedBackoff(
            base_s=heal_base_s,
            cap_s=_env_float(TPUFT_HEAL_BACKOFF_CAP_ENV, 5.0),
        )
        self._heal_failures = 0
        self._ec_enqueued_step = -1

        # Unified worker /metrics endpoint (obs/prom.py): step pace,
        # transfer totals, monotonic lane/hop counters (lane_totals), the
        # link-health EWMAs, plus any subsystem sections (the semisync
        # plane registers its tpuft_semisync_* render here).  Pull-based:
        # the provider snapshot runs at SCRAPE time, so training pays
        # nothing while nobody scrapes.  serve() is a no-op unless
        # TPUFT_WORKER_METRICS_PORT is set.
        from torchft_tpu.obs.prom import WorkerMetrics

        self._worker_metrics = WorkerMetrics(
            replica_id=self._replica_id,
            provider=self._worker_metrics_snapshot,
        )
        # Per-hop wire-byte + latency histograms, folded at SCRAPE time
        # from the ring engines' retained hop timeline — no new recording
        # cost on the data path (docs/wire.md "Worker /metrics").  The
        # cumulative buckets live here (scrape-thread-only state) so the
        # exposed histograms stay monotonic over the sliding ring.
        self._hop_hist: Dict[tuple, dict] = {}  # (tier, lane) -> buckets
        self._hop_hist_last_ts = 0.0
        self._hop_hist_lock = threading.Lock()
        self._worker_metrics.add_section(self._render_hop_histograms)
        self._worker_metrics.serve()

        self._wire_transport_spans()
        # The control plane's share of a start (obs/spans.SUBSPANS): a
        # sub-span, so it enters no step's phases, ledger or busy time.
        self._spans.note_sub("manager_start", self._step, start_ns, time.monotonic_ns())

    def _wire_transport_spans(self) -> None:
        """Hands the span tracker to transports that emit their own spans —
        the HTTP transport's background snapshotter emits ``snapshot`` spans
        so obs.report can show the flatten overlapping the train step — and
        wires the EC plane's shard store + encode hook onto the transport."""
        transport = self._checkpoint_transport
        if transport is not None and hasattr(transport, "set_span_tracker"):
            transport.set_span_tracker(self._spans)
        if (
            self._ec is not None
            and transport is not None
            and hasattr(transport, "attach_shard_store")
        ):
            transport.attach_shard_store(self._ec.store)
            transport.set_snapshot_hook(self._ec.on_snapshot)

    def _dial_peer_transport(self, manager_addr: str) -> str:
        """Resolves a peer manager's checkpoint-transport base URL for this
        local rank (the shard endpoints live on the same server).  Used by
        the EC plane — cached there per address."""
        client = self._manager_client_factory(
            manager_addr,
            connect_timeout_ms=int(self._connect_timeout.total_seconds() * 1000),
        )
        try:
            return client._checkpoint_metadata(
                self._rank,
                timeout_ms=int(self._timeout.total_seconds() * 1000),
                trace_id=self._trace_id,
            )
        finally:
            client.close()

    # -- registration -------------------------------------------------------

    def register_state_dict_fn(
        self, key: str, load: Callable[[object], None], save: Callable[[], object]
    ) -> None:
        """Registers an additional named state-dict provider (wrappers like
        LocalSGD/DiLoCo register theirs here)."""
        self._load_state_dict_fns[key] = load
        self._user_state_dicts[key] = save

    def set_checkpoint_transport(self, transport: CheckpointTransport) -> None:
        self._checkpoint_transport = transport
        self._wire_transport_spans()

    # -- quorum -------------------------------------------------------------

    def start_quorum(
        self,
        allow_heal: bool = True,
        shrink_only: bool = False,
        timeout: Optional[timedelta] = None,
    ) -> None:
        """Starts the next-step quorum computation, possibly async.

        Must be called at the top of every step (the optimizer wrapper does
        it from zero_grad).  Reference: torchft/manager.py:385-438.
        """
        # Wait for the previous quorum to finish so state isn't mutated
        # concurrently (torchft/manager.py:411-412).
        if self._quorum_future is not None:
            self._quorum_future.result()

        self._errored = None
        self._healing = False
        self._pending_work = []
        with self._ar_lock:
            # Defensive: a loop that skipped should_commit must not bleed
            # its bytes into the next step's throughput summary.
            self._ar_bytes = 0
            self._ar_t_first = None
            self._ar_t_last = None
            self._d2h_bytes = 0
            self._h2d_bytes = 0
            self._summary_extra = {}

        # Feed the erasure encoder: at the top of a step the user state IS
        # the last committed step's state (a failed vote discarded its
        # speculative update), so enqueue it for the background snapshotter
        # as a NON-serving snapshot — the flatten + k+m encode + parity
        # push all run off the train thread (the enqueue itself is ~µs),
        # and the serving slot stays quorum-paced.  Deduped per step so
        # failed-commit retries don't re-flatten identical state.
        if (
            self._ec is not None
            and self._step != self._ec_enqueued_step
            and self._ec.wants_snapshot(self._step)
            and hasattr(self._checkpoint_transport, "enqueue_snapshot")
        ):
            self._checkpoint_transport.enqueue_snapshot(
                self._step, self._manager_state_dict(), serve=False
            )
            self._ec_enqueued_step = self._step

        self._quorum_future = self._executor.submit(
            self._async_quorum,
            allow_heal=allow_heal,
            shrink_only=shrink_only,
            quorum_timeout=timeout or self._quorum_timeout,
        )
        if not self._use_async_quorum:
            self.wait_quorum()
            if self._healing:
                # Sync mode applies the fetched state dict eagerly and is
                # then fully healed: the step runs with good weights, so the
                # commit path must not try to re-apply
                # (torchft/manager.py:429-438).
                self._apply_pending_state_dict()
                self._healing = False

    def wait_quorum(self) -> None:
        """Blocks until the current quorum completes (torchft/manager.py:440-449)."""
        assert self._quorum_future is not None, "call start_quorum before wait_quorum"
        if self._quorum_future.done():
            self._quorum_future.result()
            return
        # What THIS thread waits for the quorum (the ``quorum`` span is the
        # quorum thread's RPC): every caller passes through here, and a
        # settled quorum records nothing, so the waits of one step add up.
        with self._spans.sub("quorum_wait", step=self._step):
            self._quorum_future.result()

    def _async_quorum(
        self, allow_heal: bool, shrink_only: bool, quorum_timeout: timedelta
    ) -> None:
        try:
            self._quorum_inner(allow_heal, shrink_only, quorum_timeout)
        except Exception as e:  # noqa: BLE001
            if "is draining" in str(e):
                # The LIGHTHOUSE marked this incarnation draining (operator
                # /replica/<id>/drain, or the straggler sentinel's
                # auto-drain) and refuses its joins.  That is a drain
                # notice delivered through the quorum path: begin the
                # cooperative exit so the train loop finishes this step and
                # leaves cleanly instead of flailing through failed commits
                # until something kills it.  "is draining" is the grep
                # contract with both HandleQuorum message sites in
                # native/src/lighthouse.cc (the framed-TCP wire carries
                # status + message only, no structured error payload);
                # pinned by tests/test_straggler.py.
                from torchft_tpu.drain import DrainNotice

                # The rejection may carry the announced grace remainder as
                # a "(deadline_ms=N)" suffix (root-issued drains plumb the
                # operator's deadline down through the digest response);
                # pace the cooperative exit to it instead of a fixed 30 s.
                m = re.search(r"deadline_ms=(\d+)", str(e))
                grace_s = int(m.group(1)) / 1000.0 if m else 30.0
                self._logger.warn(
                    "lighthouse declared this replica draining; beginning "
                    f"cooperative exit (grace {grace_s:.1f}s)"
                )
                self.begin_drain(
                    DrainNotice(source="lighthouse", deadline=time.time() + grace_s)
                )
            else:
                self._logger.exception(f"quorum failed: {e}")
            self.report_error(e)
            # Not participating this step.
            self._participating_replica_rank = None
            self._participating_replica_world_size = 0

    def _quorum_inner(
        self, allow_heal: bool, shrink_only: bool, quorum_timeout: timedelta
    ) -> None:
        metadata = (
            self._checkpoint_transport.metadata() if self._checkpoint_transport else ""
        )
        self._set_status("quorum")
        # Mint this step's causal trace id; the span record carries it so
        # obs/report.py can join the client-observed quorum wait against
        # the lighthouse flight recorder's server-side formation span.
        from torchft_tpu.obs.flight import mint_trace_id

        trace_id = mint_trace_id(
            self._spans.slice_gen, self._replica_id, self._step
        )
        self._trace_id = trace_id
        with self._spans.span(
            "quorum", step=self._step, trace_id=trace_id
        ) as sp_quorum:
            quorum = self._client._quorum(
                group_rank=self._rank,
                step=self._step,
                checkpoint_metadata=metadata,
                shrink_only=shrink_only,
                timeout_ms=int(quorum_timeout.total_seconds() * 1000),
                init_sync=self._init_sync,
                commit_failures=self._commit_failures,
                trace_id=trace_id,
            )

        quorum_id = quorum.quorum_id
        replica_rank = quorum.replica_rank
        replica_world_size = quorum.replica_world_size
        recover_src_replica_rank = quorum.recover_src_replica_rank
        store_address = quorum.store_address
        max_step = quorum.max_step
        heal = quorum.heal

        if self._ec is not None:
            # Refresh the EC plane's placement membership from the full
            # participant list (fields 15-16).  Empty against a pre-EC
            # server — the plane then keeps its previous view, which still
            # probes correctly (placement is a hint; reconstruction sweeps
            # holder inventories regardless).
            p_ranks = list(getattr(quorum, "participant_replica_ranks", []) or [])
            p_addrs = list(
                getattr(quorum, "participant_manager_addresses", []) or []
            )
            if p_ranks and len(p_ranks) == len(p_addrs):
                self._ec.set_peers(p_ranks, p_addrs, replica_rank)

        # Participation bookkeeping (torchft/manager.py:480-500): with async
        # quorum (or healing disabled) only the up-to-date groups participate
        # this step — a healing group's max_replica_rank is None; with sync
        # quorum every group is healthy by the time the step runs.
        if self._use_async_quorum or not allow_heal:
            self._participating_replica_rank = quorum.max_replica_rank
            self._participating_replica_world_size = quorum.max_world_size
        else:
            self._participating_replica_rank = replica_rank
            self._participating_replica_world_size = replica_world_size

        # FIXED_WITH_SPARES pins the divisor; extra live groups are spares
        # contributing zeros.
        if self._world_size_mode == WorldSizeMode.FIXED_WITH_SPARES:
            fixed = self._fixed_world_size or self._min_replica_size
            self._participating_replica_world_size = min(
                self._participating_replica_world_size, fixed
            )
            if (
                self._participating_replica_rank is not None
                and self._participating_replica_rank >= fixed
            ):
                self._participating_replica_rank = None

        self._metrics.emit(
            "quorum",
            step=self._step,
            quorum_id=quorum_id,
            replica_rank=replica_rank,
            replica_world_size=replica_world_size,
            participating=self._participating_replica_world_size,
            heal=heal,
            # Same measurement the span record carries: where a slow step
            # went (quorum wait vs reconfigure vs heal) without a profiler.
            quorum_ms=sp_quorum.duration_ms,
        )

        if quorum_id != self._quorum_id:
            # Unique store prefix per (quorum, local rank): local rank r of
            # every group forms one ring (torchft/manager.py:502-509).
            prefix = f"tpuft/{quorum_id}/{self._rank}"
            self._logger.info(
                f"reconfiguring collective for quorum {quorum_id} "
                f"(rank {replica_rank}/{replica_world_size})"
            )
            with self._spans.span("configure", step=self._step) as sp_cfg:
                self._collective.configure(
                    f"{store_address}/{prefix}", replica_rank, replica_world_size
                )
            self._quorum_id = quorum_id
            # The collective records how the configure went (full rendezvous
            # vs incremental lane reuse).  The wrappers don't proxy unknown
            # attributes, so read defensively.
            lc = getattr(self._collective, "last_configure", None) or {}
            self._metrics.emit(
                "reconfigure",
                step=self._step,
                quorum_id=quorum_id,
                replica_rank=replica_rank,
                replica_world_size=replica_world_size,
                configure_ms=sp_cfg.duration_ms,
                mode=lc.get("mode", "unknown"),
                reused_lanes=lc.get("reused_lanes", 0),
                opened_lanes=lc.get("opened_lanes", 0),
            )
            self._on_membership_change(
                quorum, quorum_id, replica_world_size, sp_cfg.duration_ms, lc
            )

        if allow_heal and self._checkpoint_transport is not None:
            # Recovery source: serve our weights (torchft/manager.py:511-528).
            # Pull-based transports serve the FULL recovering set (striped
            # multi-donor fetch pulls disjoint byte ranges from every donor);
            # point-to-point transports serve only primary assignments —
            # their sends block until the healer's matching recv.
            if self._checkpoint_transport.serves_all_donors:
                serve_dsts = list(
                    getattr(quorum, "recover_dst_replica_ranks_all", None)
                    or quorum.recover_dst_replica_ranks
                )
                # Force-recover symmetry: when we heal WHILE already holding
                # the max_step state (commit_failures re-fetch, step ==
                # max_step), our peers may be in exactly the same position —
                # a cluster-wide failed step (e.g. a replica killed
                # mid-allreduce fails EVERY group's commit) force-recovers
                # everyone, and each group's assigned donor is another
                # force-recovering group.  commit_failures is request-local,
                # so donors cannot be told to serve us; without this, nobody
                # opens a serving window and the mutual heal deadlocks until
                # timeout, every quorum, forever.  Serving is passive for
                # pull transports and our state IS the committed max_step
                # state (a failed vote discards the speculative update), so
                # opening the window is always safe.
                if not serve_dsts and heal and max_step == self._step:
                    # Our own donor rotation names the peers most likely
                    # healing from us (HTTP serving ignores the dst list —
                    # it is passive — this only makes the log truthful).
                    serve_dsts = list(quorum.recover_src_replica_ranks) or [
                        quorum.recover_src_replica_rank
                    ]
            else:
                serve_dsts = list(quorum.recover_dst_replica_ranks)
            if serve_dsts:
                self._logger.info(
                    f"serving checkpoint at step {max_step} to replicas "
                    f"{serve_dsts}"
                )
                self._checkpoint_transport.send_checkpoint(
                    dst_ranks=serve_dsts,
                    step=max_step,
                    state_dict=self._manager_state_dict(),
                    timeout=self._timeout.total_seconds(),
                )
            # Recovery destination: fetch weights from the assigned donors —
            # striped across every healthy max-step group the quorum listed,
            # so heal bandwidth scales with the donor count and one donor
            # dying mid-heal degrades instead of aborting
            # (torchft/manager.py:530-568 is the single-donor ancestor).
            if heal:
                self._healing = True
                src_rank = cast(int, recover_src_replica_rank)
                donor_ranks = list(quorum.recover_src_replica_ranks) or [src_rank]
                donor_addrs = [
                    a
                    for a in (
                        list(quorum.recover_src_manager_addresses)
                        or [quorum.recover_src_manager_address]
                    )
                    if a
                ]
                donor_ranks = donor_ranks[:_MAX_HEAL_DONORS]
                donor_addrs = donor_addrs[:_MAX_HEAL_DONORS]
                if not self._checkpoint_transport.serves_all_donors:
                    # Point-to-point transports: only the PRIMARY donor is
                    # sending to us — failing over to another donor would
                    # recv from a peer with no matching send (hang, then
                    # timeout) instead of failing fast and re-planning on
                    # the next quorum.
                    donor_ranks = donor_ranks[:1]
                    donor_addrs = donor_addrs[:1]
                if self._heal_failures > 0:
                    # Heal-retry backoff: consecutive failed fetches pace
                    # their retries with decorrelated jitter so a flapping
                    # donor cannot make every quorum round a heal storm.
                    delay = self._heal_backoff.next()
                    self._logger.warn(
                        f"heal retry #{self._heal_failures}: backing off "
                        f"{delay:.2f}s before re-fetching"
                    )
                    time.sleep(delay)
                self._set_status("heal")
                prefer_ec = self._ec is not None and self._ec.config.mode == "prefer"
                state: Optional[Dict[str, object]] = None
                fetch_err: Optional[Exception] = None
                if not prefer_ec and donor_addrs:
                    state, fetch_err = self._heal_from_donors(
                        src_rank, max_step, donor_ranks, donor_addrs
                    )
                elif not donor_addrs:
                    fetch_err = RuntimeError(
                        "quorum response names no reachable donor"
                    )
                if state is None and self._ec is not None:
                    # Donor-free fallback (or "prefer" mode's first choice):
                    # reconstruct the max-step state from any k surviving
                    # shard holders — no serving window, no donor rotation.
                    state = self._heal_from_shards(max_step, fetch_err)
                if state is None and prefer_ec and donor_addrs:
                    # prefer mode degrades to the donor path when coverage
                    # is short (fresh cluster, EC disabled on peers).
                    state, fetch_err = self._heal_from_donors(
                        src_rank, max_step, donor_ranks, donor_addrs
                    )
                if state is None:
                    self._heal_failures += 1
                    raise fetch_err if fetch_err is not None else RuntimeError(
                        "heal failed with no donors and no shard coverage"
                    )
                self._heal_failures = 0
                self._heal_backoff.reset()
                self._pending_state_dict = state
                # Fast-forward to the healed step (torchft/manager.py:562-568).
                self._step = max_step
        elif heal:
            self._healing = True

        # Quorum (and any heal fetch) resolved: the group is training until
        # the commit vote — without this the async-quorum overlap leaves the
        # replica labeled "quorum"/"heal" for the whole compute phase.
        self._set_status("step")

    def _heal_from_donors(
        self,
        src_rank: int,
        max_step: int,
        donor_ranks: List[int],
        donor_addrs: List[str],
    ) -> tuple:
        """The striped multi-donor fetch path: (state, None) on success,
        (None, error) on failure — the caller decides whether an erasure
        reconstruction can still save this quorum round."""
        # "healing from replica" is a grep contract: tests and the verify
        # recipe read the heal out of a group's log by this phrase.
        self._logger.info(
            f"healing from replica {src_rank} at step {max_step} via "
            f"{len(donor_addrs)} donor(s) {list(zip(donor_ranks, donor_addrs))}"
        )
        self._metrics.emit(
            "heal_start",
            src_rank=src_rank,
            max_step=max_step,
            n_donors=len(donor_addrs),
        )
        try:
            with self._spans.span(
                "heal", step=max_step, src_rank=src_rank
            ) as sp_heal:
                donor_metas, donor_used = self._resolve_donor_metadatas(
                    donor_ranks, donor_addrs
                )
                state = self._checkpoint_transport.recv_checkpoint(
                    src_rank=donor_used[0],
                    metadata=(
                        donor_metas if len(donor_metas) > 1 else donor_metas[0]
                    ),
                    step=max_step,
                    timeout=self._timeout.total_seconds(),
                )
            self._metrics.emit(
                "heal_fetched",
                src_rank=donor_used[0],
                step=max_step,
                heal_ms=sp_heal.duration_ms,
                n_donors=len(donor_metas),
            )
            return cast(Dict[str, object], state), None
        except Exception as e:  # noqa: BLE001 — the caller may still
            # reconstruct from erasure shards this same round
            self._logger.warn(f"donor heal fetch failed: {e}")
            return None, e

    def _heal_from_shards(
        self, max_step: int, fetch_err: Optional[Exception]
    ) -> Optional[Dict[str, object]]:
        """Donor-free reconstruction: any k surviving shard holders ->
        the max-step state, installed through the exact same
        materialization the donor path uses (bitwise-equal by
        construction).  Returns None when coverage never reached k — the
        caller then latches the donor error and the next quorum retries."""
        assert self._ec is not None
        if max_step <= 0:
            # Step-0 init sync collapses the source set to participant 0's
            # (random-init) weights; no shard generation exists for it by
            # design (pre-sync states diverge) — donor path only.
            return None
        if fetch_err is not None:
            self._logger.warn(
                f"donor path exhausted ({fetch_err}); reconstructing step "
                f"{max_step} from erasure shards"
            )
        try:
            with self._spans.span("ec_reconstruct", step=max_step) as sp:
                meta, buffers, stats = self._ec.reconstruct_state(
                    max_step, timeout=self._timeout.total_seconds()
                )
                transport = self._checkpoint_transport
                if hasattr(transport, "materialize"):
                    state = transport.materialize(meta, buffers)
                else:
                    from torchft_tpu.checkpointing.serialization import (
                        unflatten_state_dict,
                    )

                    state = unflatten_state_dict(meta, buffers)
            self._metrics.emit(
                "ec_reconstruct",
                step=max_step,
                reconstruct_ms=sp.duration_ms,
                **{
                    k: v
                    for k, v in stats.items()
                    if k in ("holders", "probes", "corrupt", "fetch_errors",
                             "shards_used", "parity_used")
                },
            )
            self._logger.info(
                f"reconstructed step {max_step} from erasure shards "
                f"{stats.get('shards_used')} ({stats['holders']} holders, "
                f"{stats.get('parity_used', 0)} parity)"
            )
            return cast(Dict[str, object], state)
        except Exception as e:  # noqa: BLE001 — reconstruction is the
            # fallback; its failure must surface as a latched step error,
            # not a dead worker.
            self._logger.warn(f"erasure reconstruction failed: {e}")
            return None

    def _resolve_donor_metadatas(
        self, donor_ranks: List[int], donor_addrs: List[str]
    ) -> tuple:
        """Dials each donor's manager for its per-rank transport metadata,
        dropping donors that do not answer (a donor can die between the
        quorum and the heal; the stripe fetch then simply never includes
        it).  The dials run in parallel so one hung donor costs a single
        timeout, not a sum of timeouts, on the heal critical path.  Raises
        only when NO donor is reachable."""

        def dial(pair) -> str:
            return self._dial_peer_transport(pair[1])

        pairs = list(zip(donor_ranks, donor_addrs))
        metas: List[str] = []
        used: List[int] = []
        last_err: Optional[Exception] = None
        if len(pairs) == 1:
            outcomes = [self._try_call(dial, pairs[0])]
        else:
            with ThreadPoolExecutor(
                max_workers=len(pairs), thread_name_prefix="tpuft_donor_dial"
            ) as pool:
                outcomes = list(pool.map(lambda p: self._try_call(dial, p), pairs))
        for (rank_i, addr_i), (meta, err) in zip(pairs, outcomes):
            if err is None:
                metas.append(meta)
                used.append(rank_i)
            else:
                last_err = err
                self._logger.warn(f"donor {rank_i} ({addr_i}) unreachable: {err}")
        if not metas:
            raise RuntimeError(
                f"no heal donor reachable (tried {len(donor_addrs)}): {last_err}"
            )
        return metas, used

    @staticmethod
    def _try_call(fn, arg) -> tuple:
        """(result, None) or (None, exception) — lets a parallel map report
        per-item failures without aborting the batch."""
        try:
            return fn(arg), None
        except Exception as e:  # noqa: BLE001
            return None, e

    def _manager_state_dict(self) -> Dict[str, object]:
        """Full transferable state: user trees + manager bookkeeping
        (torchft/manager.py:677-694)."""
        return {
            "user": {k: fn() for k, fn in self._user_state_dicts.items()},
            "tpuft": self.state_dict(),
        }

    def _apply_pending_state_dict(self) -> None:
        """Applies a healed state dict to the user model (torchft/manager.py:570-585)."""
        assert self._healing, "apply_pending_state_dict called without healing"
        if self._pending_state_dict is None:
            # Quorum thread may still be fetching.
            self.wait_quorum()
        if self._pending_state_dict is None:
            # The heal FETCH failed (donors died or their serving windows
            # were busy; the quorum thread latched the error).  Degrade,
            # never crash: skip the apply, make sure an error is latched so
            # this step's commit vote fails, and let the NEXT quorum retry
            # the heal against the then-healthy donor set.  The assert that
            # used to live here turned a transient donor 503 into the death
            # of a worker the cluster had already paid to respawn — at
            # O(100) groups a single busy donor window killed healers
            # fleet-wide (found by the scale sweep's preemption-wave cell).
            if self._errored is None:
                self.report_error(RuntimeError("healing checkpoint was not fetched"))
            self._logger.warn(
                "healed state dict was never fetched; failing this step's "
                "commit and retrying the heal at the next quorum"
            )
            return
        self._logger.info("applying healed state dict")
        user = cast(Dict[str, object], self._pending_state_dict["user"])
        for key, value in user.items():
            if key in self._load_state_dict_fns:
                self._load_state_dict_fns[key](value)
        self.load_state_dict(cast(Dict[str, int], self._pending_state_dict["tpuft"]))
        self._pending_state_dict = None

    # -- allreduce ----------------------------------------------------------

    def allreduce(
        self,
        tensor,
        should_average: bool = True,
        allow_wire_compression: bool = True,
        wire_codec: Optional[str] = None,
        donate: bool = False,
        bucket: Optional[int] = None,
    ) -> Future:
        """Fault-tolerant gradient allreduce across replica groups.

        Accepts a jax.Array or numpy array; returns a Future resolving to the
        averaged array of the same type/sharding.  Never raises — failures
        resolve to the unmodified input and latch the step error
        (reference: torchft/manager.py:262-323).

        allow_wire_compression=False exempts this call from lossy wire
        encodings (TCPCollective wire_dtype="bf16") — required when the
        payload is parameters rather than gradients (LocalSGD sync).

        wire_codec selects an explicit per-call wire encoding
        (collectives.WIRE_CODECS; "int8" = per-chunk-scale symmetric int8,
        ~0.25x the f32 wire) — the semisync pseudogradient plane's knob.
        The kwarg is only forwarded when set, so swapped-in collectives
        (tests, wrappers) keep the bare allreduce signature they mock.

        donate=True hands the host buffer's ownership to the collective:
        it may reduce in place and return the same storage, skipping the
        defensive copy.  Only safe when the caller does not reuse the
        input after the call (wire/fragment staging buffers).  On failure
        the future still resolves to the UNMODIFIED input semantics the
        caller observes today — the collective's contract is that a failed
        op never publishes a half-reduced buffer as the result.  Forwarded
        to the collective only when True, same mock-compat rule as
        wire_codec.  The average is then taken in that storage too: a
        successful result may be a view of the donated buffer, and is
        never, by identity, the input itself.

        bucket labels the op's ``ring_queue`` / ``ring_run`` / ``normalize``
        sub-spans (the GradientAverager passes its bucket index); it is
        never forwarded and changes nothing the op does.
        """
        if self.errored() is not None:
            return completed_future(tensor)

        self.wait_quorum()

        # Alone in the ring and participating: averaging is the identity —
        # skip the device->host->device roundtrip entirely (TPU HBM traffic
        # is the budget; the reference still pays a no-op pg.allreduce here).
        if self._collective.size() == 1 and self.is_participating():
            return completed_future(tensor)

        is_jax = _is_jax_array(tensor)
        try:
            # Deadline-guarded: a wedged device computation surfaces as a
            # latched TimeoutError, not a hung train loop (the reference's
            # stream_timeout edge, torchft/futures.py:129-148).
            from torchft_tpu.futures import device_get

            host = device_get(tensor, self._timeout.total_seconds())
        except TimeoutError as e:
            self._logger.exception(f"allreduce input materialization: {e}")
            self.report_error(e)
            return completed_future(tensor)
        if not self.is_participating():
            # Healing replicas / spares contribute zeros (torchft/manager.py:287-288).
            host = np.zeros_like(host)

        # The DCN-throughput gauge counts bytes AS THE WIRE CARRIES THEM:
        # a bf16-wiring collective encodes float payloads to 2 bytes/elt
        # per hop regardless of whether the cast ran on device (bf16
        # buffer handed in) or inside the ring encode (f32 handed in).
        # Counting the handoff width instead would make the same wire
        # traffic read 2x apart between those two modes, inverting the
        # device-prep comparison drawn from this gauge.  The
        # collective's own wire_nbytes is the single source of truth;
        # collectives without the probe count the handoff width.
        wire_nbytes = getattr(self._collective, "wire_nbytes", None)
        try:
            if callable(wire_nbytes):
                ar_nbytes = (
                    int(wire_nbytes(host, allow_wire_compression, wire_codec))
                    if wire_codec is not None
                    else int(wire_nbytes(host, allow_wire_compression))
                )
            else:
                ar_nbytes = int(host.nbytes)
        except Exception:  # noqa: BLE001 — telemetry only, never fail a step
            ar_nbytes = int(host.nbytes)
        with self._ar_lock:
            if self._ar_t_first is None:
                self._ar_t_first = time.monotonic()
            self._ar_bytes += ar_nbytes

        try:
            kwargs: Dict[str, Any] = {"allow_wire_compression": allow_wire_compression}
            if wire_codec is not None:
                kwargs["wire_codec"] = wire_codec
            if donate:
                kwargs["donate"] = True
            step = self._step
            tags: Dict[str, Any] = {"bytes": int(host.nbytes)}
            if bucket is not None:
                tags["bucket"] = bucket
            t_submit = time.monotonic_ns()
            work = self._collective.allreduce([host], op="sum", **kwargs)
            # The worker's stamps, filled in place before the future resolves.
            times = getattr(work, "times", (0, 0))

            def normalize(results: List[np.ndarray]):
                # On the thread that resolved the op's future.
                started, done = times
                if started and done:
                    self._spans.note_sub(
                        "ring_queue", step, t_submit, started, **tags
                    )
                    self._spans.note_sub(
                        "ring_run", step, started, done,
                        wire_bytes=ar_nbytes, **tags
                    )
                out = results[0]
                in_place = should_average and _may_average_in_place(out, host, donate)
                with self._spans.sub("normalize", step=step, in_place=in_place, **tags):
                    if should_average:
                        num = max(1, self.num_participants())
                        if in_place:
                            # The buffer the ring reduced is already touched
                            # and the op's own: no fresh pages to fault in on
                            # the worker that peers and later buckets wait for.
                            np.divide(out, num, out=out)
                            if out is host:
                                # Callers tell a latched failure by identity
                                # with their input; a success never is it.
                                out = out.view()
                        else:
                            out = (out / num).astype(host.dtype, copy=False)
                    if is_jax:
                        import jax

                        return jax.device_put(out, tensor.sharding)
                    return out

            from torchft_tpu.futures import then

            fut = then(work.future(), normalize)
            return self.wrap_future(fut, default=tensor)
        except Exception as e:  # noqa: BLE001
            self._logger.exception(f"allreduce failed: {e}")
            self.report_error(e)
            return completed_future(tensor)

    def wrap_future(self, fut: Future, default, timeout: Optional[timedelta] = None) -> Future:
        """Arms a deadline and converts failure into (default, latched error)
        (reference: torchft/manager.py:346-383)."""
        timed = future_timeout(fut, (timeout or self._timeout).total_seconds())
        out: Future = Future()

        def settle(f: Future) -> None:
            # Drain edge for the allreduce GB/s window: the LAST settle of
            # the step, not should_commit() time, ends the wire window — a
            # loop that runs its optimizer between the averager's drain and
            # the vote must not see that compute charged to the DCN path.
            with self._ar_lock:
                self._ar_t_last = time.monotonic()
            exc = f.exception()
            if exc is not None:
                self._logger.exception(f"async work failed: {exc}")
                self.report_error(exc)
                out.set_result(default)
            else:
                out.set_result(f.result())

        timed.add_done_callback(settle)
        self._pending_work.append(out)
        return out

    def note_d2h(self, nbytes: int) -> None:
        """Adds device->host fetch bytes to the step in flight's transfer
        accounting (flushed into step_summary as ``d2h_bytes``).  Called by
        the data-plane wrappers (GradientAverager) that stage gradients
        through host buffers — with device wire prep this reads wire bytes,
        the ~2x reduction the bench pins."""
        with self._ar_lock:
            self._d2h_bytes += int(nbytes)
            self._d2h_bytes_total += int(nbytes)

    def note_h2d(self, nbytes: int) -> None:
        """Adds host->device scatter-back bytes to the step in flight's
        transfer accounting (``h2d_bytes`` in step_summary) — the return
        half of the round-trip the ``allreduce_h2d`` span charges."""
        with self._ar_lock:
            self._h2d_bytes += int(nbytes)
            self._h2d_bytes_total += int(nbytes)

    def note_summary_fields(self, **fields: object) -> None:
        """Merges extra fields into the step in flight's ``step_summary``
        record (flushed at the commit vote, cleared at start_quorum).
        Wrappers with their own data plane (the semisync engine) use this
        to land per-round accounting — fragment counts, codec, wire
        bytes — in the same record the phase breakdown rides."""
        with self._ar_lock:
            self._summary_extra.update(fields)

    def register_membership_callback(
        self, cb: Callable[[Dict[str, object]], None]
    ) -> None:
        """Registers ``cb`` to run on every quorum transition that changes
        the participant set.  The callback receives the same payload the
        ``membership_change`` event carries — old/new participant replica
        ranks, joined/left deltas, transition wall time, configure mode,
        and the refreshed elastic plan (None when the elastic batch engine
        is off).  It runs on the quorum thread after the collective is
        reconfigured and before the step proceeds, so a data loader can
        re-shard before the next batch is drawn.  Exceptions are swallowed
        and logged: a resize hook must never fail the step."""
        self._membership_callbacks.append(cb)

    def elastic_plan(self) -> Optional[Dict[str, object]]:
        """The elastic batch plan for the current participant count, or
        None when the elastic batch engine is off (TPUFT_ELASTIC_GLOBAL_BATCH
        unset) or no quorum has formed yet.  Keys: participants,
        global_batch, group_batch (this group's share), microbatch,
        accum_steps, lr_scale.  Stable between quorum transitions."""
        return self._elastic_plan

    def _on_membership_change(
        self,
        quorum: object,
        quorum_id: int,
        replica_world_size: int,
        configure_ms: float,
        last_configure: Dict[str, object],
    ) -> None:
        """Post-reconfigure membership bookkeeping: refresh the elastic
        batch plan, proactively re-shard the EC plane, emit the
        ``membership_change`` event, and fire registered callbacks.  Runs
        on the quorum thread for every quorum-id change; the event and
        callbacks fire only when the participant SET actually changed
        (a quorum id can change without membership churn, e.g. a
        lighthouse failover re-issuing the same membership)."""
        new_participants = sorted(
            list(getattr(quorum, "participant_replica_ranks", []) or [])
            or range(replica_world_size)
        )
        old_participants = self._last_participants
        self._last_participants = new_participants

        # Refresh the elastic plan from the PARTICIPATING world (healing
        # groups contribute zeros and take no batch share) so the global
        # batch stays constant across the transition.
        if self._elastic is not None:
            participants = self._participating_replica_world_size or len(
                new_participants
            )
            try:
                self._elastic_plan = self._elastic.plan(
                    participants, rank=self._participating_replica_rank
                )
            except Exception as e:  # noqa: BLE001 — resize must not fail a step
                self._logger.warn(f"elastic plan failed: {e}")

        if old_participants == new_participants:
            return

        # Proactive EC re-shard: re-place the latest shard generation under
        # the new membership so coverage is restored BEFORE the next fault,
        # not after (the tpuft_ec_shard_coverage alert fires on the gap).
        if self._ec is not None and hasattr(self._ec, "reshard"):
            try:
                self._ec.reshard()
            except Exception as e:  # noqa: BLE001
                self._logger.warn(f"ec reshard failed: {e}")

        old_set = set(old_participants or [])
        new_set = set(new_participants)
        payload: Dict[str, object] = {
            "quorum_id": quorum_id,
            "old_participants": old_participants,
            "new_participants": new_participants,
            "joined": sorted(new_set - old_set),
            "left": sorted(old_set - new_set),
            "transition_s": configure_ms / 1e3,
            "mode": last_configure.get("mode", "unknown"),
            "elastic_plan": self._elastic_plan,
        }
        self._metrics.emit("membership_change", step=self._step, **payload)
        # Also land the transition on this step's step_summary record so a
        # slow step reads its cause inline (resize vs fault) without joining
        # against the membership_change stream.
        self.note_summary_fields(
            membership_change={
                "joined": payload["joined"],
                "left": payload["left"],
                "transition_s": payload["transition_s"],
                "mode": payload["mode"],
            }
        )
        for cb in self._membership_callbacks:
            try:
                cb(dict(payload))
            except Exception as e:  # noqa: BLE001
                self._logger.warn(f"membership callback failed: {e}")

    @property
    def metrics(self):
        """The Manager's :class:`~torchft_tpu.metrics.MetricsLogger`.
        Public so wrappers that run their own data plane (the semisync
        engine) can emit registered events into the SAME stream the
        Manager's spans and lifecycle events ride — one timeline per
        replica, not a side channel."""
        return self._metrics

    @property
    def spans(self):
        """The Manager's :class:`~torchft_tpu.obs.spans.SpanTracker`.
        Public so wrappers that BLOCK the train thread on FT work outside
        the Manager's own phases (GradientAverager's bucket drain, custom
        sync loops) can record that wait as a span — anything not spanned
        here is charged as busy/productive time by both obs.report and the
        straggler sentinel's step-time telemetry."""
        return self._spans

    @property
    def timeout(self) -> timedelta:
        """Default per-operation deadline.  Public so wrappers can bound their
        own device->host materializations and RPC waits without reaching into
        private state (reference exposes the same knob as a ctor arg,
        torchft/manager.py:95-97)."""
        return self._timeout

    # -- link health (docs/architecture.md "Data-plane observability") ------

    _LINK_ALPHA = 0.5

    def _observe_link(self, lanes: dict) -> Dict[str, float]:
        """One per-step link-health observation from the lane_stats
        snapshot's hop aggregates: deltas against the previous snapshot
        give this step's send-blocked / recv-wait seconds and wire bytes,
        from which the per-neighbor goodput estimates follow —

        * ``link_send_gbps`` = sent bytes per second of send-BLOCKED time,
          the localizing signal (only the degraded edge's sender blocks;
          downstream recv-waits equalize around the lockstep ring);
        * ``link_recv_gbps`` = received bytes per second of recv-wait;
        * ``link_hop_rtt_ms`` = mean recv-wait per hop.

        EWMA'd (alpha 0.5, like the step-time stats) and returned as the
        step_summary / heartbeat fields; {} when the step moved no ring
        traffic or a reconfigure reset the counters mid-window."""
        hops = lanes.get("hops") or {}
        sent = float(sum(lanes.get("sent") or []))
        recv = float(sum(lanes.get("recv") or []))
        for t in (lanes.get("tiers") or {}).values():
            sent += sum(t.get("sent") or [])
            recv += sum(t.get("recv") or [])
        cur = {
            "sent": sent,
            "recv": recv,
            "send_block": float(
                sum(h.get("send_block_s", 0.0) for h in hops.values())
            ),
            "recv_wait": float(
                sum(h.get("recv_wait_s", 0.0) for h in hops.values())
            ),
            "hops": float(sum(h.get("hops", 0) for h in hops.values())),
        }
        prev, self._link_prev = self._link_prev, cur
        if prev is None or cur["hops"] < prev["hops"]:
            # First window, or the counters reset under us (reconfigure).
            return {}
        d = {k: cur[k] - prev[k] for k in cur}
        if d["hops"] <= 0 or (d["sent"] <= 0 and d["recv"] <= 0):
            return {}
        # A healthy link's send-blocked time is near zero (sends complete
        # into kernel buffers) — dividing by it would yield an estimate
        # that is pure scheduler noise, and noise RATIOS between healthy
        # peers are unbounded (the false-alert mode the bench's control
        # cell pins at zero).  Below a 5 ms-per-window floor the estimate
        # SATURATES: lockstep peers move identical bytes per step, so all
        # healthy readings collapse to the same floored value (ratio 1.0
        # by construction) while a genuinely blocked sender's seconds of
        # send-block dominate the floor and read as the true goodput.
        floor_s = 5e-3
        cap = 1e4
        send_gbps = min(d["sent"] / 1e9 / max(d["send_block"], floor_s), cap)
        recv_gbps = min(d["recv"] / 1e9 / max(d["recv_wait"], floor_s), cap)
        rtt_ms = d["recv_wait"] / d["hops"] * 1e3
        ew = self._link_ewma
        a = self._LINK_ALPHA
        for key, obs in (
            ("recv_gbps", recv_gbps),
            ("send_gbps", send_gbps),
            ("rtt_ms", rtt_ms),
        ):
            ew[key] = obs if key not in ew else a * obs + (1 - a) * ew[key]
        return {
            "link_recv_gbps": round(ew["recv_gbps"], 4),
            "link_send_gbps": round(ew["send_gbps"], 4),
            "link_hop_rtt_ms": round(ew["rtt_ms"], 3),
        }

    @property
    def worker_metrics(self):
        """The unified worker ``/metrics`` endpoint
        (:class:`~torchft_tpu.obs.prom.WorkerMetrics`).  Public so
        subsystems with their own exposition (the semisync engine)
        register a section here instead of opening a second port."""
        return self._worker_metrics

    def _worker_metrics_snapshot(self):
        """Series provider for the worker /metrics endpoint — called at
        SCRAPE time, never on the training path."""
        series = []

        def g(name, help_, value, kind="gauge", labels=()):
            series.append((name, kind, help_, labels, value))

        g("tpuft_worker_step", "current training step", self._step)
        snap = self._step_stats.snapshot()
        g(
            "tpuft_worker_step_time_ms_ewma",
            "rolling per-step busy-time EWMA, ms",
            snap["ewma"],
        )
        with self._ar_lock:
            d2h, h2d = self._d2h_bytes_total, self._h2d_bytes_total
        g(
            "tpuft_worker_d2h_bytes_total",
            "device->host fetch bytes (lifetime)", d2h, kind="counter",
        )
        g(
            "tpuft_worker_h2d_bytes_total",
            "host->device scatter-back bytes (lifetime)", h2d, kind="counter",
        )
        lane_totals = getattr(self._collective, "lane_totals", None)
        if callable(lane_totals):
            try:
                lt = lane_totals()
            except Exception:  # noqa: BLE001
                lt = None
            if lt:
                g(
                    "tpuft_worker_reconfigures_total",
                    "collective reconfigurations banked", lt["reconfigures"],
                    kind="counter",
                )
                # Metric-major so each series family renders contiguous
                # (Prometheus text-format convention).
                tiers = sorted((lt.get("tiers") or {}).items())
                for tname, t in tiers:
                    g(
                        "tpuft_worker_lane_sent_bytes_total",
                        "ring wire bytes sent per tier (monotonic across "
                        "reconfigures — banked at the source)",
                        t["sent_bytes"], kind="counter",
                        labels=(("tier", tname),),
                    )
                for tname, t in tiers:
                    g(
                        "tpuft_worker_lane_recv_bytes_total",
                        "ring wire bytes received per tier (monotonic)",
                        t["recv_bytes"], kind="counter",
                        labels=(("tier", tname),),
                    )
                hop_tiers = sorted((lt.get("hops") or {}).items())
                for tname, h in hop_tiers:
                    g(
                        "tpuft_worker_hops_total",
                        "ring hops per tier (monotonic)", h["hops"],
                        kind="counter", labels=(("tier", tname),),
                    )
                for key, metric in (
                    ("send_block_s", "tpuft_worker_hop_send_block_seconds_total"),
                    ("recv_wait_s", "tpuft_worker_hop_recv_wait_seconds_total"),
                    ("combine_s", "tpuft_worker_hop_combine_seconds_total"),
                    ("shape_s", "tpuft_worker_hop_shaping_seconds_total"),
                ):
                    for tname, h in hop_tiers:
                        g(
                            metric,
                            "per-hop stall seconds per tier (monotonic)",
                            round(float(h.get(key, 0.0)), 6), kind="counter",
                            labels=(("tier", tname),),
                        )
        ew = self._link_ewma
        if ew:
            g("tpuft_link_recv_gbps",
              "inbound ring-edge goodput EWMA (worker-side view)",
              round(ew.get("recv_gbps", 0.0), 4))
            g("tpuft_link_send_gbps",
              "outbound ring-edge goodput EWMA (worker-side view)",
              round(ew.get("send_gbps", 0.0), 4))
            g("tpuft_link_hop_rtt_ms", "mean per-hop recv-wait, ms",
              round(ew.get("rtt_ms", 0.0), 3))
        # Goodput ledger (worker-side view; the lighthouse aggregates the
        # same counters cluster-wide from heartbeat fields 14-16).
        led = self._ledger.snapshot()
        if led["steps"]:
            g("tpuft_worker_goodput_ratio",
              "cumulative productive fraction of accounted step wall",
              led["goodput_ratio"] if led["goodput_ratio"] is not None else -1.0)
            g("tpuft_worker_compute_seconds_total",
              "productive seconds accounted by the goodput ledger",
              led["compute_s"], kind="counter")
            for cause, v in sorted(led["lost_s"].items()):
                g("tpuft_worker_lost_seconds_total",
                  "lost seconds per ledger cause (pinned taxonomy, "
                  "obs/ledger.py CAUSES)",
                  v, kind="counter", labels=(("cause", cause),))
        return series

    def _render_hop_histograms(self) -> str:
        """Worker /metrics section: per-hop latency + wire-byte histograms
        per ring tier, fed from the collective's retained hop timeline
        (``hop_records``) — the sampled ring the data-plane flight
        recorder already keeps, so scraping adds no recording cost.

        MONOTONIC across scrapes: the timeline is a bounded SLIDING ring,
        so rebucketizing the whole ring each scrape would re-count old
        records and DROP counts when they age out — Prometheus reads any
        decrease in a histogram series as a counter reset.  Instead each
        scrape folds only records NEWER than the previous scrape's
        high-water timestamp into cumulative per-tier buckets (records
        that fall off the ring between scrapes are missed — an undercount
        under sparse scraping, never a reset)."""
        hop_records = getattr(self._collective, "hop_records", None)
        if not callable(hop_records):
            return ""
        try:
            recs = hop_records()
        except Exception:  # noqa: BLE001 — telemetry only
            return ""
        from torchft_tpu.obs.prom import (
            HOP_BYTES_BOUNDS,
            HOP_LATENCY_BOUNDS,
            bucketize,
            render_histogram_counts,
        )

        with self._hop_hist_lock:
            last_ts = self._hop_hist_last_ts
            for r in recs:
                ts = float(r.get("ts", 0.0))
                if ts <= last_ts:
                    continue
                # Slots key on (tier, lane): the lane split is what tells a
                # striped ring's per-lane byte skew apart from a uniform
                # slowdown.  Records from engines predating the lane field
                # fold into lane 0.
                tier = int(r.get("tier", 0))
                lane = int(r.get("lane", 0))
                slot = self._hop_hist.setdefault(
                    (tier, lane),
                    {
                        "lat": [0] * (len(HOP_LATENCY_BOUNDS) + 1),
                        "lat_sum": 0.0,
                        "bytes": [0] * (len(HOP_BYTES_BOUNDS) + 1),
                        "bytes_sum": 0.0,
                    },
                )
                lat = (
                    float(r.get("send_s", 0.0))
                    + float(r.get("recv_s", 0.0))
                    + float(r.get("comb_s", 0.0))
                )
                _, dsum = bucketize(HOP_LATENCY_BOUNDS, (lat,), slot["lat"])
                slot["lat_sum"] += dsum
                _, dsum = bucketize(
                    HOP_BYTES_BOUNDS, (float(r.get("nbytes", 0)),),
                    slot["bytes"],
                )
                slot["bytes_sum"] += dsum
                self._hop_hist_last_ts = max(self._hop_hist_last_ts, ts)
            if not self._hop_hist:
                return ""
            # Per-tier families sum their lanes (sums of monotonic buckets
            # stay monotonic); the lane-split family emits one series per
            # slot.
            lat_series = []
            byte_series = []
            lane_byte_series = []
            for tier in sorted({t for t, _ in self._hop_hist}):
                labels = (
                    ("replica", self._replica_id),
                    ("tier", str(tier)),
                )
                lat = [0] * (len(HOP_LATENCY_BOUNDS) + 1)
                lat_sum = 0.0
                byts = [0] * (len(HOP_BYTES_BOUNDS) + 1)
                bytes_sum = 0.0
                for (t, _lane), slot in self._hop_hist.items():
                    if t != tier:
                        continue
                    lat = [a + b for a, b in zip(lat, slot["lat"])]
                    lat_sum += slot["lat_sum"]
                    byts = [a + b for a, b in zip(byts, slot["bytes"])]
                    bytes_sum += slot["bytes_sum"]
                lat_series.append((labels, lat, lat_sum))
                byte_series.append((labels, byts, bytes_sum))
            for tier, lane in sorted(self._hop_hist):
                slot = self._hop_hist[(tier, lane)]
                lane_byte_series.append(
                    (
                        (
                            ("replica", self._replica_id),
                            ("tier", str(tier)),
                            ("lane", str(lane)),
                        ),
                        list(slot["bytes"]),
                        slot["bytes_sum"],
                    )
                )
        out = render_histogram_counts(
            "tpuft_worker_hop_latency_seconds",
            "per-hop wall time (send-block + recv-wait + combine) from the "
            "retained hop timeline, per ring tier (sampled per "
            "TPUFT_HOP_SAMPLE; monotonic across scrapes)",
            HOP_LATENCY_BOUNDS, lat_series,
        )
        out += render_histogram_counts(
            "tpuft_worker_hop_wire_bytes",
            "per-hop wire payload bytes from the retained hop timeline, "
            "per ring tier (monotonic across scrapes)",
            HOP_BYTES_BOUNDS, byte_series,
        )
        out += render_histogram_counts(
            "tpuft_hop_bytes",
            "per-hop wire payload bytes split per ring tier AND lane, from "
            "the retained hop timeline (monotonic across scrapes) — the "
            "lane split exposes striped-ring byte skew the per-tier "
            "histogram averages away",
            HOP_BYTES_BOUNDS, lane_byte_series,
        )
        return out

    # -- goodput ledger (docs/architecture.md "Goodput ledger") -------------

    def _quorum_server_ms(self) -> Optional[float]:
        """Server-side share of this step's quorum wait, from the group's
        own native ManagerServer flight ring: the ``ManagerQuorum`` RPC
        span for the current trace id covers the local-rank aggregation +
        the lighthouse round (formation wait included) — everything that
        is NOT this client's transport.  The ledger splits the quorum
        cause with it (quorum_server vs quorum_transport).  None when no
        server runs here (rank != 0, fake-wire tests) or the ring holds no
        matching span — the ledger then charges the whole wait as
        quorum_server rather than fabricating a split."""
        srv = self._manager_server
        if srv is None or not self._trace_id:
            return None
        flight = getattr(srv, "flight", None)
        if not callable(flight):
            return None
        try:
            dump = flight(limit=32)
        except Exception:  # noqa: BLE001 — telemetry only
            return None
        total, seen = 0.0, False
        for ev in dump.get("events", []):
            if (
                isinstance(ev, dict)
                and ev.get("kind") == "rpc"
                and ev.get("method") == "ManagerQuorum"
                and ev.get("trace_id") == self._trace_id
            ):
                total += max(0.0, float(ev.get("dur_us", 0)) / 1e3)
                seen = True
        return total if seen else None

    def _push_ledger(self) -> None:
        """Pushes the ledger's cumulative counters onto heartbeat fields
        14-16 (best-effort; rank != 0 has no server, and status must never
        fail a step)."""
        srv = self._manager_server
        if srv is None or not hasattr(srv, "set_ledger"):
            return
        try:
            ratio, compute_s, lost = self._ledger.heartbeat_vector()
            srv.set_ledger(ratio, compute_s, lost)
        except Exception:  # noqa: BLE001
            pass

    @property
    def ledger(self):
        """The Manager's :class:`~torchft_tpu.obs.ledger.StepLedger` —
        public so benches and tests can read the cumulative cause totals
        without re-parsing the stream."""
        return self._ledger

    # -- status -------------------------------------------------------------

    def _set_status(self, state: str) -> None:
        """Pushes (step, state) plus the rolling step-time telemetry and the
        last committed step's allreduce GB/s into this group's native
        ManagerServer so its lighthouse heartbeats carry live per-replica
        progress AND pace — the feed for the lighthouse's ``GET /metrics``
        exposition (including ``tpuft_allreduce_gb_per_s``), the dashboard's
        step-lag column, and the straggler sentinel's health scoring.
        Rank != 0 has no server; best-effort by design (status must never
        fail a step)."""
        srv = self._manager_server
        if srv is None:
            return
        try:
            ec_held, ec_step, ec_k = -1, -1, -1
            if self._ec is not None:
                step, count = self._ec.coverage()
                # (-1, 0) while empty -> an authoritative zero report so a
                # pruned/fresh store never shows stale coverage.
                ec_held, ec_step = count, max(0, step)
                # k rides along so the lighthouse coverage sentinel can
                # page at coverage < k + 1 without its own EC config.
                ec_k = self._ec.config.k
            lk = self._link_ewma
            srv.set_status(
                self._step,
                state,
                self._step_stats.ewma_ms,
                self._step_stats.last_ms,
                self._ar_gbps,
                ec_held,
                ec_step,
                ec_k,
                lk.get("recv_gbps", -1.0),
                lk.get("send_gbps", -1.0),
                lk.get("rtt_ms", -1.0),
            )
        except Exception:  # noqa: BLE001
            pass

    # -- error handling -----------------------------------------------------

    def report_error(self, e: Exception) -> None:
        """Latches an error for this step; cleared at the next start_quorum
        (reference: torchft/manager.py:325-337)."""
        self._errored = e
        self._metrics.emit("error", step=self._step, error=repr(e))
        # What led up to the error leaves now: a crash after it loses at
        # most the sub-spans of the step in flight.
        self._spans.flush_subspans()

    def errored(self) -> Optional[Exception]:
        return self._errored

    # -- commit protocol ----------------------------------------------------

    def should_commit(self, timeout: Optional[timedelta] = None) -> bool:
        """Two-phase commit vote across all local ranks of the group
        (reference: torchft/manager.py:587-663)."""
        # Settle the quorum before voting: the vote concerns state the
        # quorum thread may still be mutating (heal fast-forward of _step,
        # _healing, participation bookkeeping).  A loop that allreduced
        # already waited; this closes the race for loops that vote without
        # gradient traffic (num_participants() read 0 mid-flight there).
        if self._quorum_future is not None:
            self.wait_quorum()
        # Drain pending allreduces; their errors are already latched.  The
        # span is the merge wait: how long commit time blocked on gradient
        # traffic the step's compute did not already hide.
        with self._spans.span("allreduce_merge", step=self._step):
            for work in self._pending_work:
                try:
                    work.result()
                except Exception:  # noqa: BLE001
                    pass
            self._pending_work = []

        # Allreduce data-plane throughput for this step: payload bytes over
        # the first-issue -> drained window.  Computed after the drain so
        # pipelining/lane overlap is reflected; pushed to the lighthouse on
        # the post-vote status heartbeat and into step_summary below.
        with self._ar_lock:
            ar_bytes, ar_t_first = self._ar_bytes, self._ar_t_first
            ar_t_last = self._ar_t_last
            d2h_bytes, h2d_bytes = self._d2h_bytes, self._h2d_bytes
            summary_extra = self._summary_extra
            self._ar_bytes, self._ar_t_first = 0, None
            self._ar_t_last = None
            self._d2h_bytes = 0
            self._h2d_bytes = 0
            self._summary_extra = {}
        ar_fields: Dict[str, object] = dict(summary_extra)
        # Elastic invariant audit trail: every committed step record carries
        # the plan it trained under, so the bench (and any postmortem) can
        # assert the global batch never moved across membership churn.
        if self._elastic_plan is not None:
            ar_fields.setdefault(
                "elastic_global_batch", self._elastic_plan["global_batch"]
            )
            ar_fields.setdefault(
                "elastic_group_batch", self._elastic_plan["group_batch"]
            )
            ar_fields.setdefault(
                "elastic_accum_steps", self._elastic_plan["accum_steps"]
            )
            ar_fields.setdefault(
                "elastic_participants", self._elastic_plan["participants"]
            )
        if d2h_bytes or h2d_bytes:
            ar_fields["d2h_bytes"] = d2h_bytes
            ar_fields["h2d_bytes"] = h2d_bytes
        ar_gbps: Optional[float] = None
        lanes_snap: Optional[dict] = None
        if ar_bytes and ar_t_first is not None:
            if ar_t_last is None or ar_t_last <= ar_t_first:
                ar_t_last = time.monotonic()
            ar_dur = max(1e-9, ar_t_last - ar_t_first)
            ar_gbps = ar_bytes / 1e9 / ar_dur
            ar_fields.update(
                {
                    "allreduce_bytes": ar_bytes,
                    "allreduce_s": round(ar_dur, 4),
                    "allreduce_gb_per_s": round(ar_gbps, 4),
                }
            )
            lane_stats = getattr(self._collective, "lane_stats", None)
            if callable(lane_stats):
                try:
                    lanes_snap = lane_stats()
                    ar_fields["allreduce_lanes"] = lanes_snap
                    # Per-neighbor link health from this step's hop-stall
                    # deltas (rides step_summary AND heartbeat fields
                    # 11-13 — the slow-link sentinel's feed).
                    ar_fields.update(self._observe_link(lanes_snap))
                except Exception:  # noqa: BLE001 — telemetry only
                    pass

        if self._collective.errored() is not None:
            self.report_error(cast(Exception, self._collective.errored()))

        if self._healing:
            self._apply_pending_state_dict()

        enough_replicas = self.num_participants() >= self._min_replica_size
        local_should_commit = enough_replicas and self._errored is None
        vote_step = self._step
        with self._spans.span("commit_vote", step=vote_step) as sp_vote:
            should_commit = self._client.should_commit(
                self._rank,
                vote_step,
                local_should_commit,
                timeout_ms=int((timeout or self._timeout).total_seconds() * 1000),
                trace_id=self._trace_id,
            )
        self._logger.info(
            f"should_commit={should_commit} (local={local_should_commit}, "
            f"enough_replicas={enough_replicas}, error={self._errored})"
        )
        self._metrics.emit(
            "commit",
            step=vote_step,
            committed=should_commit,
            local=local_should_commit,
            participants=self.num_participants(),
            error=repr(self._errored) if self._errored else None,
            vote_ms=sp_vote.duration_ms,
        )
        # Straggler-sentinel observation: this step's BUSY time = the
        # commit-to-commit wall interval minus the FT wait phases the span
        # accumulator holds for the step in flight (read BEFORE step_summary
        # flushes it).  In lockstep training the raw interval equalizes
        # across the quorum — everyone waits for the slowest — so only
        # wall-minus-waits identifies the host that actually computed the
        # whole time.  Failed commits produce no observation (their eventual
        # commit interval spans the retries and would misread as slowness).
        step_time_fields: Dict[str, object] = {}
        # Ledger classification reads the span accumulation BEFORE
        # step_summary flushes it (obs/ledger.py).
        phases_now = self._spans.phases_ms()
        if should_commit:
            now_mono = time.monotonic()
            if self._last_commit_mono is not None:
                wall_ms = (now_mono - self._last_commit_mono) * 1e3
                busy_ms = max(0.0, wall_ms - self._spans.ft_accounted_ms())
                self._step_stats.observe(busy_ms)
                snap = self._step_stats.snapshot()
                step_time_fields = {
                    "step_wall_ms": round(wall_ms, 3),
                    "step_time_ms": round(busy_ms, 3),
                    "step_time_ms_ewma": snap["ewma"],
                    "step_time_ms_p50": snap["p50"],
                    "step_time_ms_p99": snap["p99"],
                }
            # Ledger interval: from the ledger's own last-commit mark, so
            # a retried step's wall (failed votes included) is charged in
            # this one committed observation, with the failed attempts'
            # buffered phases merged in.
            if self._ledger_prev_commit_mono is not None:
                ledger_wall_s = now_mono - self._ledger_prev_commit_mono
                ledger_phases = dict(self._ledger_pending_phases)
                for k, v in phases_now.items():
                    ledger_phases[k] = ledger_phases.get(k, 0.0) + float(v)
                # The server/transport split costs a flight-ring read
                # (small JSON parse); only pay it when the quorum wait is
                # big enough for the split to mean anything — steady-state
                # sub-50 ms waits charge the lump to quorum_server, and
                # the ledger's commit-path cost stays sub-0.1 ms.
                q_server_ms = (
                    self._quorum_server_ms()
                    if ledger_phases.get("quorum", 0.0) > 50.0
                    else None
                )
                causes = self._ledger.observe_step(
                    vote_step,
                    ledger_wall_s,
                    ledger_phases,
                    lanes=lanes_snap,
                    committed=True,
                    draining=self.drain_requested(),
                    quorum_server_ms=q_server_ms,
                )
                if causes is not None:
                    step_time_fields["ledger"] = {
                        "causes": {k: round(v, 4) for k, v in causes.items()},
                        "goodput_ratio": self._ledger.goodput_ratio(),
                    }
                self._push_ledger()
            self._ledger_pending_phases = {}
            self._ledger_prev_commit_mono = now_mono
            self._last_commit_mono = now_mono
        else:
            # Failed votes produce no ledger observation, but their
            # phases buffer into the eventual committed interval's charge
            # and the hop-delta window still advances so the retried
            # step's stalls are not double-charged.
            for k, v in phases_now.items():
                self._ledger_pending_phases[k] = (
                    self._ledger_pending_phases.get(k, 0.0) + float(v)
                )
            self._ledger.observe_step(
                vote_step, 0.0, phases_now, lanes=lanes_snap, committed=False
            )
            self._last_commit_mono = None
        self._spans.step_summary(
            vote_step, committed=should_commit, **step_time_fields, **ar_fields
        )

        if self._checkpoint_transport is not None:
            # Weights are about to be mutated: stop serving the stale
            # checkpoint (torchft/manager.py:645).
            self._checkpoint_transport.disallow_checkpoint()

        if should_commit:
            self._step += 1
            self._batches_committed += self.num_participants()
            self._commit_failures = 0
            # The gauge is "the last COMMITTED step's" throughput (proto
            # field 6): a failed vote's timeout-stretched window must not
            # overwrite it, and a committed step with no allreduce traffic
            # (healing, spare) clears it — a stale healthy number would
            # mask exactly the DCN degradation the gauge exists to expose.
            self._ar_gbps = ar_gbps if ar_gbps is not None else 0.0
            self._set_status("step")
        else:
            self._commit_failures += 1
            if self._max_retries is not None and self._commit_failures > self._max_retries:
                raise ExceededMaxRetriesError(
                    f"exceeded max_retries={self._max_retries} consecutive failed commits"
                )
        return should_commit

    # -- cooperative drain --------------------------------------------------

    def attach_drain_watcher(self, watcher=None) -> "object":
        """Wires a :class:`~torchft_tpu.drain.DrainWatcher` to this manager
        and starts it.  With no argument, builds one from the environment
        contract (SIGTERM + ``TPUFT_DRAIN_DIR`` notice file + optional GCE
        metadata poll).  The watcher is stopped by :meth:`shutdown`."""
        if watcher is None:
            from torchft_tpu.drain import DrainWatcher

            watcher = DrainWatcher(on_notice=self.begin_drain)
        else:
            watcher._on_notice = self.begin_drain
        self._drain_watcher = watcher
        watcher.start()
        return watcher

    def begin_drain(self, notice=None) -> None:
        """Handles a drain notice: records it for the train loop and tells
        the lighthouse IMMEDIATELY (wire method 5) so the next quorum
        excludes this group with zero join/heartbeat-timeout wait, while
        the in-flight step finishes undisturbed.  Idempotent; callable from
        any thread (the DrainWatcher invokes it from a signal handler or a
        poller thread)."""
        from torchft_tpu.drain import DrainNotice

        if notice is None:
            notice = DrainNotice(source="manual", deadline=time.time() + 30.0)
        with self._drain_lock:
            if self._drain_notice is not None:
                return
            self._drain_notice = notice
        self._logger.warn(
            f"drain notice ({notice.source}): finishing in-flight step, "
            f"deadline in {notice.remaining_s():.1f}s"
        )
        self._metrics.emit(
            "drain_notice",
            step=self._step,
            source=notice.source,
            deadline_ms=notice.deadline_ms_from_now(),
        )
        self._set_status("draining")
        # Rank 0 owns the group's lighthouse relationship; other local
        # ranks observe the same notice via their own watcher/launcher
        # channel and simply stop stepping.  The RPC runs on its own
        # thread: begin_drain may be called from a SIGTERM handler on the
        # main thread, and the final step must not stall behind a dial.
        if self._rank == 0 and self._lighthouse_addr:
            def _notify() -> None:
                # Reconnect loop with DECORRELATED jitter: the notice may
                # land exactly during a lighthouse failover (the two
                # events correlate — a host being preempted can take the
                # lighthouse with it), and every draining group in a
                # preemption wave retries this same call.  Jittered sleeps
                # keep those retries from stampeding the new leader in
                # sync; the loop gives up at the drain deadline (less a
                # grace margin) because a notice that cannot be delivered
                # degrades to the crash path (heartbeat timeout) — it must
                # never outlive the process's own exit budget.
                from torchft_tpu._native import LighthouseClient
                from torchft_tpu.ha.backoff import DecorrelatedBackoff

                deadline = time.monotonic() + min(
                    10.0, max(2.0, notice.remaining_s() - 2.0)
                )
                backoff = DecorrelatedBackoff(base_s=0.1, cap_s=1.5)
                last_err: Optional[Exception] = None
                while time.monotonic() < deadline:
                    try:
                        client = LighthouseClient(
                            self._lighthouse_addr, connect_timeout_ms=2000
                        )
                        try:
                            client.drain(
                                self._replica_id,
                                deadline_ms=notice.deadline_ms_from_now(),
                                timeout_ms=2000,
                                trace_id=self._trace_id,
                            )
                        finally:
                            client.close()
                        return
                    except Exception as e:  # noqa: BLE001
                        last_err = e
                        sleep_s = backoff.next()
                        if time.monotonic() + sleep_s >= deadline:
                            break
                        time.sleep(sleep_s)
                # A failed notice degrades to the crash path (heartbeat
                # timeout), never kills the final step.
                self._logger.warn(f"lighthouse drain notice failed: {last_err}")

            threading.Thread(
                target=_notify, name="tpuft_drain_notify", daemon=True
            ).start()

    def drain_requested(self) -> bool:
        """True once a drain notice arrived: the train loop must finish the
        current step, then exit via :meth:`complete_drain`."""
        return self._drain_notice is not None

    def drain_notice(self):
        return self._drain_notice

    def complete_drain(self) -> None:
        """Marks the cooperative departure finished (call after the final
        committed step, before :meth:`shutdown`).  The checkpoint transport
        keeps serving until shutdown so an already-assigned heal against
        this donor can still complete."""
        notice = self._drain_notice
        self._metrics.emit(
            "drain_complete",
            step=self._step,
            batches_committed=self._batches_committed,
            source=notice.source if notice is not None else None,
        )
        self._logger.info(
            f"drain complete at step {self._step}; exiting cleanly"
        )

    # -- state --------------------------------------------------------------

    def load_state_dict(self, state_dict: Dict[str, int]) -> None:
        """Restores manager bookkeeping from a durable checkpoint
        (reference: torchft/manager.py:665-677)."""
        self._step = state_dict["step"]
        self._batches_committed = state_dict["batches_committed"]

    def state_dict(self) -> Dict[str, int]:
        """Manager bookkeeping to persist with the model
        (reference: torchft/manager.py:679-694)."""
        return {"step": self._step, "batches_committed": self._batches_committed}

    def current_step(self) -> int:
        """Current step, incremented on every committed step
        (reference: torchft/manager.py:742-750)."""
        return self._step

    def batches_committed(self) -> int:
        """Total batches committed across all groups and steps
        (reference: torchft/manager.py:752-762)."""
        return self._batches_committed

    def num_participants(self) -> int:
        """Replica groups participating in the current step
        (reference: torchft/manager.py:728-736)."""
        return self._participating_replica_world_size

    def participating_rank(self) -> Optional[int]:
        """This group's rank among participating groups, or None while
        healing / sparing (reference: torchft/manager.py:712-726)."""
        assert self._quorum_future is not None, "quorum not started"
        self.wait_quorum()
        return self._participating_replica_rank

    def is_participating(self) -> bool:
        """False while healing or sparing (reference: torchft/manager.py:696-710)."""
        return self._participating_replica_rank is not None

    def replica_id(self) -> str:
        return self._replica_id

    def store_address(self) -> str:
        return self._store_address

    def collective(self) -> Collective:
        return self._collective

    def _dump_hops(self) -> None:
        """Writes the collective's retained hop timeline to
        ``$TPUFT_HOP_DUMP_DIR/hops_<replica_id>.json`` (best-effort; the
        dump must never fail shutdown).  Records carry wall-clock ``ts``,
        so the trace export time-aligns them with the span stream."""
        dump_dir = os.environ.get("TPUFT_HOP_DUMP_DIR", "")
        if not dump_dir:
            return
        hop_records = getattr(self._collective, "hop_records", None)
        if not callable(hop_records):
            return
        try:
            records = hop_records()
            path = os.path.join(
                dump_dir,
                f"hops_{self._replica_id.replace('/', '_').replace(':', '_')}.json",
            )
            with open(path, "w") as f:
                json.dump(
                    {"replica_id": self._replica_id, "records": records}, f
                )
        except Exception:  # noqa: BLE001
            pass

    def shutdown(self) -> None:
        if self._drain_watcher is not None:
            try:
                self._drain_watcher.stop()
            except Exception:  # noqa: BLE001
                pass
            self._drain_watcher = None
        # Data-plane black box: like $TPUFT_FLIGHT_DIR's control-plane
        # dumps, a departing worker leaves its retained hop timeline as
        # hops_<replica_id>.json when TPUFT_HOP_DUMP_DIR is set —
        # tools/trace_export.py collects these into the per-lane
        # data-plane Perfetto track.
        self._dump_hops()
        self._worker_metrics.close()
        self._spans.flush_subspans()
        self._metrics.close()
        self._executor.shutdown(wait=True)
        if self._checkpoint_transport is not None:
            self._checkpoint_transport.shutdown(wait=False)
        self._client.close()
        self._collective.shutdown()
        if self._manager_server is not None:
            self._manager_server.shutdown()
        if self._store_server is not None:
            self._store_server.shutdown()


def _env_float(name: str, default: float) -> float:
    """Float env knob with a loud-but-safe fallback: a malformed tuning
    value must never abort recovery itself."""
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        logging.getLogger("torchft_tpu.manager").warning(
            "ignoring malformed %s", name
        )
        return default


def _may_average_in_place(out, host: np.ndarray, donate: bool) -> bool:
    """Whether ``out /= num`` gives bit for bit what
    ``(out / num).astype(host.dtype, copy=False)`` gives, in a buffer that is
    the op's to overwrite: a writeable floating array of the caller's dtype
    (an integer payload divides into float64 and casts back) that was donated
    or is not the caller's own memory."""
    return (
        isinstance(out, np.ndarray)
        and out.flags.writeable
        and out.dtype == host.dtype
        and (np.issubdtype(out.dtype, np.floating) or _is_bf16(out.dtype))
        and (donate or not np.may_share_memory(out, host))
    )


def _is_jax_array(x) -> bool:
    try:
        import jax

        return isinstance(x, jax.Array)
    except ImportError:
        return False


class _ManagerLogger:
    """Log prefix "[replica/rank - step N]" (reference: torchft/manager.py:773-792)."""

    def __init__(self, manager: Manager, replica_id: str, rank: int) -> None:
        self._logger = logging.getLogger("torchft_tpu.manager")
        self._replica_id = replica_id
        self._rank = rank
        self._manager = manager

    def prefix(self) -> str:
        return f"[{self._replica_id}/{self._rank} - step {self._manager.current_step()}]"

    def info(self, msg: str) -> None:
        self._logger.info(f"{self.prefix()} {msg}")

    def warn(self, msg: str) -> None:
        self._logger.warning(f"{self.prefix()} {msg}")

    def exception(self, msg: str) -> None:
        self._logger.exception(f"{self.prefix()} {msg}")
