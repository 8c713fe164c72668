"""HTTP checkpoint transport: pull-based live weight recovery.

Reference parity: torchft/checkpointing/http_transport.py.  A threading HTTP
server on every replica streams the current-step state dict to recovering
peers; an RWLock gates serving so the train loop can mutate weights safely
(write-held while training, released while a checkpoint is being served);
the URL scheme is /checkpoint/<step>/{full|header|metadata|<chunk_i>}.

Two performance structures on top of the reference design:

- **Async snapshot pipeline** (donor side): ``send_checkpoint`` only
  enqueues the pytree and opens the serving window — a background worker
  does the device→host flatten into the inactive buffer slot and atomically
  flips the served ``(meta, buffers, step)``, so the donor's train loop
  never blocks on host copies (jax leaves are immutable, making the
  by-reference snapshot safe).  A request for the pending step blocks
  (bounded) until the flip instead of 404ing.

- **Striped multi-donor fetch** (receiver side): ``recv_checkpoint``
  accepts a list of donor URLs, partitions the buffer index space into
  round-robin stripes (the ``chunk_<i>?n=<total>`` framing — receiver
  parameterized, not server config), assigns stripes to donors balanced by
  bytes, pulls them in parallel streaming each tensor straight into its
  preallocated buffer, and fails a stripe over to the next donor on
  error/timeout — so heal bandwidth scales with the donor count and a donor
  dying mid-heal degrades instead of aborting.

Two integrity/redundancy structures on top (this PR):

- **Per-buffer CRC32C**: the background snapshotter checksums every flat
  buffer once per snapshot (meta.crcs); receivers verify each buffer as it
  lands — on the /full path, the striped path, and the shard endpoints — so
  a torn or corrupted stream mid-heal FAILS the fetch (stripe failover,
  then latched error + retry) instead of installing garbage.

- **Erasure-shard endpoints** (torchft_tpu/ec): the same server also hosts
  the group's :class:`~torchft_tpu.ec.store.ShardStore` at
  ``GET/POST /ec/shard/<step>/<idx>`` + ``GET /ec/have/<step>`` — static
  self-verifying bytes served WITHOUT the checkpoint RWLock or a serving
  window, which is what makes reconstruction donor-free.  The snapshotter
  additionally accepts non-serving snapshot enqueues (``enqueue_snapshot``
  with serve=False): the flatten runs and the EC hook fires, but the
  served ``(meta, buffers, step)`` slot is NOT flipped, so per-commit
  encode generations can never 404 a healer mid-fetch.
"""

from __future__ import annotations

import io
import logging
import os
import pickle
import socket
import threading
import time
import urllib.parse
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from torchft_tpu.checkpointing._rwlock import RWLock
from torchft_tpu.checkpointing.serialization import (
    StateDictMeta,
    as_u8,
    flatten_state_dict,
    read_exact,
    read_exact_into,
    read_state_dict,
    state_dict_frames,
    unflatten_state_dict,
    write_state_dict,
)
from torchft_tpu.checkpointing.transport import CheckpointTransport
from torchft_tpu.http import ThreadingHTTPServerV6

logger = logging.getLogger("torchft_tpu.checkpointing.http")


class HTTPTransport(CheckpointTransport):
    """Serves pickled+raw state-dict streams over HTTP.

    Args:
        timeout: per-request deadline.
        num_chunks: if > 0, single-donor receivers that ask the legacy
            ``/metadata`` endpoint are told to split the fetch into this many
            round-robin chunks (reference:
            torchft/checkpointing/http_transport.py:287-298).  Striped
            multi-donor receivers choose their own stripe count instead.
        restore_sharding: optional spec -> jax.Sharding resolver used when
            rebuilding fetched arrays on device.
    """

    # Pull-based: opening the serving window for every recovering group is
    # free, which is what lets striped receivers fetch from all donors.
    serves_all_donors = True

    def __init__(
        self,
        timeout: float = 60.0,
        num_chunks: int = 0,
        restore_sharding: Optional[Callable[[Any], Any]] = None,
    ) -> None:
        self._timeout = timeout
        self._num_chunks = num_chunks
        self._restore_sharding = restore_sharding
        # Held while training mutates weights; released (allow_checkpoint)
        # while a consistent snapshot is being served.
        self._checkpoint_lock = RWLock(timeout=timeout)
        self._checkpoint_lock.w_acquire()
        # Served snapshot + async-snapshotter state, all guarded by
        # _snap_cond: _state/_step are the ACTIVE (served) buffer slot,
        # _snap_pending the newest enqueued-but-not-flattened snapshot
        # (double buffering: the active slot keeps serving while the worker
        # fills the inactive one; the flip is atomic under the condvar).
        self._snap_cond = threading.Condition()
        self._state: Optional[Tuple[StateDictMeta, List[np.ndarray]]] = None
        self._step = -1
        # Pending snapshots keyed by serve flag: a per-commit EC enqueue
        # (serve=False) must never overwrite a pending SERVING enqueue in
        # the single drop-stale slot, and vice versa.  Serving entries are
        # flattened first (a healer is waiting on that flip).
        self._snap_pending: Dict[bool, Tuple[int, Any]] = {}
        self._pending_step = -1
        self._snap_busy = False
        # Flatten errors latched PER KIND: a successful EC (serve=False)
        # flatten must not clear a failed SERVING snapshot's error out of
        # wait_snapshot (and an EC failure must not mark a servable donor
        # failed) — the two pipelines share a worker, not an outcome.
        self._snap_error: Dict[bool, Optional[Exception]] = {}
        self._shutdown = False
        self._spans = None  # optional obs SpanTracker (set_span_tracker)
        # Erasure-shard plane (torchft_tpu/ec): a ShardStore served at
        # /ec/shard/<step>/<idx>, and a hook the background snapshotter
        # calls with every flattened snapshot (the EC encode entry point).
        self._shard_store = None
        self._snapshot_hook: Optional[Callable[[int, StateDictMeta, List[np.ndarray]], None]] = None

        transport = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt: str, *args: object) -> None:
                logger.debug(fmt % args)

            def do_GET(self) -> None:
                path, _, query = self.path.partition("?")
                parts = path.strip("/").split("/")
                # /ec/shard/<step>/<idx> and /ec/have/<step>: the erasure
                # shard plane — static self-verifying bytes served straight
                # from the ShardStore, WITHOUT the checkpoint RWLock or a
                # serving window (the donor-free property).
                if parts and parts[0] == "ec":
                    transport._handle_ec_get(self, parts, query)
                    return
                # /checkpoint/<step>/<what>[?n=<stripes>]
                if len(parts) != 3 or parts[0] != "checkpoint":
                    self.send_error(404, "unknown path")
                    return
                try:
                    step = int(parts[1])
                except ValueError:
                    self.send_error(400, "bad step")
                    return
                what = parts[2]
                n_req: Optional[int] = None
                if query:
                    try:
                        raw_n = urllib.parse.parse_qs(query).get("n", [None])[0]
                        if raw_n is not None:
                            n_req = int(raw_n)
                    except ValueError:
                        self.send_error(400, "bad stripe count")
                        return
                    if n_req is not None and n_req <= 0:
                        self.send_error(400, "bad stripe count")
                        return
                try:
                    # A snapshot for this step may still be flattening on the
                    # worker thread: block (bounded) for the flip instead of
                    # 404ing a healer that raced the async pipeline.
                    transport._await_flip(step)
                    with transport._checkpoint_lock.r_lock(transport._timeout):
                        # Re-check after acquiring the read lock: a request
                        # that arrived before the serving window opened sees
                        # the enqueue only now (r_lock blocked on it), so the
                        # first _await_flip ran before there was anything
                        # pending to wait for.
                        transport._await_flip(step)
                        with transport._snap_cond:
                            if transport._state is None or transport._step != step:
                                self.send_error(
                                    404,
                                    f"checkpoint for step {step} not available "
                                    f"(serving {transport._step})",
                                )
                                return
                            # Buffer references are immutable after the flip:
                            # serving can proceed outside the condvar even if
                            # a newer snapshot flips mid-stream.
                            meta, buffers = transport._state
                        if what == "full":
                            # Stream header + raw buffers straight to the
                            # socket: materializing a multi-GB BytesIO first
                            # is an extra full copy on the default healing
                            # path.  state_dict_frames is the writer's own
                            # framing, so Content-Length cannot drift from
                            # what read_state_dict expects.
                            prefix, total = state_dict_frames(meta, buffers)
                            self.send_response(200)
                            self.send_header(
                                "Content-Type", "application/octet-stream"
                            )
                            self.send_header("Content-Length", str(total))
                            self.end_headers()
                            write_state_dict(
                                meta,
                                buffers,
                                self.wfile,
                                prefix=prefix,
                            )
                            return
                        if what.startswith("chunk_"):
                            # Chunks stream too: building a ~GB chunk in a
                            # BytesIO first costs two full copies made while
                            # holding the GIL, which convoys the parallel
                            # chunk readers (measured 3x worse than
                            # sequential on a 1-core host).
                            framed = transport._chunk_frame(meta, buffers, what, n_req)
                            if framed is None:
                                self.send_error(404, f"unknown object {what}")
                                return
                            sub_prefix, sel, total = framed
                            self.send_response(200)
                            self.send_header(
                                "Content-Type", "application/octet-stream"
                            )
                            self.send_header("Content-Length", str(total))
                            self.end_headers()
                            self.wfile.write(sub_prefix)
                            for i in sel:
                                self.wfile.write(memoryview(as_u8(buffers[i])))
                            return
                        payload = transport._render(meta, buffers, what)
                        if payload is None:
                            self.send_error(404, f"unknown object {what}")
                            return
                        self.send_response(200)
                        self.send_header("Content-Type", "application/octet-stream")
                        self.send_header("Content-Length", str(len(payload)))
                        self.end_headers()
                        self.wfile.write(payload)
                except TimeoutError:
                    self.send_error(503, "checkpoint lock busy")

            def do_POST(self) -> None:
                parts = self.path.partition("?")[0].strip("/").split("/")
                if parts and parts[0] == "ec":
                    transport._handle_ec_post(self, parts)
                    return
                self.send_error(404, "unknown path")

        self._server = ThreadingHTTPServerV6(("", 0), Handler)
        self._port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="tpuft_http_transport", daemon=True
        )
        self._thread.start()
        self._snap_thread = threading.Thread(
            target=self._snapshot_loop, name="tpuft_http_snapshot", daemon=True
        )
        self._snap_thread.start()

    # -- async snapshot pipeline --------------------------------------------

    def set_span_tracker(self, spans) -> None:
        """Wires an :class:`~torchft_tpu.obs.spans.SpanTracker` so the
        background flatten emits ``snapshot`` spans — the evidence in
        ``obs.report`` that snapshotting overlaps the donor's train step
        instead of sitting on its critical path."""
        self._spans = spans

    def attach_shard_store(self, store) -> None:
        """Attaches a :class:`~torchft_tpu.ec.store.ShardStore` so this
        server also serves/accepts erasure shards on ``/ec/...`` (see
        docs/wire.md "Erasure shard endpoints")."""
        self._shard_store = store

    def set_snapshot_hook(
        self, hook: Callable[[int, StateDictMeta, List[np.ndarray]], None]
    ) -> None:
        """Registers a callable run on the BACKGROUND snapshotter after
        every successful flatten — the EC plane's encode entry point
        (:meth:`~torchft_tpu.ec.store.ECPlane.on_snapshot`).  The hook runs
        off the train loop by construction and must not raise."""
        self._snapshot_hook = hook

    def _snapshot_loop(self) -> None:
        """Worker: flatten the newest enqueued pytree into the inactive
        buffer slot, then atomically flip the served snapshot (serving
        enqueues) and fire the snapshot hook (all enqueues)."""
        while True:
            with self._snap_cond:
                while not self._snap_pending and not self._shutdown:
                    self._snap_cond.wait()
                if self._shutdown:
                    return
                # Serving enqueues first: a healer is blocked on that flip,
                # while an EC encode generation only has to land eventually.
                serve = True in self._snap_pending
                step, state_dict = self._snap_pending.pop(serve)
                self._snap_busy = True
            try:
                # Device->host copies happen HERE, off the train loop.  The
                # old snapshot keeps serving from the active slot until the
                # flip below (double buffering).
                if self._spans is not None:
                    with self._spans.span("snapshot", step=step):
                        meta, buffers = self._flatten_with_crcs(state_dict, step)
                else:
                    meta, buffers = self._flatten_with_crcs(state_dict, step)
            except Exception as e:  # noqa: BLE001 — a failed snapshot must
                # not kill the worker; healers see 404 and retry next round.
                logger.exception("async snapshot for step %s failed: %s", step, e)
                with self._snap_cond:
                    self._snap_error[serve] = e
                    self._snap_busy = False
                    if serve and self._pending_step == step:
                        self._pending_step = -1
                    self._snap_cond.notify_all()
                continue
            with self._snap_cond:
                if serve and step >= self._step:
                    self._state = (meta, buffers)
                    self._step = step
                self._snap_error[serve] = None
                if serve and self._pending_step == step:
                    self._pending_step = -1
                # The flip is visible NOW (_await_flip wakes here); the
                # busy flag stays up through the hook so wait_snapshot
                # covers the full pipeline including the EC encode.
                self._snap_cond.notify_all()
            hook = self._snapshot_hook
            if hook is not None:
                try:
                    hook(step, meta, buffers)
                except Exception as e:  # noqa: BLE001 — EC encode is
                    # best-effort; a failure degrades to donor-only healing.
                    logger.exception("snapshot hook for step %s failed: %s", step, e)
            with self._snap_cond:
                self._snap_busy = False
                self._snap_cond.notify_all()

    def _flatten_with_crcs(self, state_dict: Any, step: int):
        """flatten_state_dict + per-buffer CRCs stamped into the header —
        computed ONCE here on the background thread, verified by every
        receiver (full, striped, shard endpoints)."""
        from torchft_tpu.checkpointing.integrity import checksum_buffers

        meta, buffers = flatten_state_dict(state_dict, step=step)
        meta.crc_algo, crcs = checksum_buffers(buffers)
        meta.crcs = tuple(crcs)
        return meta, buffers

    def _await_flip(self, step: int) -> None:
        """Blocks while a snapshot for ``step`` is enqueued/flattening, until
        it becomes servable (or fails / times out)."""
        deadline = time.monotonic() + self._timeout
        with self._snap_cond:
            while (
                self._step < step
                and self._pending_step >= step
                and not self._shutdown
            ):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError("snapshot still pending")
                self._snap_cond.wait(remaining)

    def wait_snapshot(self, timeout: Optional[float] = None) -> bool:
        """Blocks until no snapshot is pending (benches/tests: separates
        snapshot cost from fetch cost).  Returns False on timeout or when
        the last snapshot FAILED to flatten — a silent True here would let
        a bench/test treat an unservable donor as ready."""
        deadline = time.monotonic() + (timeout if timeout is not None else self._timeout)
        with self._snap_cond:
            while (
                self._snap_pending or self._snap_busy or self._pending_step >= 0
            ) and not self._shutdown:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._snap_cond.wait(remaining)
            # Servability is the SERVING pipeline's outcome only: an EC
            # (serve=False) flatten failure degrades the shard plane, not
            # the donor's checkpoint window.
            return self._snap_error.get(True) is None

    # -- serving ------------------------------------------------------------

    def _chunk_frame(
        self,
        meta: StateDictMeta,
        buffers: List[np.ndarray],
        what: str,
        n_req: Optional[int] = None,
    ) -> Optional[Tuple[bytes, List[int], int]]:
        """(sub_meta prefix, selected buffer indices, total body length) for
        one chunk_<i> request, or None for a bad index.  The receiver may
        parameterize the round-robin split via ``?n=<total>`` (striped
        multi-donor fetch); without it the server's own chunk config applies
        (torchft/checkpointing/http_transport.py:287-298)."""
        try:
            idx = int(what[len("chunk_"):])
        except ValueError:
            return None  # malformed chunk index -> 404, not a 500 traceback
        n = n_req if n_req is not None else self._chunk_count(buffers)
        if idx < 0 or idx >= n:
            return None
        sel = [i for i in range(len(buffers)) if i % n == idx]
        sub_meta = pickle.dumps((idx, sel))
        prefix = len(sub_meta).to_bytes(8, "little") + sub_meta
        total = len(prefix) + sum(buffers[i].nbytes for i in sel)
        return prefix, sel, total

    def _render(self, meta: StateDictMeta, buffers: List[np.ndarray], what: str) -> Optional[bytes]:
        out = io.BytesIO()
        if what == "header":
            # Just the length-prefixed pickled StateDictMeta — what a chunked
            # receiver needs to size its buffers, without making the server
            # materialize the full multi-GB stream.  Same framing source as
            # the /full path so the prefix format cannot drift.
            out.write(state_dict_frames(meta, [])[0])
        elif what == "metadata":
            out.write(pickle.dumps(self._chunk_count(buffers)))
        else:
            return None
        return out.getvalue()

    def _chunk_count(self, buffers: List[np.ndarray]) -> int:
        if self._num_chunks <= 0:
            return 1
        return max(1, min(self._num_chunks, len(buffers)))

    # -- erasure shard endpoints (torchft_tpu/ec) ----------------------------

    def _handle_ec_get(self, handler, parts: List[str], query: str = "") -> None:
        """GET /ec/shard/<step>/<idx>[?part=<i>&n=<N>] (one self-verifying
        shard frame, or header + payload byte-range part i of N — the
        striped-receiver idiom of the checkpoint path's ``?n=`` chunks,
        receiver-parameterized so reconstruction chooses its own
        parallelism; see ec.encoder.write_shard_part for the range
        contract) and GET /ec/have/<step> (JSON inventory + geometry).
        Served straight from the ShardStore — no RWLock, no serving
        window."""
        store = self._shard_store
        if store is None:
            handler.send_error(404, "no shard store attached")
            return
        try:
            if len(parts) == 4 and parts[1] == "shard":
                step, idx = int(parts[2]), int(parts[3])
                shard = store.get(step, idx)
                if shard is None:
                    handler.send_error(404, f"shard {idx} for step {step} not held")
                    return
                from torchft_tpu.ec.encoder import write_shard, write_shard_part

                part = n = None
                if query:
                    qs = urllib.parse.parse_qs(query)
                    raw_part = qs.get("part", [None])[0]
                    raw_n = qs.get("n", [None])[0]
                    if raw_part is not None or raw_n is not None:
                        try:
                            part, n = int(raw_part or 0), int(raw_n or 0)
                        except ValueError:
                            handler.send_error(400, "bad shard range")
                            return
                        if n <= 0 or not 0 <= part < n:
                            handler.send_error(400, "bad shard range")
                            return
                body = (
                    write_shard(shard) if n is None
                    else write_shard_part(shard, part, n)
                )
                handler.send_response(200)
                handler.send_header("Content-Type", "application/octet-stream")
                handler.send_header("Content-Length", str(len(body)))
                handler.end_headers()
                handler.wfile.write(body)
                return
            if len(parts) == 3 and parts[1] == "have":
                import json

                body = json.dumps(store.inventory(int(parts[2]))).encode()
                handler.send_response(200)
                handler.send_header("Content-Type", "application/json")
                handler.send_header("Content-Length", str(len(body)))
                handler.end_headers()
                handler.wfile.write(body)
                return
        except ValueError:
            handler.send_error(400, "bad step/shard index")
            return
        handler.send_error(404, "unknown ec path")

    def _handle_ec_post(self, handler, parts: List[str]) -> None:
        """POST /ec/shard/<step>/<idx>: a peer pushing a parity shard.  The
        frame's CRC is verified BEFORE storing — a torn push is refused
        (400), never served onward."""
        store = self._shard_store
        if store is None:
            handler.send_error(404, "no shard store attached")
            return
        if len(parts) != 4 or parts[1] != "shard":
            handler.send_error(404, "unknown ec path")
            return
        try:
            step, idx = int(parts[2]), int(parts[3])
            length = int(handler.headers.get("Content-Length", "0"))
        except ValueError:
            handler.send_error(400, "bad step/shard index")
            return
        if length <= 0:
            handler.send_error(400, "missing body")
            return
        try:
            from torchft_tpu.checkpointing.serialization import read_exact
            from torchft_tpu.ec.encoder import read_shard

            shard = read_shard(bytes(read_exact(handler.rfile, length)))
            if shard.step != step or shard.idx != idx:
                raise IOError(
                    f"shard header ({shard.step},{shard.idx}) != path ({step},{idx})"
                )
        except Exception as e:  # noqa: BLE001 — corrupt push -> 400, not a 500
            # ascii-sanitized: the HTTP status line is latin-1 encoded and
            # error text may carry wider characters.
            msg = f"bad shard frame: {e}".encode("ascii", "replace").decode()
            handler.send_error(400, msg)
            return
        store.put(shard)
        handler.send_response(204)
        handler.send_header("Content-Length", "0")
        handler.end_headers()

    def materialize(self, meta: StateDictMeta, buffers: List[np.ndarray]) -> Any:
        """(meta, buffers) -> the live pytree, through the same sharding
        restorer the donor-fetch path uses — the final leg of an erasure
        reconstruction, shared so the two heal paths cannot diverge."""
        return unflatten_state_dict(meta, buffers, self._restore_sharding)

    def metadata(self) -> str:
        return f"http://{socket.gethostname()}:{self._port}"

    # -- CheckpointTransport ------------------------------------------------

    def send_checkpoint(
        self, dst_ranks: List[int], step: int, state_dict: Any, timeout: float
    ) -> None:
        """Pull-based: enqueue the snapshot and open the serving window.

        Returns immediately — the flatten (device->host copy of every leaf)
        runs on the background snapshotter.  The by-reference capture is
        safe because jax.Arrays are immutable and the Manager builds a fresh
        state-dict tree per call; a caller passing mutable numpy leaves must
        not mutate them in place before the snapshot lands (wait_snapshot).
        """
        self.enqueue_snapshot(step, state_dict, serve=True)
        self.allow_checkpoint(step)

    def enqueue_snapshot(self, step: int, state_dict: Any, serve: bool = True) -> None:
        """Enqueues a snapshot for the background flatten pipeline.

        ``serve=True`` is the send_checkpoint path: the result flips the
        served ``(meta, buffers, step)`` slot.  ``serve=False`` runs the
        SAME pipeline — flatten + CRCs + the EC snapshot hook — but never
        touches the served slot, so the Manager can feed every committed
        step to the erasure encoder without racing a healer's in-flight
        fetch off its step (the serving flip stays quorum-paced).
        Drop-stale per kind: only the newest enqueue of each kind matters.
        """
        with self._snap_cond:
            self._snap_pending[serve] = (step, state_dict)
            if serve:
                self._pending_step = max(self._pending_step, step)
            self._snap_cond.notify_all()

    def allow_checkpoint(self, step: int) -> None:
        if self._checkpoint_lock.w_locked():
            self._checkpoint_lock.w_release()

    def disallow_checkpoint(self) -> None:
        if not self._checkpoint_lock.w_locked():
            if not self._checkpoint_lock.w_acquire(self._timeout):
                raise TimeoutError("timed out re-acquiring checkpoint write lock")

    def recv_checkpoint(
        self,
        src_rank: int,
        metadata: Union[str, Sequence[str]],
        step: int,
        timeout: float,
    ) -> Any:
        """Fetches the checkpoint from one or many donors.

        ``metadata`` may be a single donor base URL or an ordered donor
        list; with several donors the fetch is striped across all of them
        (disjoint byte ranges in parallel) and any stripe fails over to the
        next donor, so one donor dying mid-heal degrades bandwidth instead
        of aborting the heal.
        """
        donors = [metadata] if isinstance(metadata, str) else [m for m in metadata if m]
        if not donors:
            raise ValueError("recv_checkpoint: no donor metadata")
        try:
            forced = int(os.environ.get("TPUFT_HTTP_CHUNK_WORKERS") or 0)
        except ValueError:
            # A malformed tuning knob must not abort recovery itself.
            logger.warning("ignoring malformed TPUFT_HTTP_CHUNK_WORKERS")
            forced = 0

        n_stripes = 0
        if len(donors) == 1:
            base = f"{donors[0]}/checkpoint/{step}"
            n_chunks = pickle.loads(self._fetch(f"{base}/metadata", timeout))
            # Parallel chunk pulls only pay when there are cores to run
            # them: on a 1-core host the decode threads convoy on the GIL —
            # the RECEIVER decides, since the server serves /full regardless
            # of its chunking config.  TPUFT_HTTP_CHUNK_WORKERS overrides
            # the cpu-count heuristic (tests force the chunked path on
            # 1-core CI).
            workers = forced or min(n_chunks, os.cpu_count() or 1)
            if n_chunks <= 1 or workers < 2:
                # Deserialize straight off the socket: buffering the whole
                # multi-GB response into bytes first doubles peak memory and
                # adds a full copy.
                with self._urlopen(f"{base}/full", timeout) as resp:
                    meta, buffers = read_state_dict(resp)
                return unflatten_state_dict(meta, buffers, self._restore_sharding)
            n_stripes = n_chunks
        else:
            workers = forced or max(len(donors), min(2 * len(donors), os.cpu_count() or 1))

        meta, buffers = self._recv_striped(donors, step, n_stripes, workers, timeout)
        return unflatten_state_dict(meta, buffers, self._restore_sharding)

    # -- striped multi-donor receive ----------------------------------------

    def _recv_striped(
        self,
        donors: List[str],
        step: int,
        n_stripes: int,
        workers: int,
        timeout: float,
    ) -> Tuple[StateDictMeta, List[np.ndarray]]:
        dead: set = set()
        meta = self._fetch_header(donors, step, timeout, dead)
        n_tensors = len(meta.tensor_metas)
        if n_tensors == 0:
            return meta, []
        if n_stripes <= 0:
            # Over-stripe 2x the donor count: byte-greedy assignment can
            # then balance donors with heterogeneous tensor sizes, and a
            # dead donor's work splits across the survivors.
            n_stripes = min(n_tensors, max(1, 2 * len(donors)))
        n_stripes = min(n_stripes, n_tensors)
        sels, sizes = _stripe_partition(meta, n_stripes)
        assign = _assign_stripes_by_bytes(sizes, len(donors))

        # Preallocate every tensor's final buffer once; stripe bodies stream
        # straight into these (no whole-chunk bytes materialization, no
        # per-tensor slice copies — this halves peak RSS during heal).
        store = [bytearray(tm.nbytes) for tm in meta.tensor_metas]
        views = [memoryview(b) for b in store]

        def fetch_stripe(idx: int) -> None:
            self._fetch_stripe(
                donors, assign[idx], step, n_stripes, idx, sels[idx], meta, views,
                timeout, dead,
            )

        if workers >= 2 and n_stripes > 1:
            with ThreadPoolExecutor(max_workers=min(workers, n_stripes)) as pool:
                list(pool.map(fetch_stripe, range(n_stripes)))
        else:
            for idx in range(n_stripes):
                fetch_stripe(idx)

        buffers = [
            np.frombuffer(store[i], dtype=np.uint8).view(tm.dtype).reshape(tm.shape)
            for i, tm in enumerate(meta.tensor_metas)
        ]
        return meta, buffers

    def _fetch_header(
        self, donors: List[str], step: int, timeout: float, dead: set
    ) -> StateDictMeta:
        last: Optional[Exception] = None
        for d, donor in enumerate(donors):
            try:
                raw = self._fetch(f"{donor}/checkpoint/{step}/header", timeout)
            except Exception as e:  # noqa: BLE001 — failover to next donor
                dead.add(d)
                last = e
                logger.warning("header fetch from %s failed: %s", donor, e)
                continue
            stream = io.BytesIO(raw)
            header_len = int.from_bytes(stream.read(8), "little")
            return pickle.loads(stream.read(header_len))
        raise RuntimeError(f"all {len(donors)} donors failed serving the header: {last}")

    def _fetch_stripe(
        self,
        donors: List[str],
        assigned: int,
        step: int,
        n: int,
        idx: int,
        sel: List[int],
        meta: StateDictMeta,
        views: List[memoryview],
        timeout: float,
        dead: set,
    ) -> None:
        """Pulls stripe ``idx`` of ``n`` into the preallocated views, failing
        over from the assigned donor through the rest of the rotation."""
        order = [(assigned + k) % len(donors) for k in range(len(donors))]
        candidates = [d for d in order if d not in dead] or order
        last: Optional[Exception] = None
        crcs = getattr(meta, "crcs", None)
        crc_algo = getattr(meta, "crc_algo", None)
        # Single-donor chunked fetches omit the ?n= query: n already equals
        # the chunk count the server advertised on /metadata, and a pre-PR
        # donor's handler cannot parse a query string (rolling-upgrade
        # back-compat the wire doc promises).
        query = f"?n={n}" if len(donors) > 1 else ""
        for attempt, d in enumerate(candidates):
            url = f"{donors[d]}/checkpoint/{step}/chunk_{idx}{query}"
            try:
                with self._urlopen(url, timeout) as resp:
                    sub_len = int.from_bytes(read_exact(resp, 8), "little")
                    got_idx, got_sel = pickle.loads(bytes(read_exact(resp, sub_len)))
                    if got_idx != idx or list(got_sel) != list(sel):
                        raise RuntimeError(
                            f"stripe mismatch: asked ({idx},{n}), got {got_idx}"
                        )
                    for i in got_sel:
                        read_exact_into(resp, views[i])
                        if crcs is not None:
                            # Verify the buffer AS IT LANDS: a corrupt/torn
                            # stripe raises here and fails over to the next
                            # donor — the re-fetch simply overwrites the
                            # same preallocated view.
                            from torchft_tpu.checkpointing.integrity import verify

                            verify(
                                views[i], crcs[i], crc_algo,
                                f"stripe {idx}/{n} buffer {i} from {donors[d]}",
                            )
                return
            except Exception as e:  # noqa: BLE001 — stripe failover
                last = e
                dead.add(d)
                if attempt + 1 < len(candidates):
                    logger.warning(
                        "stripe %d/%d from %s failed (%s); failing over to %s",
                        idx, n, donors[d], e, donors[candidates[attempt + 1]],
                    )
        raise RuntimeError(
            f"stripe {idx}/{n} failed on all {len(candidates)} donors: {last}"
        )

    def _fetch(self, url: str, timeout: float) -> bytes:
        with self._urlopen(url, timeout) as resp:
            return resp.read()

    def _urlopen(self, url: str, timeout: float):
        """Single indirection for every receiver-side HTTP open (tests hook
        this to inject donor death deterministically)."""
        return urllib.request.urlopen(url, timeout=timeout)

    def shutdown(self, wait: bool = True) -> None:
        with self._snap_cond:
            self._shutdown = True
            self._snap_cond.notify_all()
        self._server.shutdown()
        self._server.server_close()
        if wait:
            self._thread.join(timeout=5)
            self._snap_thread.join(timeout=5)


def _stripe_partition(
    meta: StateDictMeta, n: int
) -> Tuple[List[List[int]], List[int]]:
    """Round-robin buffer-index stripes and their byte sizes — must mirror
    the server's ``sel`` arithmetic in ``_chunk_frame`` exactly."""
    sels: List[List[int]] = [[] for _ in range(n)]
    sizes = [0] * n
    for i, tm in enumerate(meta.tensor_metas):
        sels[i % n].append(i)
        sizes[i % n] += tm.nbytes
    return sels, sizes


def _assign_stripes_by_bytes(sizes: List[int], n_donors: int) -> List[int]:
    """Greedy byte-balanced stripe->donor assignment (largest stripes first
    onto the least-loaded donor), so heterogeneous tensor sizes don't leave
    one donor's link idle while another's saturates."""
    loads = [0] * n_donors
    assign = [0] * len(sizes)
    for idx in sorted(range(len(sizes)), key=lambda s: -sizes[s]):
        d = min(range(n_donors), key=lambda j: loads[j])
        assign[idx] = d
        loads[d] += sizes[idx]
    return assign
