"""Replica-dimension gradient averaging (the DDP analogue).

Reference parity: torchft/ddp.py.  The reference subclasses torch DDP and
installs a comm hook that routes each gradient bucket through
``manager.allreduce`` so reduction overlaps with the rest of backward
(torchft/ddp.py:47-71).  JAX has no autograd hooks — ``jax.grad`` returns the
whole gradient pytree at once — so the overlap point moves to the bucket
stream: leaves are coalesced into fixed-size flat buckets **planned once
per tree shape and packed into persistent preallocated buffers**, and the
exchange runs over them as ONE STREAM in the plan's fetch order (largest
bucket first): a bucket's device->host fetch blocks, its cross-group
allreduce is issued the moment it lands, and every ring op that has resolved
by then goes straight back to the device (``jax.device_put`` returns at
once) — so ring and way back run under the fetches of the buckets still on
the device, and with a multi-lane ring collective (``TPUFT_RING_LANES``) the
buckets overlap each other on the wire too.  A fetch starts its own
device->host copy and nothing is started ahead of it: a second transfer on a
host slows both (0.28–0.29 GB/s a process against 0.42 alone; PERF.md
section 6, PRs 28 and 30).

Wire preparation can run ON DEVICE (``device_wire_prep=True`` /
``TPUFT_DEVICE_WIRE_PREP=1``): a cached jitted epilogue casts each float
bucket to the collective's wire dtype (bf16) and lays it out flat in HBM, so
the D2H fetch moves wire bytes — half the f32 bytes — instead of staging a
full-width copy through host memory and casting on CPU.  The bf16
quantization point moves from the host encode to the device epilogue; the
wire bytes are BITWISE identical (pinned in tests/test_device_prep.py), and
local ring accumulation stays in float32 (collectives.py treats
already-wire-dtype payloads as pre-encoded).  ``sharded_fetch=True``
additionally shards the flat bucket across the local devices: each shard
slice is fetched straight off its device (no XLA gather into a replicated
host copy — on a multi-host group each host pulls only its
``addressable_shards``), ring-reduced as its own tagged op (the
cross-group allreduce becomes per-slice reduce-scatter + allgather aligned
with the in-group sharding, ZeRO-style), and scattered back per-shard with
``jax.device_put`` under the leaf's original sharding.

The per-bucket D2H wait runs in an ``allreduce_d2h`` span (``pos``: its
place in the fetch order), each harvest of resolved buckets in a short
``allreduce_h2d`` span between two fetches (so a step holds several), the
waits for what is still on the ring after the last fetch in
``allreduce_merge``, and the one wait for every put in a last
``allreduce_h2d`` (all FT time, never charged as
productive compute — obs/report.py and the straggler sentinel depend on
that).  ``step_summary.exchange_stream`` says how far the stream engaged.

Where the leaves live on an accelerator, each one's transfer takes its turn
with those of every other tpu-ft process on the machine (the host's D2H
lease, ``d2h_lease.py``, taken in ``futures.device_get_into`` around
``np.asarray`` alone): co-located groups fetch one after the other, first
come, first served, so all of them land position ``p`` before any fetches
``p + 1``, and one group's transfer runs under the others' copies, ring ops
and puts.  ``exchange_stream`` counts the fetches that went through it.

``PerLeafGradientAverager`` mirrors PureDistributedDataParallel's
per-parameter variant (torchft/ddp.py:74-97).
"""

from __future__ import annotations

import functools
import os
import threading
import time
from concurrent.futures import Future
from contextlib import nullcontext
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.manager import Manager

__all__ = [
    "ElasticBatchScaler",
    "GradientAverager",
    "PerLeafGradientAverager",
    "allreduce_pytree",
    "plan_buckets",
]

TPUFT_DEVICE_WIRE_PREP_ENV = "TPUFT_DEVICE_WIRE_PREP"

# Elastic batch engine (docs/architecture.md "Elastic scale").  The fleet's
# samples-per-step is the training contract (LR schedule, convergence
# trajectory); membership is not.  When the quorum shrinks, survivors each
# take a LARGER share via extra gradient-accumulation microsteps, and when
# spares hot-admit the share shrinks back — the global batch in every
# committed step record stays pinned.  Enabled by setting
# TPUFT_ELASTIC_GLOBAL_BATCH; the Manager rebuilds the plan on every
# quorum transition and hands it to membership callbacks.
TPUFT_ELASTIC_GLOBAL_BATCH_ENV = "TPUFT_ELASTIC_GLOBAL_BATCH"


def _env_flag(name: str, default: bool = False) -> bool:
    """Truthy env-flag parsing, shared with the semisync plane so the
    accepted token set cannot drift between data planes."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    return raw.strip().lower() in ("1", "true", "on", "yes")


class ElasticBatchScaler:
    """Constant-global-batch rescaling across membership churn.

    ``plan(participants, rank)`` splits the fixed ``global_batch`` across
    the CURRENT participant set: each group takes ``global_batch //
    participants`` samples (the first ``global_batch % participants``
    groups take one extra, so the split is exact — no rounding drift in
    the committed global batch), runs them as ``ceil(share / microbatch)``
    accumulation microsteps of at most ``microbatch`` samples, and the
    per-step examples/s the goodput ledger scores stays proportional to
    live capacity instead of collapsing to zero while a respawn rejoins.

    LR scaling is OPTIONAL and off by default: with the global batch held
    constant the LR schedule needs no correction (that is the point).
    ``scale_lr="linear"``/``"sqrt"`` support the other elastic policy —
    per-group batch held fixed, global batch breathing with membership —
    where ``lr_scale`` follows participants relative to
    ``base_participants`` (first membership seen, unless pinned by arg).
    """

    def __init__(
        self,
        global_batch: int,
        microbatch: int = 1,
        scale_lr: str = "none",
        base_participants: Optional[int] = None,
    ) -> None:
        if global_batch <= 0:
            raise ValueError(f"global_batch must be positive, got {global_batch}")
        if microbatch <= 0:
            raise ValueError(f"microbatch must be positive, got {microbatch}")
        if scale_lr not in ("none", "linear", "sqrt"):
            raise ValueError(
                f"scale_lr must be 'none', 'linear' or 'sqrt', got {scale_lr!r}"
            )
        self.global_batch = int(global_batch)
        self.microbatch = int(microbatch)
        self.scale_lr = scale_lr
        self.base_participants = (
            int(base_participants) if base_participants else None
        )

    @classmethod
    def from_env(cls) -> Optional["ElasticBatchScaler"]:
        """The scaler for TPUFT_ELASTIC_GLOBAL_BATCH with the class's own
        defaults, or None when elastic batching is off (the variable unset,
        malformed or not positive)."""
        raw = os.environ.get(TPUFT_ELASTIC_GLOBAL_BATCH_ENV)
        if not raw:
            return None
        try:
            global_batch = int(raw)
        except ValueError:
            return None
        if global_batch <= 0:
            return None
        return cls(global_batch)

    def plan(self, participants: int, rank: Optional[int] = None) -> Dict[str, Any]:
        """The batch plan for one membership: exact constant-global-batch
        split, this group's share (when ``rank`` is given), and the
        accumulation microstep count that realizes it."""
        participants = max(1, int(participants))
        if self.base_participants is None:
            self.base_participants = participants
        base_share, extra = divmod(self.global_batch, participants)
        if rank is not None and 0 <= rank < participants:
            group_batch = base_share + (1 if rank < extra else 0)
        else:
            # Membership-wide view (no rank): the largest share, which is
            # what sizes a survivor's worst-case accumulation loop.
            group_batch = base_share + (1 if extra else 0)
        accum_steps = max(1, -(-group_batch // self.microbatch))
        if self.scale_lr == "linear":
            lr_scale = participants / self.base_participants
        elif self.scale_lr == "sqrt":
            lr_scale = (participants / self.base_participants) ** 0.5
        else:
            lr_scale = 1.0
        return {
            "participants": participants,
            "global_batch": self.global_batch,
            "group_batch": group_batch,
            "microbatch": min(self.microbatch, group_batch) or 1,
            "accum_steps": accum_steps,
            "lr_scale": lr_scale,
        }


class _Unresolved:
    """Sentinel distinguishing "wire target not probed yet" from "probed:
    no wire cast" (None)."""


_UNRESOLVED = _Unresolved()

# Serializes MULTI-DEVICE (sharded) jit executions across averagers in one
# process.  A sharded epilogue/inverse is an SPMD program with cross-device
# collectives; when several replica groups share a process (the threaded
# bench and the test harness — never the deployment shape, which is one
# process per group), two such programs dispatched concurrently interleave
# their device rendezvous and deadlock XLA's CPU collective runtime.  The
# lock holder blocks until its program completes, so executions never
# overlap; single-device prep (the common case) takes no lock and keeps
# full async dispatch.
_SHARDED_EXEC_LOCK = threading.Lock()


class _Bucket:
    """One dtype-homogeneous flat slice of a bucket plan: which leaves it
    packs (original tree indices), where each lives in the flat buffer, and
    how big the whole bucket is.  Pure metadata — the backing buffer lives
    in the :class:`_BucketPlan` and is reused across steps."""

    def __init__(
        self,
        indices: List[int],
        shapes: List[tuple],
        sizes: List[int],
        dtype: np.dtype,
    ) -> None:
        self.indices = indices
        self.shapes = shapes
        self.sizes = sizes
        self.dtype = np.dtype(dtype)
        # True for split-out 0-d/scalar buckets under device wire prep:
        # they must travel FULL WIDTH (allow_wire_compression=False) — the
        # documented loss-scalar precision contract, not just a fetch-path
        # choice.
        self.wire_bypass = False
        self.offsets: List[int] = []
        off = 0
        for size in sizes:
            self.offsets.append(off)
            off += size
        self.numel = off
        self.nbytes = off * self.dtype.itemsize

    def unpack(self, flat: np.ndarray) -> List[Tuple[int, np.ndarray]]:
        """(leaf index, reshaped view into ``flat``) per packed leaf."""
        return [
            (idx, flat[off : off + size].reshape(shape))
            for idx, off, size, shape in zip(
                self.indices, self.offsets, self.sizes, self.shapes
            )
        ]


def plan_buckets(
    metas: Sequence[Tuple[tuple, Any]], bucket_bytes: int
) -> List[_Bucket]:
    """Plans the bucket layout for a leaf list given ``(shape, dtype)`` per
    leaf.

    Leaves are sort-stable GROUPED BY DTYPE first (a tree whose dtypes
    alternate — f32, i32, f32, i32 — packs into two buckets, not one per
    leaf; the original index mapping is preserved in ``_Bucket.indices``),
    then packed greedily up to ``bucket_bytes``.  A single leaf larger than
    ``bucket_bytes`` gets its own bucket.  An empty leaf list plans to no
    buckets.
    """
    order = sorted(
        # .name, not .str: distinct ml_dtypes (float8 variants, int4) share
        # the opaque '<V1' str and would interleave instead of grouping.
        range(len(metas)), key=lambda i: np.dtype(metas[i][1]).name
    )  # stable: same-dtype leaves keep their relative order
    buckets: List[_Bucket] = []
    cur_idx: List[int] = []
    cur_shapes: List[tuple] = []
    cur_sizes: List[int] = []
    cur_bytes = 0
    cur_dtype: Any = None

    def flush() -> None:
        nonlocal cur_idx, cur_shapes, cur_sizes, cur_bytes
        if cur_idx:
            buckets.append(_Bucket(cur_idx, cur_shapes, cur_sizes, cur_dtype))
        cur_idx, cur_shapes, cur_sizes, cur_bytes = [], [], [], 0

    for i in order:
        shape, dtype = metas[i]
        dtype = np.dtype(dtype)
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = size * dtype.itemsize
        if cur_idx and (cur_bytes + nbytes > bucket_bytes or dtype != cur_dtype):
            flush()
        cur_idx.append(i)
        cur_shapes.append(tuple(shape))
        cur_sizes.append(size)
        cur_bytes += nbytes
        cur_dtype = dtype
    flush()
    return buckets


class _DeviceBucket:
    """Device-resident wire prep for one bucket.

    Holds the cached jitted **epilogue** that lays the bucket's leaves out
    flat in HBM cast to the fetch dtype (the collective's wire dtype for
    float buckets — so D2H moves wire bytes), the persistent fetch-dtype
    host buffer the copy lands in, and the jitted **inverse** that slices,
    reshapes and casts reduced results back to the leaf dtype on device
    (so H2D also moves wire bytes and the upcast spends HBM bandwidth, not
    host CPU).

    With ``sharded=True`` and more than one local device the epilogue's
    output is laid out sharded across all local devices on the flat axis
    (padded to a device multiple; the pad reduces zeros and is dropped by
    the inverse), so the fetch can pull each shard straight off its device
    via ``addressable_shards`` — on a multi-host replica group each host
    only holds (and only fetches) its own slice.
    """

    def __init__(self, bucket: _Bucket, fetch_dtype: Any, sharded: bool) -> None:
        import jax
        import jax.numpy as jnp

        self.bucket = bucket
        self.fetch_dtype = np.dtype(fetch_dtype)
        self.pad = 0
        out_shardings = None
        if sharded:
            devs = jax.local_devices()
            if len(devs) > 1:
                from jax.sharding import Mesh, NamedSharding, PartitionSpec

                self.pad = (-bucket.numel) % len(devs)
                mesh = Mesh(np.asarray(devs), ("wire",))
                out_shardings = NamedSharding(mesh, PartitionSpec("wire"))
        self.numel = bucket.numel + self.pad
        self.buffer = np.empty(self.numel, dtype=self.fetch_dtype)
        # Multi-device (sharded) programs must serialize per process — see
        # _SHARDED_EXEC_LOCK.
        self.multi_device = out_shardings is not None
        # The epilogue output's sharding from the LAST prep call — the
        # scatter-back places results with the same per-device layout.
        self.last_sharding: Any = None

        fetch = self.fetch_dtype
        pad = self.pad

        def prep(leaves: List[Any]):
            flat = (
                jnp.concatenate([jnp.ravel(l) for l in leaves])
                if len(leaves) > 1
                else jnp.ravel(leaves[0])
            )
            flat = flat.astype(fetch)
            if pad:
                flat = jnp.pad(flat, (0, pad))
            return flat

        self.prep = (
            jax.jit(prep)
            if out_shardings is None
            else jax.jit(prep, out_shardings=out_shardings)
        )

        numel = bucket.numel
        offsets, sizes, shapes = bucket.offsets, bucket.sizes, bucket.shapes
        orig_dtype = bucket.dtype

        def unprep(flat):
            flat = flat[:numel].astype(orig_dtype)
            return [
                flat[off : off + size].reshape(shape)
                for off, size, shape in zip(offsets, sizes, shapes)
            ]

        self.unprep = jax.jit(unprep)


def _lives_in_host_memory(leaf) -> bool:
    """Whether ``jax.device_put`` to this jax leaf's devices may hand back an
    array that aliases its numpy source: the CPU backend does (zero-copy, for
    a source aligned to 64 bytes); an accelerator copies into its own memory."""
    return any(d.platform == "cpu" for d in leaf.devices())


def _shard_slices(flat_dev) -> Optional[List[Tuple[Any, int, int]]]:
    """``[(shard, start, stop)]`` covering a 1-D device array contiguously,
    one entry per addressable shard — or None when the layout is not a
    clean disjoint 1-D partition (single device, replicated across devices,
    or an exotic index), in which case the caller falls back to one
    full-width fetch."""
    try:
        shards = list(flat_dev.addressable_shards)
    except Exception:  # noqa: BLE001 — non-jax input (tests, numpy fallback)
        return None
    if len(shards) <= 1:
        return None
    n = int(flat_dev.shape[0])
    parts: List[Tuple[int, int, Any]] = []
    for s in shards:
        idx = s.index
        if (
            len(idx) != 1
            or not isinstance(idx[0], slice)
            or idx[0].step not in (None, 1)
        ):
            return None
        start = idx[0].start or 0
        stop = idx[0].stop if idx[0].stop is not None else n
        parts.append((start, stop, s))
    parts.sort(key=lambda t: t[0])
    pos = 0
    for start, stop, _ in parts:
        if start != pos:
            return None  # replicated or overlapping layout
        pos = stop
    if pos != n:
        return None
    return [(s, start, stop) for start, stop, s in parts]


class _BucketPlan:
    """A bucket layout plus its persistent flat buffers and precomputed
    pack views — allocated once per (treedef, shapes, dtypes) and reused
    every step, so the steady-state data plane does zero per-step
    concatenate/allocation work on the packing side.

    When device wire prep / sharded fetch is configured, each eligible
    bucket additionally carries a :class:`_DeviceBucket` (jitted epilogue +
    wire-dtype buffer).  Eligibility: every leaf has ndim >= 1 (0-d and
    Python-scalar leaves keep the full-width host path), and for the wire
    CAST the bucket dtype must be a real float of >= 4 bytes — integer and
    sub-f32 buckets ride full width, exactly like the collective's own
    compression gate."""

    def __init__(
        self,
        metas: Sequence[Tuple[tuple, Any]],
        bucket_bytes: int,
        wire_dtype: Optional[np.dtype] = None,
        sharded: bool = False,
        jax_leaves: Optional[Sequence[bool]] = None,
    ) -> None:
        self.buckets = plan_buckets(metas, bucket_bytes)
        if wire_dtype is not None or sharded:
            # 0-d leaves must bypass wire compression full-width (a loss
            # scalar's precision matters more than 2 bytes of wire), but
            # they must not drag an entire f32 gradient bucket back onto
            # the host-cast path — split them out into their own bucket.
            split: List[_Bucket] = []
            for b in self.buckets:
                zero = [k for k, s in enumerate(b.shapes) if len(s) == 0]
                if zero and len(zero) < len(b.indices):
                    keep = [k for k in range(len(b.indices)) if k not in zero]
                    for sel in (keep, zero):
                        nb = _Bucket(
                            [b.indices[k] for k in sel],
                            [b.shapes[k] for k in sel],
                            [b.sizes[k] for k in sel],
                            b.dtype,
                        )
                        nb.wire_bypass = sel is zero
                        split.append(nb)
                else:
                    if b.shapes and all(len(s) == 0 for s in b.shapes):
                        b.wire_bypass = True
                    split.append(b)
            self.buckets = split
        # The order the streamed exchange takes the buckets in: largest first
        # (ties by plan index), so the big ring ops are on the wire while
        # most of the tree is still to be fetched and the tail after the last
        # fetch is the smallest bucket's.  A function of the tree's signature
        # alone: peers pair ring ops by their order of issue, so every group
        # must take the same order.
        self.fetch_order: List[int] = sorted(
            range(len(self.buckets)), key=lambda k: (-self.buckets[k].nbytes, k)
        )
        self.device: List[Optional[_DeviceBucket]] = []
        for b in self.buckets:
            dev: Optional[_DeviceBucket] = None
            # Device mode needs leaves that already LIVE on device: running
            # the epilogue on numpy leaves would upload full-width f32 just
            # to fetch bf16 back — strictly more transfer than the host
            # cast it replaces.
            eligible = all(len(s) > 0 for s in b.shapes) and (
                jax_leaves is not None
                and all(jax_leaves[i] for i in b.indices)
            )
            cast = (
                wire_dtype is not None
                and np.issubdtype(b.dtype, np.floating)
                and b.dtype.itemsize >= 4
            )
            if eligible and (cast or sharded):
                dev = _DeviceBucket(b, wire_dtype if cast else b.dtype, sharded)
            self.device.append(dev)
        # Host-path flat buffers ONLY for host-path buckets: a device-
        # prepped bucket fetches into its _DeviceBucket.buffer and never
        # touches these — allocating both would hold a dead full-width f32
        # copy of every wire-prepped gradient (~3x the feature's memory).
        self.buffers: List[Optional[np.ndarray]] = [
            None if d is not None else np.empty(b.numel, dtype=b.dtype)
            for b, d in zip(self.buckets, self.device)
        ]
        # views[k]: [(leaf index, writable reshaped view into buffers[k])].
        self.views: List[List[Tuple[int, np.ndarray]]] = [
            [] if buf is None else b.unpack(buf)
            for b, buf in zip(self.buckets, self.buffers)
        ]


class GradientAverager:
    """Coalesced fault-tolerant gradient averaging across replica groups.

    The bucket size default matches torch DDP's 25 MB first-bucket heuristic;
    larger buckets amortize DCN round-trips, smaller ones start the overlap
    earlier.

    ``pipelined=True`` (default) streams the buckets (see the module
    docstring): largest first, each ring op issued as its bucket lands, each
    resolved bucket sent home while later ones are still leaving the device.
    ``pipelined=False`` is the monolithic reference path — one blocking
    ``device_get_tree`` of every leaf, then pack+issue in plan order, one
    drain, one scatter-back — kept for debugging and as the tests' oracle.

    ``device_wire_prep`` (default: ``TPUFT_DEVICE_WIRE_PREP``) moves the
    cast to the collective's wire dtype onto the device as a jitted
    per-bucket epilogue, halving ``allreduce_d2h`` bytes for f32 gradients
    when the collective wires bf16; ``sharded_fetch`` additionally fetches
    and ring-reduces each bucket per local-device shard slice (see the
    module docstring).  Both
    apply to the streamed path only — the monolithic path stays the
    untouched host-cast reference for A/B.  Submission order of the ring
    ops (the plan's fetch order, a function of the tree's signature alone;
    per slice within a bucket) is part of the cross-rank tag contract: every
    replica group must run the same mode, like every other collective
    knob — and for ``sharded_fetch`` the contract is ENVIRONMENTAL too:
    every group's process must see the SAME local device count (slice
    count and pad boundaries derive from it; heterogeneous counts desync
    the ring-op seq/tag stream exactly like mismatched lane counts or
    program order would).  Keep sharded fetch off on heterogeneous
    fleets.
    """

    def __init__(
        self,
        manager: Manager,
        bucket_bytes: int = 25 << 20,
        pipelined: bool = True,
        device_wire_prep: Optional[bool] = None,
        sharded_fetch: bool = False,
    ) -> None:
        self._manager = manager
        self._bucket_bytes = bucket_bytes
        self._pipelined = pipelined
        if device_wire_prep is None:
            device_wire_prep = _env_flag(TPUFT_DEVICE_WIRE_PREP_ENV)
        self._device_wire_prep = bool(device_wire_prep)
        self._sharded_fetch = bool(sharded_fetch)
        self._wire_np: Any = _UNRESOLVED
        self._plans: Dict[Any, _BucketPlan] = {}
        # Transfer accounting for the LAST allreduce call: d2h/h2d/wire
        # bytes, bucket/slice counts.  tests/ring_cells.py reads this per
        # step; the same numbers ride the span records (bytes field) and
        # the Manager's step_summary (note_d2h/note_h2d).
        self.last_stats: Dict[str, int] = {}

    @property
    def manager(self) -> Manager:
        return self._manager

    @property
    def device_wire_prep(self) -> bool:
        return self._device_wire_prep

    @property
    def sharded_fetch(self) -> bool:
        return self._sharded_fetch

    def _wire_target(self) -> Optional[np.dtype]:
        """The np dtype the collective would put on the wire for float
        payloads (None = full width).  Resolved once — the wire encoding is
        fixed at collective construction; a swapped-in collective without
        the ``wire_dtype`` probe (tests, wrappers) resolves to None and the
        averager degrades to the host-cast path."""
        if self._wire_np is not _UNRESOLVED:
            return self._wire_np
        wire: Optional[np.dtype] = None
        try:
            wd = getattr(self._manager.collective(), "wire_dtype", None)
        except Exception:  # noqa: BLE001 — mocked managers
            wd = None
        if wd == "bf16":
            import ml_dtypes

            wire = np.dtype(ml_dtypes.bfloat16)
        self._wire_np = wire
        return wire

    def _note(self, kind: str, nbytes: int) -> None:
        """Best-effort transfer-byte note into the Manager's step_summary
        accounting; a swapped-in manager without the hook is fine."""
        fn = getattr(
            self._manager, "note_d2h" if kind == "d2h" else "note_h2d", None
        )
        if callable(fn):
            try:
                fn(int(nbytes))
            except Exception:  # noqa: BLE001 — telemetry only
                pass

    def _plan_for(
        self, leaves: List[Any], treedef: Any, jax_leaves: Sequence[bool]
    ) -> _BucketPlan:
        """The cached plan for this tree signature (treedef + per-leaf
        shape/dtype + device-residency); a new signature plans and
        allocates fresh buffers."""
        metas = [(tuple(l.shape), np.dtype(l.dtype)) for l in leaves]
        # d.name, not d.str: many distinct ml_dtypes (float8 variants, int4)
        # share the opaque '<V1' str and would collide on one cached plan.
        # jax-ness is part of the signature: device-bucket eligibility
        # depends on it, and a tree alternating numpy/jax leaves across
        # calls must not reuse a plan built for the other residency.
        # Participant count is part of the signature too: membership churn
        # then costs one plan per count instead of invalidating the cache,
        # and a recurring count (a spare leaving and hot-admitting back)
        # re-hits its old plan and buffers instead of replanning.
        try:
            participants = int(self._manager.num_participants() or 0)
        except Exception:  # noqa: BLE001 — a bare collective has no quorum
            participants = 0
        key = (
            treedef,
            tuple((s, d.name) for s, d in metas),
            tuple(jax_leaves),
            participants,
        )
        plan = self._plans.pop(key, None)
        if plan is None:
            if len(self._plans) >= 8:
                # A churning signature set (odd for a train loop) must not
                # pin unbounded buffer memory — evict the least recently
                # used plan only (the hit below re-inserts, so dict order
                # IS recency order), keeping a multi-signature workload's
                # hot plans alive instead of replanning everything.
                self._plans.pop(next(iter(self._plans)))
            wire = (
                self._wire_target()
                if self._device_wire_prep and self._pipelined
                else None
            )
            sharded = self._sharded_fetch and self._pipelined
            plan = _BucketPlan(
                metas,
                self._bucket_bytes,
                wire_dtype=wire,
                sharded=sharded,
                jax_leaves=jax_leaves,
            )
        self._plans[key] = plan
        return plan

    def allreduce(self, grads: Any) -> Any:
        """Averages a gradient pytree across participating replica groups.

        Blocks until every bucket resolves; collective failures leave the
        corresponding leaves untouched (error latched in the Manager, step
        resolved at should_commit — reference: torchft/manager.py:262-323).
        """
        import jax

        from torchft_tpu.futures import LEASE_COUNTERS, device_get_tree

        leaves, treedef = jax.tree.flatten(grads)
        if not leaves:
            return grads

        # Alone in the ring and participating: averaging is the identity and
        # the device->host roundtrip is pure waste — skip before any copy.
        self._manager.wait_quorum()
        if (
            self._manager.errored() is None
            and self._manager.collective().size() == 1
            and self._manager.is_participating()
        ):
            return grads

        is_jax = [isinstance(l, jax.Array) for l in leaves]
        # Python scalars (a float loss riding in the grad tree) carry no
        # .shape/.dtype — promote them to 0-d arrays so planning and the
        # D2H copy see uniform leaves, as the monolithic asarray path did.
        leaves = [
            l if hasattr(l, "shape") else np.asarray(l) for l in leaves
        ]
        plan = self._plan_for(leaves, treedef, is_jax)
        stats = {
            "d2h_bytes": 0,
            "h2d_bytes": 0,
            "wire_bytes": 0,
            "buckets": len(plan.buckets),
            "device_buckets": sum(1 for d in plan.device if d is not None),
            "slices": 0,
            # The streamed path's fetches under the host's D2H lease.
            **dict.fromkeys(LEASE_COUNTERS, 0),
        }
        self.last_stats = stats
        # Per-hop WIRE bytes a bucket's payload travels as — NOT what this
        # host hands the collective.  The host-cast path hands f32 buffers
        # that the ring encodes to bf16 per hop, so counting buf.nbytes
        # would make the device-prep A/B read as a 2x wire saving that the
        # encode already provided; both modes must report the same wire
        # bytes (only d2h_bytes moves).  The collective's own wire_nbytes
        # probe is the source of truth (same one the Manager's GB/s gauge
        # consults); the inline gate is only the fallback for swapped-in
        # collectives without it.
        wire_target = self._wire_target()
        try:
            wire_probe = getattr(
                self._manager.collective(), "wire_nbytes", None
            )
        except Exception:  # noqa: BLE001 — mocked managers
            wire_probe = None

        def wire_nbytes(b: _Bucket) -> int:
            if callable(wire_probe):
                try:
                    per_el = int(
                        wire_probe(
                            np.empty(1, dtype=b.dtype), not b.wire_bypass
                        )
                    )
                    return per_el * b.numel
                except Exception:  # noqa: BLE001 — non-conforming mock
                    pass
            if (
                wire_target is not None
                and not b.wire_bypass
                and np.issubdtype(b.dtype, np.floating)
            ):
                return b.numel * wire_target.itemsize
            return b.nbytes

        if self._pipelined:
            return self._allreduce_streamed(
                grads, leaves, treedef, is_jax, plan, stats, wire_nbytes
            )

        # Monolithic reference path: one deadline-guarded fetch of the
        # whole tree, then pack+issue every bucket in plan order, one drain,
        # one scatter-back.  Every bucket is a host-path bucket here: device
        # wire prep and sharded fetch are planned for the streamed path only.
        step = self._manager.current_step()
        timeout = self._manager.timeout.total_seconds()
        with self._manager.spans.span("allreduce_d2h", step=step) as sp:
            try:
                hosts = device_get_tree(leaves, timeout)
            except TimeoutError as e:
                self._manager.report_error(e)
                return grads
            d2h = sum(int(getattr(l, "nbytes", 0)) for l in leaves)
            sp.fields["bytes"] = d2h
        stats["d2h_bytes"] += d2h
        self._note("d2h", d2h)

        pending: List[Tuple[_Bucket, np.ndarray, Future]] = []
        for k, (bucket, buf, views) in enumerate(
            zip(plan.buckets, plan.buffers, plan.views)
        ):
            for i, view in views:
                np.copyto(view, np.asarray(hosts[i]).reshape(view.shape))
            stats["wire_bytes"] += wire_nbytes(bucket)
            pending.append((bucket, buf, self._issue(bucket, buf, k)))

        out: List[Any] = list(leaves)
        # The bucket drain blocks this (train) thread on the ring exchange —
        # i.e. on the SLOWEST peer's gradients.  Span it as allreduce_merge:
        # unrecorded, this wait would be charged as productive/busy time,
        # and on a cluster with one slow host EVERY fast replica would read
        # as busy for the whole stall — hiding exactly the straggler the
        # step-time telemetry exists to expose (the commit-time drain of
        # what remains keeps the same phase name; the accumulator sums).
        with self._manager.spans.span("allreduce_merge", step=step):
            resolved = [fut.result() for _bucket, _buf, fut in pending]

        # Scatter-back, spanned as allreduce_h2d — like the fetch, this is
        # FT time on the train thread, never productive compute.
        # Collective failures resolve a bucket to its own input buffer
        # (wrap_future's default); those buckets keep their ORIGINAL leaves
        # untouched — the error is latched and the commit vote fails.
        with self._manager.spans.span("allreduce_h2d", step=step) as sp_h2d:
            h2d_bytes = 0
            for k, ((bucket, buf, _fut), res) in enumerate(zip(pending, resolved)):
                # One bucket's way back: unpack.
                with self._manager.spans.sub(
                    "h2d_put", step=step, bucket=k, bytes=bucket.nbytes
                ):
                    flat = np.asarray(res)
                    if flat is buf:
                        # Latched failure resolved to the donated staging
                        # buffer — with donate the op may have half-reduced
                        # it, so it must not be republished as gradients.
                        # Leaves stay untouched; the commit vote fails.
                        continue
                    # The average was taken in the ring's buffer, which
                    # with the native engine is ``buf`` itself, and the
                    # next call rewrites ``buf``: a leaf that would go on
                    # living in host memory leaves as a copy.
                    owned = not np.may_share_memory(flat, buf)
                    for idx, arr in bucket.unpack(flat):
                        out[idx] = (
                            arr
                            if owned or (is_jax[idx] and not _lives_in_host_memory(leaves[idx]))
                            else arr.copy()
                        )

            devices = []
            # The per-leaf placement of what came back as host arrays, as one.
            with self._manager.spans.sub("h2d_put", step=step) as sub_put:
                for i, a in enumerate(out):
                    if is_jax[i]:
                        if not isinstance(a, jax.Array):
                            h2d_bytes += int(getattr(a, "nbytes", 0))
                        devices.append(jax.device_put(a, leaves[i].sharding))
                    else:
                        devices.append(
                            np.asarray(a) if isinstance(a, jax.Array) else a
                        )
                # device_put may read its source after it returns, and the
                # sources are views of the plan's persistent buffers: no
                # host view is held past this call.
                jax.block_until_ready(
                    [d for d in devices if isinstance(d, jax.Array)]
                )
                sub_put.fields["bytes"] = h2d_bytes
            sp_h2d.fields["bytes"] = h2d_bytes
        stats["h2d_bytes"] += h2d_bytes
        self._note("h2d", h2d_bytes)
        return jax.tree.unflatten(treedef, devices)

    def _issue(self, bucket: _Bucket, buf: np.ndarray, k: int) -> Future:
        """Hands one host-path bucket's flat buffer to the ring."""
        # Split-out 0-d/scalar buckets opt OUT of the lossy wire encoding —
        # full-width is the contract, not just full-width fetch.  The bucket
        # plan's staging buffer is rewritten from the leaves every step, so
        # the op may own it for the round: donate lets the native engine
        # reduce in place with no working-buffer copy.
        if bucket.wire_bypass:
            return self._manager.allreduce(
                buf, allow_wire_compression=False, donate=True, bucket=k
            )
        return self._manager.allreduce(buf, donate=True, bucket=k)

    def _allreduce_streamed(
        self,
        grads: Any,
        leaves: List[Any],
        treedef: Any,
        is_jax: List[bool],
        plan: _BucketPlan,
        stats: Dict[str, int],
        wire_nbytes: Callable[[_Bucket], int],
    ) -> Any:
        """The exchange as one stream over the buckets in ``plan.fetch_order``:
        fetch, ring, way back — a bucket's ring op and its return to the
        device run under the fetches of the buckets after it."""
        import jax

        from torchft_tpu.futures import LEASE_COUNTERS, device_get_into

        spans = self._manager.spans
        step = self._manager.current_step()
        timeout = self._manager.timeout.total_seconds()
        order = plan.fetch_order

        # Dispatch EVERY single-device epilogue before the first blocking
        # fetch — jit dispatch is async, so a later bucket's cast runs on
        # device under an earlier bucket's D2H wait (device programs, not
        # transfers).  Multi-device
        # (sharded) programs stay lazy: they serialize behind
        # _SHARDED_EXEC_LOCK with a blocking wait anyway.
        flat_devs: Dict[int, Any] = {
            k: dev.prep([leaves[i] for i in plan.buckets[k].indices])
            for k, dev in enumerate(plan.device)
            if dev is not None and not dev.multi_device
        }

        def fetch(k: int, pairs: list, nbytes: int, pos: int) -> bool:
            """One blocking, deadline-guarded fetch into a persistent buffer:
            wedged device work latches an error instead of hanging the step
            (stream_timeout analogue).  Spanned as allreduce_d2h — this wait
            blocks the train thread and must be attributed as FT time, not
            productive compute."""
            with spans.span("allreduce_d2h", step=step, bytes=nbytes, bucket=k, pos=pos):
                try:
                    device_get_into(
                        pairs,
                        timeout,
                        sub=functools.partial(spans.sub, step=step, bucket=k),
                        lease_counts=stats,
                    )
                except TimeoutError as e:
                    self._manager.report_error(e)
                    return False
            stats["d2h_bytes"] += nbytes
            self._note("d2h", nbytes)
            return True

        def fetch_and_issue(k: int, pos: int) -> Optional[Tuple[int, str, Any]]:
            """Bucket k off the device and onto the ring: (k, "host" or
            "device", future) or (k, "sharded", [(view, future)]); None where
            a fetch timed out."""
            bucket, dev = plan.buckets[k], plan.device[k]
            if dev is None:
                pairs = [(leaves[i], view) for i, view in plan.views[k]]
                if not fetch(k, pairs, bucket.nbytes, pos):
                    return None
                stats["wire_bytes"] += wire_nbytes(bucket)
                return k, "host", self._issue(bucket, plan.buffers[k], k)
            if dev.multi_device:
                with _SHARDED_EXEC_LOCK:
                    flat_dev = dev.prep([leaves[i] for i in bucket.indices])
                    jax.block_until_ready(flat_dev)
            else:
                flat_dev = flat_devs[k]
            dev.last_sharding = getattr(flat_dev, "sharding", None)
            parts = _shard_slices(flat_dev) if self._sharded_fetch else None
            if parts is None:
                if not fetch(k, [(flat_dev, dev.buffer)], dev.buffer.nbytes, pos):
                    return None
                stats["wire_bytes"] += wire_nbytes(bucket)
                return k, "device", self._manager.allreduce(
                    dev.buffer, donate=True, bucket=k
                )
            # Sharded fetch: each shard slice comes straight off its device
            # and rides the ring as its own tagged op — the bucket's
            # cross-group allreduce decomposes into per-slice reduce-scatter
            # + allgather, and the slices overlap each other on the wire
            # like buckets do.
            slice_futs = []
            for shard, start, stop in parts:
                view = dev.buffer[start:stop]
                if not fetch(k, [(shard.data, view)], view.nbytes, pos):
                    return None
                slice_futs.append(
                    (view, self._manager.allreduce(view, donate=True, bucket=k))
                )
            stats["slices"] += len(parts)
            stats["wire_bytes"] += wire_nbytes(bucket)
            return k, "sharded", slice_futs

        out: List[Any] = list(leaves)

        def put_back(k: int, kind: str, payload: Any) -> int:
            """Bucket k's way back, its ring op(s) resolved: the averaged
            leaves go to their devices NOW (``jax.device_put`` returns at
            once and the copy runs under the fetches still to come).  Returns
            the bytes handed to the device.  Collective failures resolve a
            bucket to its own input buffer (wrap_future's default); such a
            bucket keeps its ORIGINAL leaves untouched — the error is latched
            and the commit vote fails."""
            bucket, dev = plan.buckets[k], plan.device[k]
            if kind == "host":
                buf = plan.buffers[k]
                flat = np.asarray(payload.result())
                if flat is buf:
                    # With donate the op may have half-reduced the staging
                    # buffer: it must not be republished as gradients.
                    return 0
                # The average was taken in the ring's buffer, which with the
                # native engine is ``buf`` itself, and the next call rewrites
                # ``buf``: a leaf that would go on living in host memory
                # leaves as a copy.
                owned = not np.may_share_memory(flat, buf)
                nbytes = 0
                for idx, arr in bucket.unpack(flat):
                    if not is_jax[idx]:
                        out[idx] = arr if owned else arr.copy()
                        continue
                    if not owned and _lives_in_host_memory(leaves[idx]):
                        arr = arr.copy()
                    nbytes += arr.nbytes
                    out[idx] = jax.device_put(arr, leaves[idx].sharding)
                return nbytes
            # Device-prepped results go home as wire-dtype bytes (H2D moves
            # bf16; the upcast to the leaf dtype runs on device in the jitted
            # inverse).
            if kind == "device":
                res = payload.result()
                if res is dev.buffer:
                    return 0
                flat_host = np.asarray(res)
            else:
                results = [(view, fut.result()) for view, fut in payload]
                if any(r is view for view, r in results):
                    return 0
                flat_host = np.concatenate(
                    [np.asarray(r).reshape(-1) for _, r in results]
                )
            # device_put with the epilogue's sharding performs the per-shard
            # H2D placement: each slice lands on its own device (each host
            # transfers only its addressable slices).
            with _SHARDED_EXEC_LOCK if dev.multi_device else nullcontext():
                flat_back = (
                    jax.device_put(flat_host, dev.last_sharding)
                    if dev.last_sharding is not None
                    else jax.device_put(flat_host)
                )
                backs = dev.unprep(flat_back)
                if dev.multi_device:
                    jax.block_until_ready(backs)
                for idx, arr in zip(bucket.indices, backs):
                    out[idx] = jax.device_put(arr, leaves[idx].sharding)
            return flat_host.nbytes

        # On the ring, in fetch order: (k, kind, payload).
        pending: List[Tuple[int, str, Any]] = []
        h2d_bytes = 0

        def futures_of(entry: Tuple[int, str, Any]) -> List[Future]:
            _k, kind, payload = entry
            return [fut for _view, fut in payload] if kind == "sharded" else [payload]

        def harvest() -> int:
            """Sends home every bucket whose ring op has resolved, in a short
            allreduce_h2d span of their own (none where nothing is ready), so
            between two fetches it lies outside any allreduce_d2h span.
            Returns how many buckets went."""
            nonlocal h2d_bytes
            ready = [e for e in pending if all(f.done() for f in futures_of(e))]
            if not ready:
                return 0
            pending[:] = [e for e in pending if e not in ready]
            with spans.span("allreduce_h2d", step=step) as sp_h2d:
                put = 0
                for entry in ready:
                    with spans.sub(
                        "h2d_put",
                        step=step,
                        bucket=entry[0],
                        bytes=plan.buckets[entry[0]].nbytes,
                    ):
                        put += put_back(*entry)
                sp_h2d.fields["bytes"] = put
            h2d_bytes += put
            return len(ready)

        early_puts = 0
        for pos, k in enumerate(order):
            entry = fetch_and_issue(k, pos=pos)
            if entry is None:
                return grads
            # Bucket k hits the wire here while the buckets after it are
            # still on the device (and, with ring lanes, while the one before
            # it is still mid-flight — the collective overlaps back-to-back
            # calls); whatever the ring has finished meanwhile goes home.
            pending.append(entry)
            if pos + 1 < len(order):
                early_puts += harvest()
        fetched_at = time.monotonic()

        # What is still on the ring when the last fetch has landed, in fetch
        # order.  The wait blocks this (train) thread on the ring exchange —
        # i.e. on the SLOWEST peer's gradients.  Span it as allreduce_merge:
        # unrecorded, this wait would be charged as productive/busy time,
        # and on a cluster with one slow host EVERY fast replica would read
        # as busy for the whole stall — hiding exactly the straggler the
        # step-time telemetry exists to expose (the commit-time drain of
        # what remains keeps the same phase name; the accumulator sums).
        while pending:
            waiting = [f for f in futures_of(pending[0]) if not f.done()]
            if waiting:
                with spans.span("allreduce_merge", step=step):
                    for fut in waiting:
                        fut.result()
            harvest()

        # device_put may read its source after it returns, and the sources
        # are views of the plan's persistent buffers: no host view is held
        # past this call.  The one wait for every put of this call.
        with spans.span("allreduce_h2d", step=step):
            with spans.sub("h2d_put", step=step, bytes=h2d_bytes):
                jax.block_until_ready([a for a in out if isinstance(a, jax.Array)])
        stats["h2d_bytes"] += h2d_bytes
        stats["early_puts"] = early_puts
        self._note("h2d", h2d_bytes)
        note_fields = getattr(self._manager, "note_summary_fields", None)
        if callable(note_fields):
            try:
                note_fields(
                    exchange_stream={
                        "early_puts": early_puts,
                        "buckets": len(order),
                        "tail_s": round(time.monotonic() - fetched_at, 4),
                        **{name: stats[name] for name in LEASE_COUNTERS},
                    }
                )
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        return jax.tree.unflatten(treedef, out)


class PerLeafGradientAverager:
    """One allreduce per gradient leaf (reference:
    PureDistributedDataParallel, torchft/ddp.py:74-97).  Simpler, slower —
    useful for debugging numerics per parameter."""

    def __init__(self, manager: Manager) -> None:
        self._manager = manager

    def allreduce(self, grads: Any, allow_wire_compression: bool = True) -> Any:
        import jax

        leaves, treedef = jax.tree.flatten(grads)
        if not leaves:
            return grads
        # Parity with GradientAverager: settle the quorum once up front and
        # take the alone-in-the-ring fast path before ANY device->host
        # traffic — N per-leaf roundtrips for an identity average is pure
        # HBM-bandwidth waste.
        self._manager.wait_quorum()
        if (
            self._manager.errored() is None
            and self._manager.collective().size() == 1
            and self._manager.is_participating()
        ):
            return grads
        futs = [
            self._manager.allreduce(
                l, allow_wire_compression=allow_wire_compression
            )
            for l in leaves
        ]
        # Same accounting contract as GradientAverager: the drain blocks on
        # the slowest peer's gradients and must be spanned, or the wait is
        # charged as busy time and the straggler sentinel goes blind.
        with self._manager.spans.span(
            "allreduce_merge", step=self._manager.current_step()
        ):
            results = [f.result() for f in futs]
        # Results land back on each leaf's original device/sharding, like
        # GradientAverager: Manager.allreduce device_puts jax inputs itself,
        # but a swapped-in manager (tests, wrappers) may hand back host
        # arrays — re-place those so callers always see device-resident
        # leaves where they provided device-resident gradients.
        out = []
        for leaf, res in zip(leaves, results):
            if isinstance(leaf, jax.Array) and not isinstance(res, jax.Array):
                res = jax.device_put(res, leaf.sharding)
            out.append(res)
        return jax.tree.unflatten(treedef, out)


def allreduce_pytree(
    manager: Manager,
    tree: Any,
    bucket_bytes: int = 25 << 20,
    device_wire_prep: Optional[bool] = None,
    sharded_fetch: Optional[bool] = None,
) -> Any:
    """Functional one-shot form of GradientAverager.allreduce."""
    return GradientAverager(
        manager,
        bucket_bytes,
        device_wire_prep=device_wire_prep,
        sharded_fetch=sharded_fetch,
    ).allreduce(tree)
