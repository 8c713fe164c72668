"""Fragment planning for the streaming semi-sync data plane.

The outer (DiLoCo) state is partitioned into **fragments** — dtype-
homogeneous flat slices of the parameter pytree — on the exact bucket
machinery the DDP gradient path already uses (:func:`torchft_tpu.ddp.
plan_buckets`): leaves are grouped by dtype, packed greedily up to
``fragment_bytes``, and each fragment remembers which leaves it covers and
where each lives in the flat buffer.  One fragment is the unit of the
background pseudogradient sync (Streaming DiLoCo, arXiv:2501.18512): a
round's fragments are issued at staggered inner-step slots so each
fragment's wire time overlaps the remaining inner compute instead of
stalling the whole round at the sync boundary.

Reusing ``plan_buckets`` (rather than a private re-implementation) keeps
the two data planes' packing semantics identical — 0-d leaves, dtype
grouping, oversized-leaf handling — and means a fix there fixes both.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from torchft_tpu.ddp import plan_buckets

__all__ = [
    "Fragment",
    "FragmentPlan",
    "pack_flat",
    "DEFAULT_FRAGMENT_BYTES",
]


def pack_flat(arrs: Sequence[Any], dtype: Any) -> np.ndarray:
    """One contiguous 1-D host array of ``dtype`` from a leaf list — THE
    packing primitive of this plane, shared by :meth:`Fragment.pack` and
    the codecs' host paths so the two cannot drift."""
    parts = [np.asarray(a).reshape(-1) for a in arrs]
    flat = parts[0] if len(parts) == 1 else np.concatenate(parts)
    return flat.astype(np.dtype(dtype), copy=False)

# Default fragment size.  Smaller than DDP's 25 MB gradient buckets: a
# fragment is the granularity of sync/compute overlap within one outer
# round, and a round has only ``sync_every`` slots to hide fragments in —
# 4 MB keeps several fragments per round for typical outer states while
# staying large enough to amortize ring framing.
DEFAULT_FRAGMENT_BYTES = 4 << 20


class Fragment:
    """One flat slice of the outer state: which leaves it packs and how they
    lay out in the fragment's flat buffer (delegated to the shared
    ``ddp._Bucket`` metadata), plus whether the fragment is eligible for
    lossy wire codecs (real floats of >= 4 bytes — the same gate the DDP
    wire compression applies; integer and sub-f32 fragments always ride
    raw full-width)."""

    def __init__(self, index: int, bucket: Any) -> None:
        self.index = index
        self.bucket = bucket
        self.numel = bucket.numel
        self.nbytes = bucket.nbytes
        self.dtype = bucket.dtype
        self.lossy_ok = (
            np.issubdtype(bucket.dtype, np.floating)
            and bucket.dtype.itemsize >= 4
        )

    def pack(self, leaves: Sequence[Any]) -> np.ndarray:
        """Flat host array (fragment dtype) of this fragment's leaves, in
        bucket layout.  ``leaves`` is the FULL tree's leaf list; the
        fragment selects its own by index."""
        return pack_flat(
            [leaves[i] for i in self.bucket.indices], self.dtype
        )

    def unpack(self, flat: np.ndarray) -> List[Tuple[int, np.ndarray]]:
        """(leaf index, reshaped view) pairs — the shared bucket unpack."""
        return self.bucket.unpack(np.asarray(flat).astype(self.dtype, copy=False))


class FragmentPlan:
    """The fragment layout for one tree signature plus the per-round issue
    schedule.

    ``slot(f, sync_every)`` staggers fragment issues across the round's
    inner steps: fragment f of F is due after inner step
    ``1 + floor(f * sync_every / F)`` (clamped to the round), so the first
    fragment leaves the moment the round starts making progress and the
    last still has ``~sync_every/F`` inner steps of compute to hide its
    wire time behind.  Every group derives the identical schedule from
    (tree signature, sync_every) alone — fragment issue order is part of
    the cross-group ring-op alignment contract, exactly like bucket
    submission order in the DDP plane.
    """

    def __init__(
        self,
        metas: Sequence[Tuple[tuple, Any]],
        fragment_bytes: int = DEFAULT_FRAGMENT_BYTES,
    ) -> None:
        self.fragment_bytes = max(1, int(fragment_bytes))
        self.fragments = [
            Fragment(i, b)
            for i, b in enumerate(plan_buckets(metas, self.fragment_bytes))
        ]
        self.total_bytes = sum(f.nbytes for f in self.fragments)

    def __len__(self) -> int:
        return len(self.fragments)

    def slot(self, index: int, sync_every: int) -> int:
        """The inner step (1-based) after which fragment ``index`` is
        issued."""
        n = max(1, len(self.fragments))
        return min(sync_every, 1 + (index * sync_every) // n)

    def schedule(self, sync_every: int) -> Dict[int, List[Fragment]]:
        """inner step -> fragments due at that step, covering every
        fragment exactly once."""
        by_slot: Dict[int, List[Fragment]] = {}
        for f in self.fragments:
            by_slot.setdefault(self.slot(f.index, sync_every), []).append(f)
        return by_slot
