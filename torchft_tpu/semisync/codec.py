"""Per-fragment wire preparation for the semi-sync pseudogradient plane.

A fragment codec owns the step from "the fragment's live leaves + the
last-committed backup" to "the host payload handed to the cross-group
ring", per fragment:

  pseudogradient:  pg = backup - local     (the paper sign, DiLoCo
                                            arXiv:2311.08105 — an outer SGD
                                            *descent* step moves the global
                                            params toward averaged local
                                            progress)

``int8`` — **int8 + error feedback** (the new wire codec this subsystem
introduces): the fragment is quantized at the SOURCE with a per-fragment
scale (amax/127) after adding the residual the previous round failed to
transmit, and the new residual ``x - q*scale`` is carried forward — on
device, inside the same jitted per-fragment epilogue that computes the
pseudogradient (PR 8's device wire-prep hook), so the D2H fetch moves int8
bytes (~0.25x of f32) and the ring then wires scale+int8 frames
(``wire_codec="int8"``, collectives.py).  Pseudogradients tolerate this
because error feedback turns per-round quantization error into a
one-round delay instead of a loss; raw weights do NOT — LocalSGD's
parameter averaging stays full-width, unchanged.

``bf16`` / ``f32`` — the fallback knob (``StreamingDiLoCo(codec=...)``): bf16
casts the pseudogradient on device and wires bf16 (0.5x); f32 opts the
sync out of every lossy encoding; ``auto`` defers to the collective's own
wire policy (the legacy DiLoCo port's behavior — bf16 only when the link
profile says bandwidth-bound).

Every codec works on host (numpy) leaves too — the device path engages
only when all of a fragment's leaves are jax arrays, mirroring the DDP
device-bucket eligibility gate.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.semisync.fragments import Fragment, pack_flat

__all__ = [
    "CODECS",
    "FragmentCodec",
    "make_codec",
]

CODECS = ("int8", "int4", "bf16", "f32", "auto")


def _all_jax(leaves: Sequence[Any]) -> bool:
    try:
        import jax

        return all(isinstance(l, jax.Array) for l in leaves)
    except ImportError:
        return False


def _device_flat(leaves: List[Any], dtype):
    """The jit-side counterpart of ``fragments.pack_flat``: one flat device
    array of ``dtype`` from a leaf list — shared by every jitted encoder so
    the three epilogues cannot drift in their flatten prologue."""
    import jax.numpy as jnp

    flat = (
        jnp.concatenate([jnp.ravel(l) for l in leaves])
        if len(leaves) > 1
        else jnp.ravel(leaves[0])
    )
    return flat.astype(dtype)


class FragmentCodec:
    """Base: raw pseudogradient in the fragment dtype, no compression.

    Subclasses override :meth:`_encode_host` / :meth:`_encode_device` and
    the wire-policy properties.  One codec instance per fragment — codecs
    are stateful (the int8 residual) and cache their jitted epilogues.
    """

    name = "f32"
    #: allow the collective's own lossy wire encoding (bf16-if-shaped)?
    allow_wire_compression = False
    #: explicit per-call wire codec for collectives that support it
    wire_codec: Optional[str] = None

    def __init__(self, fragment: Fragment) -> None:
        self.fragment = fragment
        self._backup_dev: Any = None  # device mirror, built lazily
        self._backup_host: Optional[np.ndarray] = None

    @property
    def _work_dtype(self) -> np.dtype:
        """The dtype the codec's pseudogradient math runs in.  The base
        (f32/auto) codecs keep the FRAGMENT dtype — an f64 fragment must
        not be silently downcast by a codec whose whole point is "no lossy
        encoding".  Quantizing codecs override (int8's residual math is
        f32 by construction)."""
        return self.fragment.dtype

    @property
    def payload_dtype(self) -> np.dtype:
        """The dtype of the host payload :meth:`encode` hands the ring.
        Non-participating groups must contribute zeros of EXACTLY this
        dtype: the ring's per-hop frame sizes derive from each rank's
        payload dtype, so a mismatched placeholder breaks the cross-rank
        frame contract."""
        return self._work_dtype

    def zero_payload(self) -> np.ndarray:
        return np.zeros(self.fragment.numel, dtype=self.payload_dtype)

    # -- backup management --------------------------------------------------

    def set_backup(self, flat_host: np.ndarray) -> None:
        """Installs the fragment's last-committed flat backup (host).  The
        device mirror is invalidated and re-uploaded lazily on the next
        device-path encode — callers on the host path never pay the H2D."""
        self._backup_host = np.ascontiguousarray(
            np.asarray(flat_host).astype(self._work_dtype, copy=False)
        )
        self._backup_dev = None

    def _backup_device(self):
        import jax

        if self._backup_dev is None:
            self._backup_dev = jax.device_put(self._backup_host)
        return self._backup_dev

    # -- encode -------------------------------------------------------------

    def encode(self, leaves: Sequence[Any]) -> Tuple[np.ndarray, int]:
        """(host payload for the ring, d2h bytes fetched).  ``leaves`` is
        the FULL tree leaf list; the fragment picks its own.  The d2h
        charge counts only bytes that actually crossed the device boundary
        — a pure-host (numpy) tree fetches nothing, and the telemetry must
        not claim it did."""
        frag_leaves = [leaves[i] for i in self.fragment.bucket.indices]
        if self.fragment.lossy_ok and _all_jax(frag_leaves):
            return self._encode_device(frag_leaves)
        payload = self._encode_host(frag_leaves)
        d2h = 0
        try:
            import jax

            d2h = sum(
                int(getattr(l, "nbytes", 0))
                for l in frag_leaves
                if isinstance(l, jax.Array)
            )
        except ImportError:
            pass
        return payload, d2h

    def _pack_local(self, frag_leaves: Sequence[Any]) -> np.ndarray:
        # The same flatten+cast the fragment's own pack uses — one
        # implementation, so the two packing paths cannot drift.
        return pack_flat(frag_leaves, self._work_dtype)

    def _encode_host(self, frag_leaves: Sequence[Any]) -> np.ndarray:
        local = self._pack_local(frag_leaves)
        return (self._backup_host - local).astype(local.dtype, copy=False)

    def _encode_device(self, frag_leaves: Sequence[Any]) -> Tuple[np.ndarray, int]:
        fn = self._jitted_pg()
        out = fn(frag_leaves, self._backup_device())
        host = np.asarray(out)
        return host, int(host.nbytes)

    def _jitted_pg(self):
        if getattr(self, "_pg_fn", None) is None:
            import jax

            def pg(leaves: List[Any], backup):
                return backup - _device_flat(leaves, backup.dtype)

            self._pg_fn = jax.jit(pg)
        return self._pg_fn

    # -- round lifecycle ----------------------------------------------------

    def on_commit(self) -> None:
        """The round's averaged pseudogradient was applied."""

    def on_abort(self) -> None:
        """The round failed (error latched / commit vote lost): any
        codec-internal state tied to the discarded transmission is reset."""


class _AutoCodec(FragmentCodec):
    """Legacy-port parity: f32 payload, collective decides the wire
    (bf16 only when the link profile says bandwidth-bound)."""

    name = "auto"
    allow_wire_compression = True


class _BF16Codec(FragmentCodec):
    """Pseudogradient cast to bfloat16 on device (or host fallback): the
    D2H fetch and the ring wire both move 2 bytes/element.  The collective
    treats already-bf16 payloads as pre-encoded (f32 accumulation)."""

    name = "bf16"
    allow_wire_compression = True

    @property
    def _work_dtype(self) -> np.dtype:
        # Quantizing codec: math in f32 (the cast to bf16 IS the encoding;
        # doing the subtraction in f64 would buy nothing past the cast).
        return np.dtype(np.float32)

    @property
    def payload_dtype(self) -> np.dtype:
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)

    def _encode_host(self, frag_leaves):
        import ml_dtypes

        local = self._pack_local(frag_leaves)
        return (self._backup_host - local).astype(ml_dtypes.bfloat16)

    def _encode_device(self, frag_leaves):
        if getattr(self, "_bf16_fn", None) is None:
            import jax
            import jax.numpy as jnp

            def enc(leaves: List[Any], backup):
                local = _device_flat(leaves, backup.dtype)
                return (backup - local).astype(jnp.bfloat16)

            self._bf16_fn = jax.jit(enc)
        out = self._bf16_fn(frag_leaves, self._backup_device())
        host = np.asarray(out)
        return host, int(host.nbytes)


class _Int8EFCodec(FragmentCodec):
    """int8 + error feedback (see module docstring).

    Device path: ONE jitted epilogue computes pg, adds the carried
    residual, derives the per-fragment scale, quantizes, and produces the
    next residual — the residual never leaves the device and the D2H fetch
    is int8 + one f32 scale.  Host path mirrors the math in numpy.

    The ring still requantizes per chunk/hop (scale+int8 frames,
    collectives.py ``wire_codec="int8"``); the residual captures the
    SOURCE quantization error, which dominates.  On a failed round the
    pending residual is discarded (on_abort): the transmission it
    described never landed anywhere, and the next round's pseudogradient
    re-derives the full difference from scratch.
    """

    name = "int8"
    allow_wire_compression = True
    wire_codec = "int8"

    @property
    def _work_dtype(self) -> np.dtype:
        # Quantizing codec: residual math and the dequantized payload are
        # f32 by construction (int8's 8-bit mantissa makes wider inputs
        # pointless past the quantizer).
        return np.dtype(np.float32)

    def __init__(self, fragment: Fragment) -> None:
        super().__init__(fragment)
        self._residual_host: Optional[np.ndarray] = None
        self._residual_dev: Any = None
        # Set by encode, promoted to the carried residual on commit,
        # discarded on abort — a failed sync must not corrupt EF state.
        self._pending_residual: Any = None
        self._pending_on_device = False

    def _residual(self, device: bool):
        if device:
            if self._residual_dev is None:
                import jax
                import jax.numpy as jnp

                if self._residual_host is not None:
                    self._residual_dev = jax.device_put(
                        self._residual_host.astype(np.float32)
                    )
                else:
                    self._residual_dev = jnp.zeros(
                        self.fragment.numel, dtype=jnp.float32
                    )
            return self._residual_dev
        if self._residual_host is None:
            self._residual_host = np.zeros(self.fragment.numel, dtype=np.float32)
        return self._residual_host

    def residual_l2(self) -> float:
        """Diagnostic: L2 norm of the carried residual (telemetry only).
        The device-resident residual is reduced ON DEVICE and only the
        scalar is fetched — a full-width D2H here would cost 4x the int8
        payload fetch the codec exists to avoid."""
        if self._residual_host is not None:
            return float(np.linalg.norm(self._residual_host))
        if self._residual_dev is not None:
            import jax.numpy as jnp

            return float(jnp.linalg.norm(self._residual_dev))
        return 0.0

    def _encode_host(self, frag_leaves):
        from torchft_tpu.collectives import quantize_int8

        local = self._pack_local(frag_leaves)
        x = (self._backup_host - local) + self._residual(device=False)
        scale, q = quantize_int8(x)
        deq = q.astype(np.float32) * np.float32(scale)
        # Non-finite elements cannot ride the wire (quantize_int8 encodes
        # NaN as 0, inf saturated); their residual is zeroed, not carried —
        # a NaN residual would force scale=1 garbage on every later round.
        self._pending_residual = np.where(np.isfinite(x), x - deq, 0.0).astype(
            np.float32
        )
        self._pending_on_device = False
        return deq

    def _encode_device(self, frag_leaves):
        import jax

        if getattr(self, "_enc_fn", None) is None:
            import jax.numpy as jnp

            def enc(leaves: List[Any], backup, residual):
                # Mirrors collectives.quantize_int8 (the host twin),
                # including the non-finite rules: NaN encodes as 0, inf
                # saturates, and non-finite elements carry a ZERO residual.
                local = _device_flat(leaves, jnp.float32)
                x = (backup - local) + residual
                amax = jnp.max(jnp.abs(x))
                scale = jnp.where(
                    (amax > 0) & jnp.isfinite(amax), amax / 127.0, 1.0
                ).astype(jnp.float32)
                scaled = jnp.nan_to_num(x / scale, nan=0.0)
                q = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)
                new_residual = jnp.where(
                    jnp.isfinite(x), x - q.astype(jnp.float32) * scale, 0.0
                )
                return q, scale, new_residual

            self._enc_fn = jax.jit(enc)
        q, scale, new_residual = self._enc_fn(
            frag_leaves, self._backup_device(), self._residual(device=True)
        )
        # Fetch int8 + the scalar scale — the 0.25x D2H the codec exists
        # for; the residual stays resident on device.
        q_host = np.asarray(q)
        s = float(np.asarray(scale))
        self._pending_residual = new_residual
        self._pending_on_device = True
        deq = q_host.astype(np.float32) * np.float32(s)
        return deq, int(q_host.nbytes) + 4

    def on_commit(self) -> None:
        if self._pending_residual is None:
            return
        if self._pending_on_device:
            self._residual_dev = self._pending_residual
            self._residual_host = None
        else:
            self._residual_host = self._pending_residual
            self._residual_dev = None
        self._pending_residual = None

    def on_abort(self) -> None:
        # Discard BOTH the pending and the carried residual: the carried
        # one described a delta relative to a transmission history the
        # failed round just invalidated, and the next round's pg re-derives
        # the full backup-local difference anyway.
        self._pending_residual = None
        self._residual_host = None
        self._residual_dev = None


class _Int4EFCodec(_Int8EFCodec):
    """int4 + error feedback: the Streaming-DiLoCo design point
    (arXiv:2501.18512 wires 4-bit outer gradients) — same source-side
    quantize + residual carry as int8, but the per-fragment scale is
    amax/7 and values clip to [-7, 7], so the ring's ``wire_codec="int4"``
    packs two elements per byte (0.125x the f32 wire per hop).

    The D2H fetch on the device path still moves one int8-typed byte per
    element (nibble packing is a host-side wire concern; a device gather
    into packed nibbles would cost more than the fetch saves) — the 4-bit
    saving is on the CROSS-GROUP WIRE, which is the DiLoCo bottleneck.
    EF semantics are inherited unchanged: pending residual promoted on
    commit, all residual state discarded on abort.
    """

    name = "int4"
    wire_codec = "int4"

    def _encode_host(self, frag_leaves):
        from torchft_tpu.collectives import quantize_int4

        local = self._pack_local(frag_leaves)
        x = (self._backup_host - local) + self._residual(device=False)
        scale, q = quantize_int4(x)
        deq = q.astype(np.float32) * np.float32(scale)
        self._pending_residual = np.where(np.isfinite(x), x - deq, 0.0).astype(
            np.float32
        )
        self._pending_on_device = False
        return deq

    def _encode_device(self, frag_leaves):
        import jax

        if getattr(self, "_enc4_fn", None) is None:
            import jax.numpy as jnp

            def enc(leaves: List[Any], backup, residual):
                # Mirrors collectives.quantize_int4 (the host twin),
                # including the non-finite rules: NaN encodes as 0, inf
                # saturates, non-finite elements carry a ZERO residual.
                local = _device_flat(leaves, jnp.float32)
                x = (backup - local) + residual
                amax = jnp.max(jnp.abs(x))
                scale = jnp.where(
                    (amax > 0) & jnp.isfinite(amax), amax / 7.0, 1.0
                ).astype(jnp.float32)
                scaled = jnp.nan_to_num(x / scale, nan=0.0)
                q = jnp.clip(jnp.round(scaled), -7, 7).astype(jnp.int8)
                new_residual = jnp.where(
                    jnp.isfinite(x), x - q.astype(jnp.float32) * scale, 0.0
                )
                return q, scale, new_residual

            self._enc4_fn = jax.jit(enc)
        q, scale, new_residual = self._enc4_fn(
            frag_leaves, self._backup_device(), self._residual(device=True)
        )
        q_host = np.asarray(q)
        s = float(np.asarray(scale))
        self._pending_residual = new_residual
        self._pending_on_device = True
        deq = q_host.astype(np.float32) * np.float32(s)
        return deq, int(q_host.nbytes) + 4


_CODEC_CLASSES = {
    "f32": FragmentCodec,
    "auto": _AutoCodec,
    "bf16": _BF16Codec,
    "int8": _Int8EFCodec,
    "int4": _Int4EFCodec,
}


def make_codec(name: str, fragment: Fragment) -> FragmentCodec:
    """Codec instance for one fragment.  Fragments ineligible for lossy
    encodings (integer / sub-f32 dtypes) always get the raw base codec,
    whatever was requested — the same full-width guarantee the DDP wire
    compression gate gives scalars and integer buckets."""
    if name not in _CODEC_CLASSES:
        raise ValueError(f"unknown semisync codec {name!r}; expected {CODECS}")
    if not fragment.lossy_ok and name in ("int8", "int4", "bf16"):
        return FragmentCodec(fragment)
    return _CODEC_CLASSES[name](fragment)
