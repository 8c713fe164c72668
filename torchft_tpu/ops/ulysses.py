"""Ulysses sequence parallelism: all-to-all head<->sequence resharding.

The second long-context strategy next to ring attention (the torchft
reference has neither — SURVEY.md §5 long-context "not present").  Where the
ring keeps Q resident and rotates K/V shard-by-shard (n-1 neighbor hops,
one block in flight), Ulysses (arXiv:2309.14509) does two all-to-alls: swap
the sharded axis from *sequence* to *heads*, run ordinary full-sequence
attention on a head subset — the pallas flash kernel applies unchanged —
and swap back.  Cheaper in latency terms when the head count divides the
mesh axis (2 collectives instead of n-1 hops) and composes with any local
attention kernel; the ring wins when heads < devices or memory for a full
K/V sequence per device is the constraint.  Both are exposed; the
transformer selects via ``TransformerConfig.attention``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from torchft_tpu.ops.attention import flash_attention


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Local body — call inside shard_map over the sequence mesh axis.

    q/k/v: local sequence shards [B, H, S_local, D]; the q and kv head
    counts must each be divisible by the axis size.  GQA stays compressed
    through the all_to_all (k/v may have fewer heads than q); the local
    flash kernel broadcasts groups after the exchange.
    """
    # [B, H, S_local, D] -> all_to_all -> [B, H/n, S, D]: the head axis is
    # scattered across the axis while sequence gathers.
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    q = a2a(q, split_axis=1, concat_axis=2)
    k = a2a(k, split_axis=1, concat_axis=2)
    v = a2a(v, split_axis=1, concat_axis=2)
    # `flash_attention` is position-major: turned to here, at this form's own boundary
    out = flash_attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)), causal=causal, scale=scale).transpose(0, 2, 1, 3)
    # [B, H/n, S, D] -> [B, H, S_local, D]
    return a2a(out, split_axis=2, concat_axis=1)


def ulysses_attention_sharded(
    mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    batch_axis: Optional[str] = "data",
    head_axis: Optional[str] = "tensor",
    seq_axis: str = "sequence",
) -> jax.Array:
    """shard_map wrapper mirroring ring_attention_sharded: batch over
    `batch_axis`, heads over `head_axis` (TP), sequence over `seq_axis`."""
    from jax.sharding import PartitionSpec as P

    from torchft_tpu.ops._shard_map import shard_map

    n = mesh.shape[seq_axis]
    tp = max(1, mesh.shape.get(head_axis, 1) if head_axis else 1)
    for name, heads in (("q", q.shape[1]), ("kv", k.shape[1])):
        # Guard TP divisibility first (e.g. 2 kv heads over tp=4): without
        # it, heads//tp floors to 0, 0 % n == 0 passes the check below, and
        # the misconfiguration surfaces later as an opaque shard_map
        # partitioning error instead of this message.
        assert heads % tp == 0, (
            f"Ulysses needs {name} heads ({heads}) divisible by the "
            f"'{head_axis}' axis ({tp}); use ring attention otherwise"
        )
        heads_local = heads // tp
        assert heads_local % n == 0, (
            f"Ulysses needs {name} heads-per-TP-shard ({heads_local}) divisible "
            f"by the sequence axis ({n}); use ring attention otherwise"
        )
    spec = P(batch_axis, head_axis, seq_axis, None)
    fn = shard_map(
        functools.partial(
            ulysses_attention, axis_name=seq_axis, causal=causal, scale=scale
        ),
        mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
