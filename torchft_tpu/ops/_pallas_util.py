"""Small helpers shared by the pallas TPU kernels."""

from __future__ import annotations

import math
import warnings

import jax
import jax.numpy as jnp

LANE = 128  # TPU lane width


def on_tpu() -> bool:
    """True when the default backend is a TPU.  A backend that fails to
    come up raises here: a kernel gate that swallowed the error would run
    the XLA reference on a machine whose chip is broken or taken."""
    return jax.default_backend() == "tpu"


def kernels_apply(mesh=None) -> bool:
    """The one gate every pallas kernel of the model path asks: on a TPU,
    and the program being traced spans a single device.

    A pallas custom call has no SPMD partitioning rule, so under a
    multi-device mesh XLA would gather its operands and run it replicated.
    The decision reads the mesh of the program being traced — the one the
    caller passes, else the ambient abstract mesh (``TrainStep`` sets it
    while tracing) — never the number of chips the host happens to have.
    Axes already made manual by an enclosing ``shard_map`` do not count:
    there the kernel sees one shard.
    """
    if not on_tpu():
        return False
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
        spans = math.prod(
            mesh.shape[a] for a in mesh.auto_axes + mesh.explicit_axes
        )
    else:
        spans = mesh.size
    if spans > 1:
        # Trace time, and attributed to this line, so the default warnings
        # filter says it once per process however many call sites ask.
        warnings.warn(
            f"pallas kernels are off under a {spans}-device mesh: attention "
            "and the lm-head CE take their XLA formulations, which the "
            "SPMD partitioner can shard"
        )
        return False
    return True


def row_stat_col(ref, idx, block: int):
    """Row-stat block (1, 1, N) -> column (block, 1) for row-block idx.

    Row statistics (lse, delta, targets) enter kernels as compact
    [.., 1, N] arrays (4 KB per visit) instead of the official kernels'
    lane-padded [.., N, 128] layout (260 KB per visit); the in-kernel
    slice + lane->sublane relayout of `block` elements is measured noise."""
    from jax.experimental import pallas as pl

    seg = ref[0, 0:1, pl.ds(idx * block, block)]  # (1, block)
    return jnp.transpose(seg, (1, 0))
