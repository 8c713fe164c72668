"""The one shard_map call every sharded op goes through."""

from __future__ import annotations

from typing import Any, Optional

import jax


def shard_map(
    f,
    mesh,
    in_specs: Any,
    out_specs: Any,
    check: Optional[bool] = None,
):
    """``jax.shard_map(f)`` bound to ``mesh`` with the given specs.

    ``check=None`` keeps the library's default replication checking;
    False/True pins ``check_vma``.
    """
    kwargs = {} if check is None else {"check_vma": check}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )
