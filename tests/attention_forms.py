"""The attention tests' operands, written head-major ([B * heads, S, d]: a head
an array of its own, the form the XLA oracles `_fa_reference` / `_fa_bwd_xla`
take), fed to the flash kernels position-major as the projections leave them
and the kernels read them ([B, S, heads * d]: a head a lane-aligned column
block), and the kernels' results turned back for the comparison."""

from torchft_tpu.ops import attention as fa


def rows(x, batch: int = 1):
    """[B * heads, S, d] -> [B, S, heads * d]."""
    return fa._from_heads(x, x.shape[0] // batch)


def heads(x, n: int):
    """[B, S, n * d] -> [B * n, S, d]."""
    return fa._to_heads(x, n)


def fwd(q, k, v, *args, batch: int = 1, **more):
    """`_fa_pallas_call` on head-major q, k, v: (out head-major, lse)."""
    n = q.shape[0] // batch
    o, lse = fa._fa_pallas_call(rows(q, batch), rows(k, batch), rows(v, batch), *args, q_heads=n, **more)
    return heads(o, n), lse


def bwd(q, k, v, o, lse, g, *args, batch: int = 1, **more):
    """`_fa_bwd_pallas` on head-major operands: (dq, dk, dv) head-major, dk
    and dv a query head each."""
    n = q.shape[0] // batch
    grads = fa._fa_bwd_pallas(*(rows(x, batch) for x in (q, k, v, o)), lse, rows(g, batch), *args, q_heads=n, **more)
    return tuple(heads(x, n) for x in grads)
