"""Mixture-of-Experts FFN: dropless on one device, capacity-bound over an
expert-parallel mesh.

Capability beyond the reference: torchft has no EP anywhere (SURVEY.md §2.3
— PP/CP/EP absent); this is part of the TPU build's first-class parallelism
surface alongside ring/Ulysses sequence parallelism.

Routing is shared (``route``): float32 softmax over the router's logits,
top-k, the kept gates renormalised or left as they are (OLMoE leaves them).
Then one of two ways to the experts, both with STATIC shapes:

  - **dropless, sorted** (``capacity_factor=None``; one device holds every
    expert): the T * k (token, expert) assignments are ordered by expert —
    a rank within the expert from a cumulative sum, no sort — into one
    row buffer in which every expert's rows start on a row-tile boundary
    (``ops.padded_group_sizes``), the three expert projections are grouped
    matmuls over that buffer (``ops.grouped_matmul``: the ``tpuft_gmm_*``
    kernels on a TPU), and the rows go back to their tokens weighted by
    their gates.  No capacity, so no assignment is ever dropped.  Both
    directions of both moves are gathers (a token has exactly k rows and a
    row one token), so the backward pass has no scatter-add;
  - **capacity-bound, dense dispatch** (GShard/Switch style,
    arXiv:2006.16668; what a mesh with an "expert" axis runs): dense
    dispatch/combine tensors [T, n_exp, capacity], over-capacity
    assignments dropped (the residual path carries the token), the expert
    FFN a batched einsum over the stacked expert axis, which maps to the
    "expert" mesh axis so that the dispatch/combine einsums compile to the
    all-to-alls.  Its tensors grow with T * n_exp * capacity: at 8192
    tokens, 64 experts and top-8 each is 2.7 GB, which is why one device
    takes the sorted path.

Auxiliary losses, per sequence and averaged over the batch (as a
data-parallel job takes its statistics per device batch): the load-balance
loss ``n_exp * sum_e f_e * P_e`` with f_e the share of the sequence's
positions that chose expert e among their k (Switch Transformer,
arXiv:2101.03961, as OLMoE applies it to top-k) and P_e the mean router
probability, and the router z-loss ``mean(logsumexp(logits)^2)``
(arXiv:2202.08906).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, padded_group_sizes
from torchft_tpu.parallel.sharding import ShardingRules, constrain

Stats = Dict[str, jax.Array]


def moe_capacity(tokens: int, n_experts: int, top_k: int, capacity_factor: float) -> int:
    """Static per-expert token capacity, padded to the 8-sublane boundary."""
    cap = int(tokens * top_k * capacity_factor / n_experts) + 1
    return max(8, -(-cap // 8) * 8)


def route(x: jax.Array, router: jax.Array, top_k: int, norm_topk: bool):
    """x [B, S, E], router [E, n_exp] -> (logits, probs [B, S, n_exp] f32,
    gate_vals [B, S, k] f32, gate_idx [B, S, k]).  The logits are a float32
    product at the highest precision: which experts a token takes hangs on
    differences far under bf16's rounding."""
    logits = jnp.einsum(
        "bse,ex->bsx", x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        # Renormalize the kept gates so the combine is a convex mixture.
        gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, gate_idx


def router_stats(logits: jax.Array, probs: jax.Array, gate_idx: jax.Array) -> Stats:
    """The two auxiliary losses (module docstring) and how many assignments
    each expert received, over the whole batch."""
    n_exp = probs.shape[-1]
    chose = jnp.sum(jax.nn.one_hot(gate_idx, n_exp, dtype=jnp.float32), axis=2)  # [B, S, n_exp]
    balance = n_exp * jnp.sum(jnp.mean(chose, axis=1) * jnp.mean(probs, axis=1), axis=-1)
    return {
        "balance": jnp.mean(balance),
        "z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "tokens_per_expert": jnp.sum(chose, axis=(0, 1)).astype(jnp.int32),
        "chosen": gate_idx,
    }


# -- dropless: sorted rows, grouped matmuls -----------------------------------


def _int_zero(x: jax.Array):
    return np.zeros(x.shape, jax.dtypes.float0)


@jax.custom_vjp
def _rows_of_tokens(xf, row_token, dest):
    """xf [T, E] -> [R, E]: row r is token ``row_token[r]``'s activation.  A
    row no assignment landed in (``row_token[r] == T``) repeats the last
    token's: it only has to be finite, because its cotangent is zero
    (``_tokens_of_rows`` gives it a gate of zero), which keeps it out of
    every gradient, and nothing reads its output."""
    return jnp.take(xf, row_token, axis=0, mode="clip")


def _rows_fwd(xf, row_token, dest):
    return _rows_of_tokens(xf, row_token, dest), (row_token, dest)


def _rows_bwd(res, drows):
    # A token's k rows are at dest[t]: a gather and a sum, not a scatter-add.
    row_token, dest = res
    dxf = jnp.sum(jnp.take(drows, dest, axis=0, mode="clip").astype(jnp.float32), axis=1)
    return dxf.astype(drows.dtype), _int_zero(row_token), _int_zero(dest)


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _tokens_of_rows(rows, gates, dest, row_assignment):
    """rows [R, E], gates [T, k] f32 -> [T, E]: each token the sum of its k
    rows weighted by their gates."""
    picked = jnp.take(rows, dest, axis=0, mode="clip").astype(jnp.float32)  # [T, k, E]
    return jnp.einsum("tke,tk->te", picked, gates).astype(rows.dtype)


def _tokens_fwd(rows, gates, dest, row_assignment):
    return _tokens_of_rows(rows, gates, dest, row_assignment), (rows, gates, dest, row_assignment)


def _tokens_bwd(res, dy):
    rows, gates, dest, row_assignment = res
    k = gates.shape[1]
    # Row r belongs to assignment row_assignment[r] = t * k + j (T * k where
    # none landed): its cotangent is token t's, weighted by that gate — by
    # zero where none landed.
    row_gate = jnp.take(gates.reshape(-1), row_assignment, mode="fill", fill_value=0)
    drows = jnp.take(dy, row_assignment // k, axis=0, mode="clip")
    drows = (drows.astype(jnp.float32) * row_gate[:, None]).astype(rows.dtype)
    picked = jnp.take(rows, dest, axis=0, mode="clip").astype(jnp.float32)
    dgates = jnp.einsum("tke,te->tk", picked, dy.astype(jnp.float32))
    return drows, dgates, _int_zero(dest), _int_zero(row_assignment)


_tokens_of_rows.defvjp(_tokens_fwd, _tokens_bwd)


def _dropless_ffn(xf, gate_vals, gate_idx, w_gate, w_up, w_down, *, mesh):
    """xf [T, E] in the compute type; gate_vals, gate_idx [T, k].  Returns
    (y [T, E], assignments that found no row — none, by the buffer's size)."""
    tokens, k = gate_idx.shape
    n_exp = w_gate.shape[0]
    n_assign = tokens * k
    row_tile = ROW_TILE
    rows = -(-(n_assign + n_exp * row_tile) // row_tile) * row_tile

    expert = gate_idx.reshape(n_assign)
    mine = (expert[:, None] == jnp.arange(n_exp, dtype=expert.dtype)[None, :]).astype(jnp.int32)
    arrived = jnp.cumsum(mine, axis=0)  # [T * k, n_exp]: assignments of each expert up to and with this one
    # Each assignment's own column, as a masked sum: a gather of T * k
    # scalars costs the v5e 4 ms, this pass over 16 MB a few microseconds.
    rank = jnp.sum(arrived * mine, axis=1) - 1
    sizes = padded_group_sizes(arrived[-1], row_tile)
    starts = jnp.cumsum(sizes) - sizes
    dest = jnp.sum(starts[None, :] * mine, axis=1) + rank  # the row of each assignment; distinct, all < rows
    row_assignment = jnp.full((rows,), n_assign, jnp.int32).at[dest].set(
        jnp.arange(n_assign, dtype=jnp.int32), unique_indices=True
    )
    dest = dest.reshape(tokens, k)

    xs = _rows_of_tokens(xf, row_assignment // k, dest)
    gate = grouped_matmul(xs, w_gate, sizes, row_tile=row_tile, mesh=mesh)
    up = grouped_matmul(xs, w_up, sizes, row_tile=row_tile, mesh=mesh)
    out = grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes, row_tile=row_tile, mesh=mesh)
    y = _tokens_of_rows(out, gate_vals, dest, row_assignment)
    return y, jnp.sum((dest >= rows).astype(jnp.int32))


# -- capacity-bound: dense dispatch/combine tensors ---------------------------


def _capacity_ffn(xf, gate_vals, gate_idx, w_gate, w_up, w_down, *, capacity, dtype, mesh, rules):
    """xf [T, E]; returns (y [T, E], assignments dropped over capacity)."""
    T = xf.shape[0]
    n_exp = w_gate.shape[0]
    top_k = gate_idx.shape[1]
    C = capacity

    # Position of each (token, choice) in its expert's capacity buffer:
    # choices are prioritized k-major (all rank-0 choices first), so a
    # token's primary expert wins buffer slots over anyone's secondary.
    onehot = jax.nn.one_hot(gate_idx, n_exp, dtype=jnp.float32)  # [T, k, n_exp]
    flat = onehot.transpose(1, 0, 2).reshape(top_k * T, n_exp)    # k-major
    pos_flat = jnp.cumsum(flat, axis=0) - 1.0                     # [kT, n_exp]
    pos = pos_flat.reshape(top_k, T, n_exp).transpose(1, 0, 2)    # [T, k, n_exp]
    within = (pos < C) & (onehot > 0)

    # dispatch[t, e, c] = 1 where token t landed in slot c of expert e;
    # combine carries the gate weight instead.
    slot = jax.nn.one_hot(
        jnp.where(within, pos, -1).astype(jnp.int32).max(axis=-1).clip(0),
        C,
        dtype=jnp.float32,
    )  # [T, k, C] (clip is safe: masked rows are zeroed below)
    kept = within.any(axis=-1).astype(jnp.float32)                 # [T, k]
    expert_oh = onehot * within.astype(jnp.float32)                # [T, k, n_exp]
    dispatch = jnp.einsum("tke,tkc,tk->tec", expert_oh, slot, kept)
    combine = jnp.einsum("tke,tkc,tk->tec", expert_oh, slot, kept * gate_vals)

    # Dispatch -> stacked expert FFN -> combine.  The "expert" leading axis
    # is sharded over the expert mesh axis; these einsums ARE the
    # all-to-alls once partitioned.
    xin = jnp.einsum("tec,td->ecd", dispatch.astype(dtype), xf.astype(dtype))
    xin = constrain(xin, ("expert", None, "embed"), mesh, rules)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, w_gate.astype(dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xin, w_up.astype(dtype))
    h = constrain(h, ("expert", None, "mlp"), mesh, rules)
    out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dtype))
    out = constrain(out, ("expert", None, "embed"), mesh, rules)
    y = jnp.einsum("tec,ecd->td", combine.astype(dtype), out)
    return y, (T * top_k - jnp.sum(kept)).astype(jnp.int32)


# -- the layer ------------------------------------------------------------------


def moe_layer(
    x: jax.Array,
    router: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    top_k: int = 2,
    capacity_factor: Optional[float] = 1.25,
    norm_topk: bool = True,
    dtype: Any = jnp.bfloat16,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> Tuple[jax.Array, Stats]:
    """MoE feed-forward.

    Args:
        x: [B, S, E] activations.
        router: [E, n_exp] routing weights (kept f32 — routing logits are
            numerically sensitive).
        w_gate/w_up: [n_exp, E, F]; w_down: [n_exp, F, E] stacked experts.
        capacity_factor: None = dropless (the sorted path; one device holds
            every expert); a number = the capacity-bound dense dispatch.

    Returns:
        (y [B, S, E], stats): ``balance`` and ``z`` (scalar f32 auxiliary
        losses), ``tokens_per_expert`` ([n_exp] int32, assignments each
        expert was sent), ``chosen`` ([B, S, k], the experts each position
        took) and ``dropped`` (int32, assignments that reached no expert: 0
        on the dropless path by construction).
    """
    rules = rules or ShardingRules()
    B, S, E = x.shape
    n_exp = router.shape[1]
    T = B * S
    logits, probs, gate_vals, gate_idx = route(x, router, top_k, norm_topk)
    stats = router_stats(logits, probs, gate_idx)
    gate_vals, gate_idx = gate_vals.reshape(T, top_k), gate_idx.reshape(T, top_k)
    xf = x.reshape(T, E)
    if capacity_factor is None:
        if mesh is not None and "expert" in mesh.axis_names and mesh.shape["expert"] > 1:
            raise ValueError(
                "the dropless MoE path holds every expert on one device; a mesh with an "
                "'expert' axis takes the capacity-bound path (set moe_capacity_factor)"
            )
        y, dropped = _dropless_ffn(
            xf.astype(dtype), gate_vals, gate_idx, w_gate, w_up, w_down, mesh=mesh
        )
    else:
        y, dropped = _capacity_ffn(
            xf, gate_vals, gate_idx, w_gate, w_up, w_down,
            capacity=moe_capacity(T, n_exp, top_k, capacity_factor), dtype=dtype, mesh=mesh, rules=rules,
        )
    stats["dropped"] = dropped
    return y.reshape(B, S, E).astype(x.dtype), stats


def moe_ffn(x, router, w_gate, w_up, w_down, **kwargs) -> Tuple[jax.Array, jax.Array]:
    """``moe_layer`` returning (y, load-balance loss) alone."""
    y, stats = moe_layer(x, router, w_gate, w_up, w_down, **kwargs)
    return y, stats["balance"]
