"""Mixture-of-Experts FFN: dropless on one device, capacity-bound over an
expert-parallel mesh.

Capability beyond the reference: torchft has no EP anywhere (SURVEY.md §2.3
— PP/CP/EP absent); this is part of the TPU build's first-class parallelism
surface alongside ring/Ulysses sequence parallelism.

Routing is shared (``route``), a float32 product over ALL the router's
outputs in one of two forms:

  - ``score="softmax"`` (OLMoE): softmax over the logits, the k largest,
    the kept gates renormalised or left as they are (OLMoE leaves them);
  - ``score="sigmoid"`` (DeepSeek-V3's ``noaux_tc``, Moonlight): a sigmoid
    score per expert, the k largest of score + ``bias`` chosen (the bias
    steers the choice alone: a buffer, not a trained weight, constant under
    the gradient), the gates the chosen scores WITHOUT the bias,
    renormalised over the k and times ``scale``
    (``routed_scaling_factor``).  Its balance loss takes the scores
    normalised over the experts for P_e and divides by k (DeepSeek-V3's
    sequence-wise term).

The router is one matrix, or a function with a state that the layer loop
carries (``state_router_logits``); its last output may be a **choice that
takes no expert** (``moe_layer(skip=True)``): the position gets no row on the
dropless path, adds nothing, and is counted in ``skipped`` — never in
``dropped``, which counts assignments to held experts that found no row.

Then one of two ways to the experts, both with STATIC shapes:

  - **dropless, sorted** (``capacity_factor=None``; the experts' matrices
    are the ones THIS device holds, ``held = (first, count)`` of the
    router's outputs — all of them for OLMoE, one chip's share of an
    expert-parallel layer for Moonlight): the (token, expert) assignments
    that fall on held experts are ordered by expert — a rank within the
    expert from a cumulative sum, no sort — into one row buffer in which
    every held expert's rows start on a row-tile boundary
    (``ops.padded_group_sizes``), the three expert projections are grouped
    matmuls over that buffer (``ops.grouped_matmul``: the ``tpuft_gmm_*``
    kernels on a TPU), and the rows go back to their tokens weighted by
    their gates.  An assignment to an expert held elsewhere gets no row
    and adds nothing: the layer's result is this device's part of the sum
    (its all-to-all partner would add the rest; no code stands in for it).
    With every expert held the buffer takes all T * k assignments, so none
    is ever dropped; a share's buffer is ``HELD_ROWS_FACTOR`` times its
    even share of them, and an assignment to a held expert that finds it
    full is counted in ``dropped``.  Both directions of both moves are
    gathers (a token has at most k rows and a row one token), so the
    backward pass has no scatter-add.  A layer gathers a token's T * k rows
    twice and no more: in the combine (``_tokens_of_rows``) and in the
    dispatch's transpose (``_rows_bwd``, a combine of the same kind).  The
    combine's own backward gathers none: a gate's gradient is the product
    of its row with its token's cotangent, and both are already on the ROW
    side there — the expert's output, and the cotangent's rows gathered for
    the rows' own gradient — so it is a pass over two [R, E] arrays and a
    scatter of R float32 scalars through ``row_assignment`` (distinct
    indices; 0.08-0.34 ms on a v5e for 17,408-73,728 rows) where the
    token-side form gathered all T * k rows a third time (4.8 ms over
    131,072 rows of 2,048; PERF.md, PR 45).  A chip of an expert-parallel
    layer has the same choice: the expert's side returns T * k scalars or
    the token's side needs the rows again.  Read back by gather
    (``rowdot[dest]``) the scalars would cost more than they save: 65,536
    gathered scalars took this chip 4.1 ms (PERF.md, PR 27).  Where k is no
    whole number of the
    TPU's 8-row tiles (top-6) a token's k rows are gathered **k-major**,
    ``[k, T, E]``: the TPU tiles an array's two minor axes, so ``[T, 6, E]``
    lays six rows on an eight-row tile and the compiler re-tiles the
    gathered rows — a relayout of T * k * E elements after each gather,
    1.5 ms at 16,384 x 6 x 2,048 on a v5e, 22 ms a step over fifteen
    gathers — where ``[k, T, E]`` keeps ``[T, E]`` minor, which tiles at
    every k, and IS the gather's ``[k * T, E]`` result, bit for bit.  At
    k = 8 ``[T, k, E]`` is that result bit for bit too, and there the rows
    stay token-major (``_k_leads``): measured on the v5e in the cells that
    route top-8, the k-major program's gathers of 262,144 rows took 15%
    longer and a step's expert layers 19 ms more (PERF.md, PR 36).  Where
    the row buffer is too large for XLA to keep it in the fast memory (on
    one TPU device: ``ops/moe_rows.applies``, a rule over static shapes)
    those two T * k-row gathers and their sums are one `tpuft_moe_rows` call
    each (``_rows_summed``): the rows fetched a DMA each and summed where
    they land, no ``[T, k, E]`` array — 3.5 ms a call where the gather and
    its weighting pass took 11.3 (262,144 rows; PERF.md, PR 67).  For
    the same reason as the k-major rows, a chosen scalar is picked by
    comparison and never gathered along a k-wide minor axis (``route``'s
    gates, ``rank`` and ``dest`` below);
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
arXiv:2101.03961, as OLMoE applies it to top-k; over k for the sigmoid
router, arXiv:2412.19437 eq. 17-19) and P_e the mean router probability,
and the router z-loss ``mean(logsumexp(logits)^2)`` (arXiv:2202.08906).

A **shared expert** (``moe_layer(shared=...)``) is one SwiGLU every token
passes beside the routed ones; every device of an expert-parallel layer
computes it alike.  Under a gate of its own (``shared_scale`` [E, 1], Qwen3-
Next's and Qwen2-MoE's) it adds ``sigmoid(x w_s) * Shared(x)``: the gate a
number a token, a float32 product and sigmoid, its mean over the positions in
``stats["shared_gate"]``.

The gated product's activation is the caller's (``activation``, ``ACTIVATIONS``):
SiLU, or ReLU (ReGLU, SmallThinker's experts) — whose derivative is the
mask ``gate > 0`` and nothing else of the gate, and which leaves a share of
each row's hidden units at exactly zero: ``stats["active_units"]`` counts the
others over the rows that hold an assignment.

The scores' input need not be the experts' (``routing``, ``moe_layer(routed=
...)``): a router that reads the layer's input BEFORE its mixer is routed
there, by the layer, and the experts take the choice with the normed stream
after the mixer.  The gates' cotangent then flows into the stream the router
read.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.ops import moe_rows
from torchft_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul, padded_group_sizes
from torchft_tpu.parallel.sharding import ShardingRules, constrain

Stats = Dict[str, jax.Array]

# A share's dropless row buffer as a multiple of its even share of the
# assignments: shapes are static, so the buffer needs a bound (`held_rows`).
HELD_ROWS_FACTOR = 2.0

# What a gated feed-forward applies to its gate: SwiGLU's or ReGLU's; and what
# an UN-GATED one ("relu2": no gate matrix, `w_gate` None) applies to its one
# hidden product: the square of ReLU (Nemotron-H's experts).
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu, "relu2": lambda h: jnp.square(jax.nn.relu(h))}


def hidden_units(activation: str, w_gate, w_up, product):
    """A feed-forward's hidden units from ``product(w)``, its input times a
    matrix: ``act(product(w_gate)) * product(w_up)``, or un-gated (``w_gate``
    None) ``act(product(w_up))``."""
    if w_gate is None:
        return ACTIVATIONS[activation](product(w_up))
    return ACTIVATIONS[activation](product(w_gate)) * product(w_up)


def moe_capacity(tokens: int, n_experts: int, top_k: int, capacity_factor: float) -> int:
    """Static per-expert token capacity, padded to the 8-sublane boundary."""
    cap = int(tokens * top_k * capacity_factor / n_experts) + 1
    return max(8, -(-cap // 8) * 8)


def state_router_logits(x: jax.Array, router: Dict[str, jax.Array], state: Optional[jax.Array], rms_eps: float):
    """The router as a function with a state (ZAYA1's, arXiv:2511.17127):
    x [B, S, E], ``router`` its subtree of weights, ``state`` [B, S, R] the
    layer before's (None: there is none) -> (logits [B, S, outputs], this
    layer's state [B, S, R]), both float32:

        r = x W_down + b_down + carry * state      (what the next layer receives, un-normed)
        logits = W3 gelu(W2 gelu(W1 RMSNorm(r) + b1) + b2)

    all of it float32 at the highest precision, as a matrix router's product
    is: which expert a token takes hangs on differences far under bf16's
    rounding, and here they pass through three more products."""
    hp = jax.lax.Precision.HIGHEST
    w = {name: leaf.astype(jnp.float32) for name, leaf in router.items()}
    r = jnp.einsum("bse,er->bsr", x.astype(jnp.float32), w["down"], precision=hp) + w["down_bias"]
    if state is not None:
        r = r + w["carry"] * state
    h = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + rms_eps) * w["norm"]
    h = jax.nn.gelu(jnp.einsum("bsr,rq->bsq", h, w["w1"], precision=hp) + w["b1"], approximate=False)
    h = jax.nn.gelu(jnp.einsum("bsr,rq->bsq", h, w["w2"], precision=hp) + w["b2"], approximate=False)
    return jnp.einsum("bsr,rx->bsx", h, w["w3"], precision=hp), r


def route(x: jax.Array, router: Optional[jax.Array], top_k: int, norm_topk: bool, *,
          score: str = "softmax", bias: Optional[jax.Array] = None, scale: float = 1.0,
          logits: Optional[jax.Array] = None):
    """x [B, S, E], router [E, n_exp] -> (logits, probs [B, S, n_exp] f32,
    gate_vals [B, S, k] f32, gate_idx [B, S, k]).  The logits are a float32
    product at the highest precision: which experts a token takes hangs on
    differences far under bf16's rounding — or, where the router is a
    function (``state_router_logits``), come in as ``logits``.  ``probs`` is
    what the balance loss averages: the softmax, or the sigmoid scores
    normalised over the experts.  ``bias`` [n_exp] moves the choice and never
    a gate."""
    if logits is None:
        logits = jnp.einsum(
            "bse,ex->bsx", x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
    if score == "softmax":
        assert scale == 1.0, "the softmax router has no scale"
        probs = jax.nn.softmax(logits, axis=-1)
        if bias is not None:
            # The k largest of probability + bias, the gates the probabilities
            # themselves: picked by comparison as the sigmoid router's are.
            _, gate_idx = jax.lax.top_k(probs + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
            chosen = gate_idx[..., None] == jnp.arange(probs.shape[-1], dtype=gate_idx.dtype)
            gate_vals = jnp.sum(jnp.where(chosen, probs[..., None, :], 0.0), axis=-1)
        else:
            gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
        if norm_topk:
            # Renormalize the kept gates so the combine is a convex mixture.
            gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
        return logits, probs, gate_vals, gate_idx
    assert score == "sigmoid", f"unknown router score {score!r}"
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, gate_idx = jax.lax.top_k(choice, top_k)
    # The chosen scores, as a masked sum over the experts: one term is not
    # zero, so the values are the gathered ones bit for bit, and the
    # derivative is a masked sum too (the k indices are distinct) where
    # `take_along_axis` gathers T * k scalars along a k-wide axis and
    # scatter-adds them back (0.6 ms a call on the v5e at 16,384 x 6 of 64).
    chosen = gate_idx[..., None] == jnp.arange(scores.shape[-1], dtype=gate_idx.dtype)
    gate_vals = jnp.sum(jnp.where(chosen, scores[..., None, :], 0.0), axis=-1)
    if norm_topk:
        gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-20)
    probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    return logits, probs, gate_vals * scale, gate_idx


def router_stats(logits: jax.Array, probs: jax.Array, gate_idx: jax.Array, *, per_choice: bool = False) -> Stats:
    """The two auxiliary losses (module docstring) and how many assignments
    each expert received, over the whole batch.  ``per_choice``: f_e counts
    a position's k choices as one (the balance loss over k)."""
    n_exp = probs.shape[-1]
    chose = jnp.sum(jax.nn.one_hot(gate_idx, n_exp, dtype=jnp.float32), axis=2)  # [B, S, n_exp]
    balance = n_exp * jnp.sum(jnp.mean(chose, axis=1) * jnp.mean(probs, axis=1), axis=-1)
    if per_choice:
        balance = balance / gate_idx.shape[-1]
    return {
        "balance": jnp.mean(balance),
        "z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "tokens_per_expert": jnp.sum(chose, axis=(0, 1)).astype(jnp.int32),
        "chosen": gate_idx,
    }


# -- dropless: sorted rows, grouped matmuls -----------------------------------


def _int_zero(x: jax.Array):
    return np.zeros(x.shape, jax.dtypes.float0)


def _k_leads(k: int) -> bool:
    """Whether a token's k rows are gathered as [k, T, E] (module
    docstring): where [T, k, E] would lay k rows on tiles of 8."""
    return k % 8 != 0


def _take_rows(rows, dest, every_row_exists: bool):
    """rows[dest] for ``dest`` [T, k]: [T, k, E], or [k, T, E] where the k
    choices lead (``_k_leads``).  A `dest` past the end (an assignment
    without a row) reads zeros.  Where every assignment has a row by
    construction the check is left out."""
    if _k_leads(dest.shape[1]):
        dest = dest.T
    if every_row_exists:
        return jnp.take(rows, dest, axis=0, mode="clip")
    return jnp.take(rows, dest, axis=0, mode="fill", fill_value=0)


def _rows_summed(rows, dest, gates, every_row_exists: bool, fused: bool):
    """rows [R, E], dest [T, k], gates [T, k] f32 or None (ones) -> [T, E] in
    ``rows``'s type: each token the float32 sum of its rows ``rows[dest[t]]``
    weighted by its gates, an assignment without a row adding zero.
    ``fused``: one `tpuft_moe_rows` call (``ops/moe_rows.py``: the rows
    fetched a DMA each and summed where they land, no [T, k, E] array); else
    XLA's gather and a pass over what it gathered — k-major where the k
    choices lead, the gates staying [T, k] as the router gives them (T * k
    scalars: their transpose is inside the product)."""
    if fused:
        return moe_rows.moe_rows(rows, dest, gates)
    picked = _take_rows(rows, dest, every_row_exists).astype(jnp.float32)
    k_leads = _k_leads(dest.shape[1])
    if gates is None:
        return jnp.sum(picked, axis=0 if k_leads else 1).astype(rows.dtype)
    return jnp.einsum("kte,tk->te" if k_leads else "tke,tk->te", picked, gates).astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rows_of_tokens(xf, row_token, dest, every_row_exists: bool, fused: bool):
    """xf [T, E] -> [R, E]: row r is token ``row_token[r]``'s activation.  A
    row no assignment landed in (``row_token[r] == T``) repeats the last
    token's: it only has to be finite, because its cotangent is zero
    (``_tokens_of_rows`` gives it a gate of zero), which keeps it out of
    every gradient, and nothing reads its output."""
    return jnp.take(xf, row_token, axis=0, mode="clip")


def _rows_fwd(xf, row_token, dest, every_row_exists, fused):
    return _rows_of_tokens(xf, row_token, dest, every_row_exists, fused), (row_token, dest)


def _rows_bwd(every_row_exists, fused, res, drows):
    # A token's rows are at dest[t]: a gather and a sum, not a scatter-add.
    row_token, dest = res
    return _rows_summed(drows, dest, None, every_row_exists, fused), _int_zero(row_token), _int_zero(dest)


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _tokens_of_rows(rows, gates, dest, row_assignment, every_row_exists: bool, fused: bool):
    """rows [R, E], gates [T, k] f32, dest [T, k] -> [T, E]: each token the
    sum of its rows weighted by their gates (``_rows_summed``)."""
    return _rows_summed(rows, dest, gates, every_row_exists, fused)


def _tokens_fwd(rows, gates, dest, row_assignment, every_row_exists, fused):
    out = _tokens_of_rows(rows, gates, dest, row_assignment, every_row_exists, fused)
    return out, (rows, gates, dest, row_assignment)


def _tokens_bwd(every_row_exists, fused, res, dy):
    rows, gates, dest, row_assignment = res
    k, n_assign = gates.shape[1], gates.size
    # Row r belongs to assignment row_assignment[r] = t * k + j (T * k where
    # none landed): its cotangent is token t's, weighted by that gate — by
    # zero where none landed.  (R scalars gathered, 0.48 ms at 67,584: the
    # T * k gates scattered through `dest` took longer wherever R < T * k.)
    row_gate = jnp.take(gates.reshape(-1), row_assignment, mode="fill", fill_value=0)
    dy_rows = jnp.take(dy, row_assignment // k, axis=0, mode="clip").astype(jnp.float32)
    drows = (dy_rows * row_gate[:, None]).astype(rows.dtype)
    # The gate's gradient is <row, its token's cotangent>, taken here where
    # both rows already are and written to the row's assignment: token-major
    # whichever axis leads the forward's gather.  A row none landed in gets
    # an index past the end of its own, so the indices stay distinct and the
    # scatter skips it; an assignment without a row keeps its zero.
    rowdot = jnp.sum(rows.astype(jnp.float32) * dy_rows, axis=1)
    empty = row_assignment == n_assign
    at = row_assignment + jnp.where(empty, jnp.arange(row_assignment.size, dtype=row_assignment.dtype), 0)
    dgates = jnp.zeros((n_assign,), jnp.float32).at[at].set(rowdot, unique_indices=True, mode="drop")
    return drows, dgates.reshape(gates.shape), _int_zero(dest), _int_zero(row_assignment)


_tokens_of_rows.defvjp(_tokens_fwd, _tokens_bwd)


def held_rows(n_assign: int, n_exp: int, count: int, factor: float, row_tile: int = ROW_TILE) -> int:
    """Rows of the dropless buffer for ``count`` held experts of ``n_exp``:
    ``factor`` times their even share of the assignments — never more than
    all of them, which is what every expert held takes — plus a tile an
    expert for the padding, in whole tiles."""
    share = n_assign if count == n_exp else min(n_assign, int(-(-n_assign * count * factor // n_exp)))
    return -(-(share + count * row_tile) // row_tile) * row_tile


def _dropless_ffn(xf, gate_vals, gate_idx, w_gate, w_up, w_down, *, n_exp, first, rows_factor, mesh,
                  skip: bool = False, activation: str = "silu"):
    """xf [T, E] in the compute type; gate_vals, gate_idx [T, k] over the
    router's ``n_exp`` experts — and, with ``skip``, the choice ``n_exp`` that
    takes none: like an expert held elsewhere it gets no row, and the buffer
    is sized as if no position took it; the matrices those of the held experts
    ``first ... first + count - 1``.  Returns (y [T, E], assignments that
    fell on held experts, those of them that found no row — none where
    every expert is held, by the buffer's size — and under ReLU the (row,
    hidden unit) pairs that are not zero over the rows an assignment landed
    in, else None)."""
    tokens, k = gate_idx.shape
    count = w_up.shape[0]
    n_assign = tokens * k
    row_tile = ROW_TILE
    rows = held_rows(n_assign, n_exp, count, rows_factor, row_tile)
    every_row_exists = count == n_exp and not skip  # every assignment is held and the buffer takes them all: `dest` is in bounds

    expert = gate_idx.reshape(n_assign)
    held = jnp.arange(first, first + count, dtype=expert.dtype)
    mine = (expert[:, None] == held[None, :]).astype(jnp.int32)
    arrived = jnp.cumsum(mine, axis=0)  # [T * k, count]: assignments of each held expert up to and with this one
    # Each assignment's own column, as a masked sum: a gather of T * k
    # scalars costs the v5e 4 ms, this pass over 16 MB a few microseconds.
    rank = jnp.sum(arrived * mine, axis=1) - 1
    sizes = padded_group_sizes(arrived[-1], row_tile)
    starts = jnp.cumsum(sizes) - sizes
    dest = jnp.sum(starts[None, :] * mine, axis=1) + rank  # the row of each assignment; distinct
    n_held = jnp.sum(arrived[-1])
    if every_row_exists:
        dropped = jnp.sum((dest >= rows).astype(jnp.int32))
    else:
        # An assignment to an expert held elsewhere, or past the buffer's
        # end, gets a row past `rows` (each its own, so that the scatter's
        # indices stay distinct): out of bounds, so the scatter below skips
        # it and every gather reads zeros for it.
        here = jnp.sum(mine, axis=1) > 0
        dropped = jnp.sum((here & (dest >= rows)).astype(jnp.int32))
        dest = jnp.where(here & (dest < rows), dest, rows + jnp.arange(n_assign, dtype=dest.dtype))
    row_assignment = jnp.full((rows,), n_assign, jnp.int32).at[dest].set(
        jnp.arange(n_assign, dtype=jnp.int32), unique_indices=True
    )
    dest = dest.reshape(tokens, k)
    # The layer's two T * k-row gathers (the combine, the dispatch's transpose:
    # the same shapes) through `tpuft_moe_rows` where the row buffer is too
    # large for XLA to read it out of the fast memory: a static shape decides.
    fused = moe_rows.applies((rows, xf.shape[1]), xf.dtype, dest.shape, mesh)

    xs = _rows_of_tokens(xf, row_assignment // k, dest, every_row_exists, fused)
    if w_gate is None:  # un-gated: the activation on the one hidden product
        gate = grouped_matmul(xs, w_up, sizes, row_tile=row_tile, mesh=mesh)
        hidden = ACTIVATIONS[activation](gate)
    else:
        gate = grouped_matmul(xs, w_gate, sizes, row_tile=row_tile, mesh=mesh)
        up = grouped_matmul(xs, w_up, sizes, row_tile=row_tile, mesh=mesh)
        hidden = ACTIVATIONS[activation](gate) * up
    out = grouped_matmul(hidden, w_down, sizes, row_tile=row_tile, mesh=mesh)
    y = _tokens_of_rows(out, gate_vals, dest, row_assignment, every_row_exists, fused)
    active = None
    if activation in ("relu", "relu2"):
        landed = (row_assignment < n_assign)[:, None]  # a padding row repeats a token's and counts nothing
        active = jnp.sum(((ACTIVATIONS[activation](gate) != 0) & landed).astype(jnp.int32))
    return y, n_held.astype(jnp.int32), dropped, active


# -- capacity-bound: dense dispatch/combine tensors ---------------------------


def _capacity_ffn(xf, gate_vals, gate_idx, w_gate, w_up, w_down, *, capacity, dtype, mesh, rules,
                  activation: str = "silu"):
    """xf [T, E]; returns (y [T, E], assignments dropped over capacity)."""
    T = xf.shape[0]
    n_exp = w_up.shape[0]
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
    h = hidden_units(activation, w_gate, w_up, lambda w: jnp.einsum("ecd,edf->ecf", xin, w.astype(dtype)))
    h = constrain(h, ("expert", None, "mlp"), mesh, rules)
    out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dtype))
    out = constrain(out, ("expert", None, "embed"), mesh, rules)
    y = jnp.einsum("tec,ecd->td", combine.astype(dtype), out)
    return y, (T * top_k - jnp.sum(kept)).astype(jnp.int32)


# -- the layer ------------------------------------------------------------------


def routing(x: jax.Array, router, *, top_k: int, norm_topk: bool = True, score: str = "softmax",
            route_bias: Optional[jax.Array] = None, route_scale: float = 1.0,
            router_state: Optional[jax.Array] = None, skip: bool = False, rms_eps: float = 1e-5):
    """The router's part of a layer, from ITS input x [B, S, E] (``moe_layer``
    says what the arguments are): (gates [T, k] f32, chosen outputs [T, k],
    the router's statistics).  ``moe_layer`` calls it on the experts' input;
    a layer whose router reads another tensor calls it there and hands the
    result on (``moe_layer(routed=...)``)."""
    # The scopes are parts of obs/spans.PARTS: they name the work for a profile.
    with jax.named_scope("router"):
        T = x.shape[0] * x.shape[1]
        logits = new_state = None
        if isinstance(router, dict):
            logits, new_state = state_router_logits(x, router, router_state, rms_eps)
        logits, probs, gate_vals, gate_idx = route(
            x, router, top_k, norm_topk, score=score, bias=route_bias, scale=route_scale, logits=logits)
        n_exp = logits.shape[-1] - int(skip)
        stats = router_stats(logits, probs, gate_idx, per_choice=score == "sigmoid")
        if skip:
            sent = stats["tokens_per_expert"]
            stats.update(tokens_per_expert=sent[:n_exp], skipped=sent[n_exp])
        if new_state is not None:
            stats["router_state"] = new_state
        return gate_vals.reshape(T, top_k), gate_idx.reshape(T, top_k), stats


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
    score: str = "softmax",
    route_bias: Optional[jax.Array] = None,
    route_scale: float = 1.0,
    router_state: Optional[jax.Array] = None,
    skip: bool = False,
    rms_eps: float = 1e-5,
    held_first: int = 0,
    held_rows_factor: float = HELD_ROWS_FACTOR,
    shared: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    shared_scale: Optional[jax.Array] = None,
    activation: str = "silu",
    routed: Optional[Tuple[jax.Array, jax.Array, Stats]] = None,
    dtype: Any = jnp.bfloat16,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> Tuple[jax.Array, Stats]:
    """MoE feed-forward.

    Args:
        x: [B, S, E] activations.
        router: [E, n_exp] routing weights (kept f32 — routing logits are
            numerically sensitive), over ALL the layer's experts; or the
            subtree of a router with a state (``state_router_logits``), whose
            input state is ``router_state`` ([B, S, R] float32, ``rms_eps``
            its norm's) and whose own comes back in ``stats["router_state"]``.
        skip: the router's LAST output is the choice that takes no expert.
        w_gate/w_up: [held, E, F] (w_gate None: un-gated experts, `activation`
            on `x w_up` — "relu2"); w_down: [held, F, E]: the stacked experts
            this device holds, ``held_first ... held_first + held - 1`` of the
            router's outputs.  ``held == n_exp`` is every expert; fewer
            (dropless path only) is one device's share of an expert-parallel
            layer, and the result then this device's part of the sum.
        capacity_factor: None = dropless (the sorted path); a number = the
            capacity-bound dense dispatch (every expert held).
        score, route_bias, route_scale: the router's form (``route``).
        held_rows_factor: the dropless row buffer of a share, as a multiple
            of its even share of the assignments (``held_rows``).
        shared: (gate [E, Fs], up [E, Fs], down [Fs, E]) of a SwiGLU every
            token passes beside the routed experts (gate None: un-gated, as
            the experts), or None.
        shared_scale: [E, 1], the shared expert's own gate — it adds
            ``sigmoid(x shared_scale) * Shared(x)`` — or None: it adds Shared(x).
        activation: the gated products' ("silu" | "relu") or the un-gated
            one's ("relu2"), the shared expert's too.
        routed: ``routing``'s result where the scores' input is not x (the
            router's arguments above are then unused here), or None: x is
            routed here.

    Returns:
        (y [B, S, E], stats): ``balance`` and ``z`` (scalar f32 auxiliary
        losses), ``tokens_per_expert`` ([n_exp] int32, assignments each of
        the router's experts was sent), with ``skip`` also ``skipped`` (int32,
        assignments to the choice that takes none), ``chosen`` ([B, S, k], the
        outputs each position took), ``assignments`` (int32, B * S * k),
        ``rows_held`` (int32, those that fell on held experts) and
        ``dropped`` (int32, assignments to HELD experts that reached none: 0
        on the dropless path with every expert held, by construction); on the
        dropless path under ReLU (or its square) also ``active_units`` (int32, the (row,
        hidden unit) pairs that are not zero, over the rows an assignment
        landed in — of ``(rows_held - dropped) * F``); under ``shared_scale``
        also ``shared_gate`` (f32, the gate's mean over the positions).
    """
    rules = rules or ShardingRules()
    B, S, E = x.shape
    held = w_up.shape[0]
    T = B * S
    if routed is None:
        routed = routing(x, router, top_k=top_k, norm_topk=norm_topk, score=score, route_bias=route_bias,
                         route_scale=route_scale, router_state=router_state, skip=skip, rms_eps=rms_eps)
    gate_vals, gate_idx, stats = routed
    stats = dict(stats)
    n_exp = stats["tokens_per_expert"].shape[-1]
    with jax.named_scope("experts"):
        xf = x.reshape(T, E)
        if capacity_factor is None:
            if mesh is not None and "expert" in mesh.axis_names and mesh.shape["expert"] > 1:
                raise ValueError(
                    "the dropless MoE path runs on one device (all the experts, or the share it is "
                    "told it holds); a mesh with an 'expert' axis takes the capacity-bound path "
                    "(set moe_capacity_factor)"
                )
            assert 0 <= held_first and held_first + held <= n_exp, (held_first, held, n_exp)
            y, rows_held, dropped, active = _dropless_ffn(
                xf.astype(dtype), gate_vals, gate_idx, w_gate, w_up, w_down,
                n_exp=n_exp, first=held_first, rows_factor=held_rows_factor, mesh=mesh, skip=skip,
                activation=activation,
            )
            if active is not None:
                stats["active_units"] = active
        else:
            if held != n_exp or skip:
                raise ValueError("the capacity-bound path holds every expert (shard them over an 'expert' mesh axis) "
                                 "and has no choice that takes none")
            y, dropped = _capacity_ffn(
                xf, gate_vals, gate_idx, w_gate, w_up, w_down,
                capacity=moe_capacity(T, n_exp, top_k, capacity_factor), dtype=dtype, mesh=mesh, rules=rules,
                activation=activation,
            )
            rows_held = jnp.asarray(T * top_k, jnp.int32)
        stats.update(dropped=dropped, rows_held=rows_held, assignments=jnp.asarray(T * top_k, jnp.int32))
        y = y.reshape(B, S, E).astype(x.dtype)
    if shared is not None:
        with jax.named_scope("shared_expert"):
            s_gate, s_up, s_down = (w if w is None else w.astype(dtype) for w in shared)
            out = hidden_units(activation, s_gate, s_up, lambda w: x @ w) @ s_down
            if shared_scale is not None:
                scale = jax.nn.sigmoid(jnp.einsum("bse,eo->bso", x.astype(jnp.float32), shared_scale.astype(jnp.float32),
                                                  precision=jax.lax.Precision.HIGHEST))
                stats["shared_gate"] = jnp.mean(jax.lax.stop_gradient(scale))
                out = out * scale.astype(out.dtype)
            y = y + out.astype(x.dtype)
    return y, stats


def moe_ffn(x, router, w_gate, w_up, w_down, **kwargs) -> Tuple[jax.Array, jax.Array]:
    """``moe_layer`` returning (y, load-balance loss) alone."""
    y, stats = moe_layer(x, router, w_gate, w_up, w_down, **kwargs)
    return y, stats["balance"]
