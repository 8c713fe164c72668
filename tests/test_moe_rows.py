"""`ops/moe_rows.py`: a token's k rows fetched and summed in one kernel call,
held to XLA's gather and weighting pass (`models/moe.py::_rows_summed`) —
the kernel interpreted on the CPU — and the rule that says who takes it."""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import moe
from torchft_tpu.ops import moe_rows as mr

TOKENS = 32


def _operands(k: int, width: int, fill: bool, weighted: bool, n_rows: int = 96, dtype=jnp.bfloat16):
    """rows [R, E], dest [T, k] with distinct rows — seven in eight of them past
    the end with ``fill``, each its own index, as `_dropless_ffn` gives them —
    and gates or None."""
    rng = np.random.default_rng(k * width + fill)
    n_assign = TOKENS * k
    here = rng.random(n_assign) < 0.125 if fill else np.ones(n_assign, bool)
    dest = n_rows + np.arange(n_assign)
    dest[here] = rng.permutation(max(n_rows, n_assign))[:here.sum()] % n_rows
    rows = jnp.asarray(rng.standard_normal((n_rows, width), np.float32), dtype)
    gates = jnp.asarray(rng.random((TOKENS, k), np.float32)) if weighted else None
    return rows, jnp.asarray(dest.reshape(TOKENS, k), jnp.int32), gates


def _within_a_step(got, want, exact: bool = False) -> bool:
    """Equal, or — where XLA may sum the k terms in another order than j = 0 ..
    k - 1 — no further apart than one step of the result's type at that size."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if exact or np.array_equal(got, want):
        return np.array_equal(got, want)
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 2.0 ** -126))) - 7)  # bfloat16 keeps 8 bits
    return bool(np.all(np.abs(got - want) <= step))


def _in_order(rows, dest, gates):
    """The kernel's arithmetic written out: float32 products added j = 0 .. k - 1, rounded once."""
    rows32 = np.concatenate([np.asarray(rows, np.float32), np.zeros((1, rows.shape[1]), np.float32)])
    at = np.minimum(np.asarray(dest), rows.shape[0])
    acc = np.zeros((dest.shape[0], rows.shape[1]), np.float32)
    for j in range(dest.shape[1]):
        w = np.float32(1.0) if gates is None else np.asarray(gates)[:, j:j + 1]
        acc = acc + w * rows32[at[:, j]]
    return jnp.asarray(acc).astype(rows.dtype)


@pytest.mark.parametrize("weighted", [True, False], ids=["gates", "plain_sum"])
@pytest.mark.parametrize("fill", [True, False], ids=["seven_in_eight_past_the_end", "every_row_exists"])
@pytest.mark.parametrize("width", [2048, 2560])
@pytest.mark.parametrize("k", [1, 6, 8])
def test_the_kernel_is_xlas_gather_and_weighting_pass(k, width, fill, weighted) -> None:
    """`tpuft_moe_rows` (interpreted) against `_take_rows` + `einsum` / `sum`
    — k-major (6) or token-major, a row of 16 or of 20 pieces of 128 columns
    (the 20 padded to 24 inside the call), with assignments that have no row
    reading zeros and with every row there.  The float32 terms are added j = 0
    .. k - 1 and rounded once: bit for bit that sum written out where the
    terms are the rows themselves (under gates the CPU, which interprets the
    kernel here, may fuse a product into its add), and never further than one
    bfloat16 step from XLA's result, whose sum may run in another order."""
    rows, dest, gates = _operands(k, width, fill, weighted)
    want = moe._rows_summed(rows, dest, gates, not fill, False)
    got = mr.moe_rows(rows, dest, gates, interpret=True)
    assert got.shape == want.shape == (TOKENS, width) and got.dtype == want.dtype == rows.dtype
    assert _within_a_step(got, _in_order(rows, dest, gates), exact=not weighted or k == 1)
    assert _within_a_step(got, want, exact=k == 1)
    if fill:  # a token none of whose assignments has a row is exactly zero
        none = np.all(np.asarray(dest) >= rows.shape[0], axis=1)
        assert none.any() and not np.asarray(got, np.float32)[none].any()


@pytest.mark.parametrize("buffers,tokens_a_step", [(1, 8), (2, 8), (1, 32), (2, 16)])
def test_the_blocks_and_buffers_change_no_bit(buffers, tokens_a_step) -> None:
    """One buffer or two, 8 / 16 / 32 tokens a grid step (one step, two, four:
    the next block's rows fetched while this one's are summed): the same
    bits, in float32 rows too."""
    rows, dest, gates = _operands(8, 256, True, True, dtype=jnp.float32)
    want = moe._rows_summed(rows, dest, gates, False, False)
    got = mr.moe_rows(rows, dest, gates, tokens_a_step=tokens_a_step, buffers=buffers, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.fixture
def through_the_kernel(monkeypatch):
    """`models/moe.py` given the kernel (interpreted) whatever the rule says:
    the test steers, the program has no option for it."""
    monkeypatch.setattr(mr, "applies", lambda *a, **kw: True)
    monkeypatch.setattr(mr, "moe_rows", functools.partial(mr.moe_rows, interpret=True))


LAYERS = {  # k, experts, first held, held
    "top6_of_16_with_4_held": (6, 16, 4, 4),
    "top8_of_8_all_held": (8, 8, 0, 8),
    "top1_of_4_with_2_held": (1, 4, 1, 2),
}


def _layer(case: str, width: int = 128, inner: int = 64):
    k, n_exp, first, count = LAYERS[case]
    ks = jax.random.split(jax.random.PRNGKey(k + n_exp), 6)
    xf = jax.random.normal(ks[0], (TOKENS, width), jnp.float32).astype(jnp.bfloat16)
    gate_vals, gate_idx = jax.lax.top_k(jax.nn.softmax(jax.random.normal(ks[1], (TOKENS, n_exp), jnp.float32)), k)
    w = [jax.random.normal(key, shape, jnp.float32) * 0.1
         for key, shape in zip(ks[2:5], [(count, width, inner), (count, width, inner), (count, inner, width)])]
    return xf, gate_vals, gate_idx, w, dict(n_exp=n_exp, first=first, rows_factor=2.0, mesh=None)


@pytest.mark.parametrize("case", list(LAYERS))
def test_a_dropless_layer_through_the_kernel_is_the_xla_layer(case, through_the_kernel, monkeypatch) -> None:
    """`_dropless_ffn` with both of its T * k-row gathers through
    `tpuft_moe_rows` against the same layer on the XLA path: the forward, and
    the gradients of the input (`_rows_bwd`: the plain sum of a token's
    rows' cotangents), the gates and the three matrices (which see `drows`):
    the kernel's arithmetic is the XLA form's, so what the two gathers do not
    reach is bit for bit and what they write is within a bfloat16 step (the
    order of a sum of k terms), bit for bit at k = 1."""
    xf, gate_vals, gate_idx, w, static = _layer(case)

    def loss(xf, gate_vals, *w):
        y, held, dropped, _ = moe._dropless_ffn(xf, gate_vals, gate_idx, *w, **static)
        return jnp.sum(y.astype(jnp.float32) * jnp.cos(jnp.arange(y.size, dtype=jnp.float32).reshape(y.shape))), (y, held, dropped)

    grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    (_, (y, held, dropped)), got = grads(xf, gate_vals, *w)
    monkeypatch.setattr(mr, "applies", lambda *a, **kw: False)
    (_, (want_y, want_held, want_dropped)), want = grads(xf, gate_vals, *w)
    assert int(held) == int(want_held) > 0 and int(dropped) == int(want_dropped) == 0
    k = LAYERS[case][0]
    assert _within_a_step(y, want_y, exact=k == 1) and np.asarray(y, np.float32).any()
    for name, g, wg in zip(("dxf", "dgates", "dw_gate", "dw_up", "dw_down"), got, want):
        assert g.dtype == wg.dtype and np.asarray(g, np.float32).any(), name
        assert _within_a_step(g, wg, exact=k == 1 or name != "dxf"), name


@pytest.mark.parametrize("case", list(LAYERS))
def test_the_two_custom_vjps_through_the_kernel(case, through_the_kernel) -> None:
    """`_tokens_of_rows` (forward, `drows`, `dgates`) and `_rows_of_tokens`
    (`dxf`) with ``fused`` against without, on one row table."""
    xf, gate_vals, gate_idx, _, static = _layer(case)
    k, n_exp, first, count = LAYERS[case]
    n_rows = moe.held_rows(TOKENS * k, n_exp, count, 2.0)
    every = count == n_exp
    rng = np.random.default_rng(7)
    here = (np.asarray(gate_idx) >= first) & (np.asarray(gate_idx) < first + count)
    dest = n_rows + np.arange(TOKENS * k).reshape(TOKENS, k)
    dest[here] = rng.permutation(n_rows)[:here.sum()]
    row_assignment = np.full((n_rows,), TOKENS * k, np.int32)
    row_assignment[dest[here]] = np.flatnonzero(here.reshape(-1))
    dest, row_assignment = jnp.asarray(dest, jnp.int32), jnp.asarray(row_assignment)
    rows = jnp.asarray(rng.standard_normal((n_rows, xf.shape[1]), np.float32), jnp.bfloat16)
    dy = jnp.asarray(rng.standard_normal(xf.shape, np.float32), jnp.bfloat16)

    def combine(fused):
        out, vjp = jax.vjp(lambda r, g: moe._tokens_of_rows(r, g, dest, row_assignment, every, fused), rows, gate_vals)
        return (out, *vjp(dy))

    def dispatch(fused):
        out, vjp = jax.vjp(lambda x: moe._rows_of_tokens(x, row_assignment // k, dest, every, fused), xf)
        return (out, *vjp(rows))

    for fn in (combine, dispatch):
        for got, want in zip(fn(True), fn(False)):
            assert got.dtype == want.dtype and _within_a_step(got, want, exact=k == 1), fn.__name__


RULE = {  # the cells' row buffers: (rows, columns, tokens, k) -> takes the kernel on one TPU device
    "moonlight_100_mib": ((25600, 2048, 16384, 6), False),
    "zaya_68_mib": ((17408, 2048, 16384, 1), False),
    "smallthinker_125_mib": ((25600, 2560, 16384, 6), True),
    "laguna_144_mib": ((36864, 2048, 16384, 8), True),
    "keye_and_sdar_264_mib": ((67584, 2048, 32768, 8), True),
    "olmoe_288_mib": ((73728, 2048, 8192, 8), True),
    "columns_no_whole_pieces": ((73728, 2000, 8192, 8), False),
    "tokens_no_whole_blocks": ((73728, 2048, 8191, 8), False),
}


@pytest.mark.parametrize("case", list(RULE))
def test_who_takes_the_kernel_is_read_from_shapes(case, monkeypatch, caplog) -> None:
    """`applies`: on one TPU device (the gate every kernel asks, here
    answered by the test) the source's bytes decide — above what XLA keeps in
    the fast memory — and shapes the kernel does not tile stay XLA's; off the
    TPU nothing takes it.  Each decision is counted and said once a shape."""
    (n_rows, cols, tokens, k), takes = RULE[case]
    shapes = ((n_rows, cols), jnp.bfloat16, (tokens, k))
    assert mr.applies(*shapes) is False  # the CPU: `kernels_apply` says no
    monkeypatch.setattr(mr._pallas_util, "kernels_apply", lambda mesh=None: True)
    mr._say_once.cache_clear()
    caplog.clear()
    before = dict(mr.counts)
    with caplog.at_level(logging.INFO, logger=mr.logger.name):
        assert mr.applies(*shapes) is takes and mr.applies(*shapes) is takes
    taken = {name: mr.counts[name] - before[name] for name in before}
    assert taken == ({"kernel": 2, "xla": 0} if takes else {"kernel": 0, "xla": 2})
    said = [r.getMessage() for r in caplog.records if "moe_rows" in r.getMessage()]
    assert len(said) == 1 and ("through tpuft_moe_rows" in said[0]) == takes
    mr._say_once.cache_clear()
