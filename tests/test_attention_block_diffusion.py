"""The block-diffusion walk of the flash kernels (`tpuft_bd_*`,
`flash_attention(..., block_length=b)`): a stream of a noised and a clean copy
of L tokens under the three-part block mask.  The rule pair by pair, the walk's
tables and their ends against it, the kernels in interpret mode against the XLA
form, and the grids at the cell's lengths.  Apart from
`tests/test_attention_walks.py`, which is the suite's longest file."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import attention_forms as forms
from test_ops import pallas_call_grids
from torchft_tpu.ops import attention as fa


def _rule(L: int, b: int) -> np.ndarray:
    """bool [2L, 2L], the published rule written pair by pair."""
    sees = np.zeros((2 * L, 2 * L), bool)
    for i in range(2 * L):
        for j in range(2 * L):
            block_i, block_j = (i % L) // b, (j % L) // b
            if i < L:  # a noised query: its own block's noised keys, the clean keys of the blocks before
                sees[i, j] = (block_j == block_i) if j < L else (block_j < block_i)
            else:      # a clean query: the clean keys up to its own block, no noised key
                sees[i, j] = j >= L and block_j <= block_i
    return sees


@pytest.mark.parametrize("L,b", [(24, 4), (64, 32), (36, 6), (8, 8)])
def test_the_xla_form_s_mask_is_the_rule_pair_by_pair(L, b) -> None:
    sees = _rule(L, b)
    assert np.array_equal(np.asarray(fa._visible(2 * L, 2 * L, None, b)), sees)
    assert sees.sum() == L * L + L * b and not sees[L:, :L].any()          # a quarter of the pairs and a block's worth
    assert all(sees[i, i] for i in range(2 * L))                            # every query sees itself
    assert (sees[:L, :L] == sees[:L, :L].T).all()                           # inside a block attention runs both ways


@pytest.mark.parametrize("L,b,block", [(64, 4, 16), (48, 4, 32), (96, 32, 64), (24, 4, 16), (64, 32, 32), (32, 8, 64),
                                       (768, 32, 512), (768, 4, 512), (96, 12, 64), (2048, 4, 512)])
def test_the_walk_has_a_step_for_every_tile_that_holds_a_visible_pair_and_no_other(L, b, block) -> None:
    """Row by row and column by column, tiles that straddle the halves (L no
    multiple of the tile) and blocks that do not divide it among them; and the
    ends the kernels start, assign and emit at are the tables' own."""
    n = 2 * L // block
    holds_a_pair = np.asarray(fa._visible(2 * L, 2 * L, None, b)).reshape(n, block, n, block).any(axis=(1, 3))
    for kv_major in (False, True):
        walk = fa._Walk(True, 2 * L, 2 * L, block, block, kv_major=kv_major, block_length=b)
        rows, cols = (np.asarray(x) for x in walk.tables)
        visited = np.zeros((n, n), bool)
        visited[rows, cols] = True
        assert len(rows) == holds_a_pair.sum() and (visited == holds_a_pair).all()
        major, minor = (cols, rows) if kv_major else (rows, cols)
        assert (np.diff(major) >= 0).all() and (np.diff(minor)[np.diff(major) == 0] > 0).all()
        for i in range(n):
            in_row, in_col = cols[rows == i], rows[cols == i]
            assert (int(walk.first_k(i)), int(walk.last_k(i))) == (in_row.min(), in_row.max())
            assert (int(walk.first_q(i)), int(walk.last_q(i))) == (in_col.min(), in_col.max())
    if L % block == 0 and block % b == 0 and block > b:  # whole tiles a half, blocks a tile: the noised diagonal, two triangles, a dead quadrant
        half = L // block
        assert holds_a_pair.sum() == half + half * (half + 1) and not holds_a_pair[half:, :half].any()
    # the pairs its steps cover are the rule's, all of them; a walk short of one live tile covers fewer
    walk = fa._Walk(True, 2 * L, 2 * L, block, block, block_length=b)
    assert walk.pairs_seen() == L * L + L * b
    short = fa._Walk(True, 2 * L, 2 * L, block, block, block_length=b)
    short.__dict__["tables"] = tuple(t[1:] for t in walk.tables)
    in_the_first = np.asarray(fa._visible(2 * L, 2 * L, None, b))[:block, :block].sum()
    assert short.pairs_seen() == L * L + L * b - in_the_first < L * L + L * b
    assert fa.bd_pairs_walked(2 * L, b) == L * L + L * b  # at the tiles the kernels take, or one where they do not apply


@pytest.mark.parametrize("b", [4, 32])
def test_block_diffusion_kernels_match_the_xla_form(b) -> None:
    """`tpuft_bd_fwd` and `tpuft_bd_bwd_dkdv_dq` in interpret mode at L = 768,
    no multiple of the 512 tile — the middle tile holds noised and clean rows
    — with two query heads on one KV head: out, lse, dq, dk and dv."""
    L, heads, d = 768, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(b), 4)
    q, g = (jax.random.normal(k, (heads, 2 * L, d), jnp.float32) for k in (ks[0], ks[3]))
    k, v = (jax.random.normal(key, (1, 2 * L, d), jnp.float32) for key in ks[1:3])
    scale = d ** -0.5
    o, lse = forms.fwd(q, k, v, scale, True, interpret=True, kv_group=heads, block_length=b)
    wide = lambda t: jnp.repeat(t, heads, axis=0)  # noqa: E731
    want_o, want_lse = fa._fa_reference(q, wide(k), wide(v), scale, True, None, b)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), atol=4e-6)
    dq, dk, dv = forms.bwd(q, k, v, o, lse, g, scale, True, interpret=True, kv_group=heads, block_length=b)
    want = fa._fa_bwd_xla(q, wide(k), wide(v), want_o, want_lse, g, scale, True, None, b)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(want[0]), atol=5e-6)
    for got, ref in ((dk, want[1]), (dv, want[2])):  # a query head each: the KV head's is their sum
        np.testing.assert_allclose(np.asarray(got.sum(0)), np.asarray(ref.sum(0)), atol=1e-5)
    # and not what a causal call over the 2 L positions gives
    causal, _ = fa._fa_reference(q, wide(k), wide(v), scale, True)
    assert float(jnp.max(jnp.abs(causal - want_o))) > 0.1


def test_flash_attention_under_a_block_length_is_the_rule_and_differentiates() -> None:
    """The public call on the CPU (the XLA form): [B, 2L, heads, d] in and
    out, grouped queries, against a dense softmax under `_rule`; its gradient
    against the dense form's."""
    L, b, H, KV, d = 24, 4, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 2 * L, H, d), jnp.float32)
    k, v = (jax.random.normal(key, (2, 2 * L, KV, d), jnp.float32) for key in ks[1:])
    sees = jnp.asarray(_rule(L, b))

    def dense(q, k, v):
        kk, vv = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
        s = jnp.where(sees, jnp.einsum("bqhd,bkhd->bhqk", q, kk) * d ** -0.5, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vv)

    ours = functools.partial(fa.flash_attention, block_length=b)
    np.testing.assert_allclose(np.asarray(ours(q, k, v)), np.asarray(dense(q, k, v)), atol=2e-6)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))  # noqa: E731
    for got, want in zip(jax.grad(loss(ours), (0, 1, 2))(q, k, v), jax.grad(loss(dense), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    with pytest.raises(AssertionError, match="two halves of whole blocks"):
        fa.flash_attention(q, k, v, block_length=5)


def test_the_grids_at_the_cell_s_lengths() -> None:
    """16,384 data tokens in blocks of 4, 32 query heads on 4 KV heads of 128:
    the stream's 64 x 64 tiles of 512 hold 4,096 pairs of tiles, of which the
    walk has a step for 1,088 — 32 on the noised diagonal and two triangles of
    528 — where a causal call over 2 L would take 2,080; eight heads a step
    forward, two backward (a 16 MiB dq row a head), under the names a trace
    tells from `tpuft_fa_*`."""
    L, b, H, KV = 16384, 4, 32, 4
    q = jax.ShapeDtypeStruct((H, 2 * L, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((KV, 2 * L, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((H, 2 * L), jnp.float32)
    fwd = functools.partial(forms.fwd, scale=0.088, causal=True, kv_group=H // KV, block_length=b)
    bwd = functools.partial(forms.bwd, scale=0.088, causal=True, kv_group=H // KV, block_length=b)
    tiles = 32 + 32 * 33
    assert tiles == 1088 and len(fa._Walk(True, 2 * L, 2 * L, 512, 512).tables[0]) == 2080
    assert pallas_call_grids(fwd, q, kv, kv) == {"tpuft_bd_fwd": (H // 8, tiles)}
    assert pallas_call_grids(bwd, q, kv, kv, q, lse, q) == {"tpuft_bd_bwd_dkdv_dq": (H // 2, tiles)}
