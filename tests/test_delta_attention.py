"""The gated delta rule's scan (``ops/delta_attention.py``) on the CPU at small
sizes: the chunk form in XLA against the recurrence position by position —
output and all five gradients, at chunk sizes that do and do not divide the
sequence, at one chunk and at many, at decays down to g = -20 a position (no
inf, no nan) and at g = 0 (the plain delta rule), at beta 0 (the state only
decays) and 1 — and the kernels in ``interpret`` mode against the XLA form at a
few tiles — each for the rule's two shapes of the decay (a number a channel of
the key: Kimi Delta Attention; ONE number a head: Gated DeltaNet) and its two
counts of key heads (as many as value heads; half of them, value head j on key
head j // 2), which `kda` reads off its operands (`RULES`).  Float32 on both
sides unless a case says bfloat16, so what differs is the order of sums."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from torchft_tpu.ops import delta_attention as da  # noqa: E402

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# (positions, chunk, g's lowest value, how g is drawn, beta)
CASES = {
    "chunks_divide": (64, 16, 0.5, "uniform", "sigmoid"),
    "chunks_do_not_divide": (70, 16, 0.5, "uniform", "sigmoid"),
    "one_chunk": (16, 16, 0.5, "uniform", "sigmoid"),
    "one_short_chunk": (9, 16, 1.0, "uniform", "sigmoid"),
    "many_chunks_of_64": (192, 64, 2.0, "uniform", "sigmoid"),
    "g_down_to_minus_20": (64, 16, 20.0, "uniform", "sigmoid"),
    "g_minus_20_everywhere": (48, 16, 20.0, "constant", "sigmoid"),
    "g_zero_the_plain_delta_rule": (64, 16, 0.0, "constant", "sigmoid"),
    "beta_zero_the_state_only_decays": (64, 16, 0.5, "uniform", "zero"),
    "beta_one": (64, 16, 0.5, "uniform", "one"),
}


# the rule by its operands' shapes: (the decay: a number a "channel" of the key | ONE a "head", the key heads: "all" the
# value heads' | "half" of them)
RULES = {"a_decay_a_channel": ("channel", "all"), "a_decay_a_head": ("head", "all"), "half_the_key_heads": ("channel", "half"),
         "a_decay_a_head_and_half_the_key_heads": ("head", "half")}
OWN = "a_decay_a_channel"
# every case under the channel rule; under the other three the cases that cross a chunk's edge with a ragged end, that
# reach g = -20 and that run at the model's chunk
RULE_CASES = [(case, OWN) for case in CASES] + [
    (case, rule) for rule in RULES if rule != OWN for case in ("chunks_do_not_divide", "many_chunks_of_64", "g_down_to_minus_20")]


def _inputs(seq, g_low, g_kind, beta_kind, heads=2, width=16, seed=0, dtype=jnp.float32, rule=OWN):
    decay, keys = RULES[rule]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    key_heads = heads if keys == "all" else heads // 2
    q = unit(jax.random.normal(ks[0], (1, key_heads, seq, width))) * width ** -0.5
    k = unit(jax.random.normal(ks[1], (1, key_heads, seq, width)))
    v = jax.random.normal(ks[2], (1, heads, seq, width))
    of_g = (1, heads, seq, width) if decay == "channel" else (1, heads, seq)
    g = -g_low * (jax.random.uniform(ks[3], of_g) if g_kind == "uniform" else jnp.ones(of_g))
    beta = {"sigmoid": jax.nn.sigmoid(jax.random.normal(ks[4], (1, heads, seq))),
            "zero": jnp.zeros((1, heads, seq)), "one": jnp.ones((1, heads, seq))}[beta_kind]
    weight = jax.random.normal(ks[5], (1, heads, seq, width))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), weight


def _both(fn, args, weight):
    """(o, dq, dk, dv, dg, dbeta) of `fn` under the loss sum(o * weight)."""
    o = fn(*args)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight), argnums=range(5))(*args)
    return (o,) + tuple(grads)


@functools.lru_cache(maxsize=None)
def _chunked_and_loop(case, rule):
    seq, chunk, g_low, g_kind, beta_kind = CASES[case]
    args, weight = _inputs(seq, g_low, g_kind, beta_kind, rule=rule)
    got = _both(lambda *a: da.kda(*a, chunk=chunk), args, weight)
    want = _both(lambda *a: da.kda_loop(*a)[0], args, weight)
    return got, want


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case,rule", RULE_CASES, ids=[f"{case}-{rule}" for case, rule in RULE_CASES])
def test_the_chunk_form_is_the_recurrence(case, rule, name) -> None:
    """5e-5 of the largest entry: float32 sums in another order (the chunk
    form re-associates products of up to `chunk` decays and a triangular
    solve by squarings); nothing else differs — for a decay a head as for one
    a channel (dg then one number a head and position), for shared key heads as
    for a key head a value head (dq and dk then the sums over a key head's
    value heads)."""
    got, want = _chunked_and_loop(case, rule)
    assert [a.shape for a in got] == [a.shape for a in want]
    a, b = np.asarray(got[NAMES.index(name)], np.float64), np.asarray(want[NAMES.index(name)], np.float64)
    assert np.all(np.isfinite(a)), "an exponent left its bounds"
    scale = max(float(np.max(np.abs(b))), 1e-6)
    assert float(np.max(np.abs(a - b))) <= 5e-5 * scale, (case, name, float(np.max(np.abs(a - b))), scale)
    if case == "beta_zero_the_state_only_decays" and name == "o":
        assert float(np.max(np.abs(b))) == 0.0  # nothing is ever written into the state


# (heads, heads a grid step): set through the kernels' private keyword, or None = what `kda` reads from the shape
# (6 heads -> all 6 in a step, 5 -> 5: the largest divisor not above HEADS_PER_STEP).  Three chunks of 64, so that a
# chunk's output depends on the state its own head carried through the two before: a head that read its neighbour's row
# of the scratch would fail from the second chunk on
KERNEL_CASES = {"1_of_4": (4, 1), "2_of_4": (4, 2), "4_of_4": (4, 4), "6_from_the_shape": (6, None),
                "5_from_the_shape": (5, None)}
KERNEL_NAMES = NAMES + ("states",)
GATED_DELTA_NET = "a_decay_a_head_and_half_the_key_heads"  # Gated DeltaNet's rule, through `kda` itself: 4 value heads on 2 key heads


def _kernel_inputs(dtype_name, heads, rule=OWN):
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    args, weight = _inputs(192, 1.0, "uniform", "sigmoid", heads=heads, width=128, seed=3, dtype=dtype, rule=rule)
    return args, weight, [a.reshape(a.shape[1], 192, *a.shape[3:]) for a in args]


@functools.lru_cache(maxsize=None)
def _xla(dtype_name, heads):
    args, weight, flat = _kernel_inputs(dtype_name, heads)
    return _both(lambda *a: da.kda(*a), args, weight) + (da._forward_xla(*flat, da.CHUNK, True)[1],)


@functools.lru_cache(maxsize=None)
def _interpreted(dtype_name, case):
    heads, heads_per_step = KERNEL_CASES[case]
    args, weight, flat = _kernel_inputs(dtype_name, heads)
    kw = dict(interpret=True, heads_per_step=heads_per_step)
    _, states = da._fwd_pallas(*flat, da.CHUNK, True, **kw)
    if heads_per_step is None:
        assert da._heads_per_step(heads) == heads
        return _both(lambda *a: da.kda(*a, interpret=True), args, weight) + (states,)
    o = da._fwd_pallas(*flat, da.CHUNK, False, **kw)[0]
    grads = da._bwd_pallas(*flat, states, weight[0].astype(o.dtype), da.CHUNK, **kw)
    return tuple(a[None] for a in (o,) + tuple(grads)) + (states,)


@pytest.mark.parametrize("name", KERNEL_NAMES)
@pytest.mark.parametrize("case", list(KERNEL_CASES))
@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
def test_the_kernels_in_interpret_mode_are_the_xla_form(dtype_name, case, name) -> None:
    """The kernels run the XLA form's two chunk functions on each head's
    blocks, however many heads a grid step carries, so the two agree to the
    last bit or two of float32 (the carried state and the chunks' order are
    the same); in bfloat16 the outputs are rounded alike."""
    got, want = _interpreted(dtype_name, case), _xla(dtype_name, KERNEL_CASES[case][0])
    a, b = np.asarray(got[KERNEL_NAMES.index(name)], np.float64), np.asarray(want[KERNEL_NAMES.index(name)], np.float64)
    assert a.shape == b.shape and np.all(np.isfinite(a))
    assert float(np.max(np.abs(a - b))) <= (1e-5 if dtype_name == "float32" else 2e-2) * float(np.max(np.abs(b)))


@functools.lru_cache(maxsize=None)
def _gated_delta_net(interpret: bool):
    args, weight, _ = _kernel_inputs("float32", 4, GATED_DELTA_NET)
    return _both(lambda *a: da.kda(*a, interpret=interpret), args, weight)


@pytest.mark.parametrize("name", NAMES)
def test_the_kernels_in_interpret_mode_are_the_xla_form_for_a_decay_a_head_under_shared_key_heads(name) -> None:
    """Gated DeltaNet's operands — g [B, H, S], q and k at half the value
    heads — through `kda` with the kernels (``interpret``) and with the XLA
    form: the output and all five gradients, dq and dk a KEY head's and dg one
    number a head and position, agree as the channel rule's do."""
    got, want = _gated_delta_net(True), _gated_delta_net(False)
    a, b = np.asarray(got[NAMES.index(name)], np.float64), np.asarray(want[NAMES.index(name)], np.float64)
    assert a.shape == b.shape == {"o": (1, 4, 192, 128), "dq": (1, 2, 192, 128), "dk": (1, 2, 192, 128), "dv": (1, 4, 192, 128),
                                  "dg": (1, 4, 192), "dbeta": (1, 4, 192)}[name]
    assert np.all(np.isfinite(a)) and float(np.max(np.abs(a - b))) <= 1e-5 * float(np.max(np.abs(b)))


@pytest.mark.parametrize("bh, most, heads", [(32, 8, 8), (32, 4, 4), (4, 8, 4), (6, 4, 3), (6, 8, 6), (5, 4, 1), (7, 8, 7),
                                             (1, 8, 1), (48, 32, 24)])
def test_the_heads_of_a_grid_step_divide_the_heads(bh, most, heads) -> None:
    assert da._heads_per_step(bh, most) == heads


@pytest.mark.parametrize("chunk", [2, 16, 64])
def test_the_levels_cover_each_pair_once_and_sum_only_what_lies_between(chunk) -> None:
    """Every pair (r, i), i < r, belongs to exactly one level; a level's row
    of sums adds g over (b, r] for a row above its boundary b and over (r, b]
    for a row up to it — never over a range that would make an exponent
    positive, and the two ranges of a pair join to (i, r]."""
    c = da._constants(chunk)
    n = da._levels(chunk)
    masks = c["masks"].reshape(n, chunk, chunk)
    assert np.array_equal(masks.sum(axis=0), np.tril(np.ones((chunk, chunk)), -1))
    sums = c["sums"].reshape(n + 2, chunk, chunk)
    assert np.array_equal(sums[0], np.tril(np.ones((chunk, chunk)))) and np.array_equal(sums[-1], np.triu(np.ones((chunk, chunk)), 1))
    for level in range(n):
        for r, i in zip(*np.nonzero(masks[level])):
            between = np.zeros(chunk)
            between[i + 1:r + 1] = 1
            assert np.array_equal(sums[1 + level][r] + sums[1 + level][i], between)


def test_the_benchmark_counts_the_chunk_the_program_runs() -> None:
    from benchmark.spec import Benchmark

    assert Benchmark(ROOT).flops("tpuft_kda").CHUNK == Benchmark(ROOT).flops("tpuft_gdn").CHUNK == da.CHUNK == 64


@pytest.mark.parametrize("rule", list(RULES))
def test_a_sequence_is_padded_with_positions_that_write_nothing(rule) -> None:
    """A chunk that does not divide the sequence: the padded call's outputs up
    to the sequence's end are the outputs of the longer sequence whose tail
    writes nothing, and the gradients of what was cut away are not asked for."""
    (q, k, v, g, beta), _ = _inputs(40, 0.5, "uniform", "sigmoid", rule=rule)
    short = da.kda(q[:, :, :37], k[:, :, :37], v[:, :, :37], g[:, :, :37], beta[:, :, :37], chunk=8)
    whole = da.kda(q, k, v, g, beta, chunk=8)
    assert short.shape == (1, 2, 37, 16)
    np.testing.assert_allclose(np.asarray(short), np.asarray(whole[:, :, :37]), rtol=1e-6, atol=1e-7)
