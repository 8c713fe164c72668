"""`ops/ssm_mix.py` — the `tpuft_ssmmix_*` kernels around Mamba-2's scan — on
the CPU (``interpret``), against the XLA halves they stand for
(`models/mamba.py::_before`, `_after`): forward values and every gradient, the
small leaves' included; the convolution's rows across a tile's edge and at the
sequence's start, both directions; a head of 64 columns under ITS dt; which
path `mamba2_mixer` takes; and the benchmark's count of the part and its reader
(`benchmark/flops/tpuft_ssmmix.py`, `benchmark/layer_metrics/ssm_mix_roofline.py`).
Nothing is timed."""

import contextlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.spec import Benchmark  # noqa: E402
from torchft_tpu.models import LayerKind, TransformerConfig, init_params, mamba  # noqa: E402
from torchft_tpu.ops import _pallas_util, ssd, ssm_mix  # noqa: E402

F32 = jnp.float32
BEFORE_LEAVES, AFTER_LEAVES = mamba._SMALL[:4], mamba._SMALL[4:]
KERNELS = ("tpuft_ssmmix_fwd", "tpuft_ssmmix_bwd", "tpuft_ssmmix_out_fwd", "tpuft_ssmmix_out_bwd")


def _cfg(heads: int, p: int, groups: int, state: int, **more):
    kind = LayerKind("layers", False, heads, 1e4, mixer="mamba2", feed_forward=False)
    return kind, TransformerConfig(vocab_size=32, d_model=24, n_layers=1, n_heads=heads, n_kv_heads=heads, d_ff=16,
                                   ssm_head_dim=p, ssm_groups=groups, ssm_state=state, rms_eps=1e-5, pattern=(kind,),
                                   **{"dtype": jnp.float32, "ssm_chunk": 16, **more})


def _inputs(seed: int, batch: int, seq: int, heads: int, p: int, groups: int, state: int, dtype):
    """(u, dt_raw), the five cotangents of the first half, (y, x, z, dout), the small leaves."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 20)
    inner, bc = heads * p, groups * state
    channels = inner + 2 * bc
    normal = lambda i, width, dt=dtype: jax.random.normal(ks[i], (batch, seq, width)).astype(dt)   # noqa: E731
    w = {"ssm_conv": 0.5 * jax.random.normal(ks[0], (channels, 4)), "ssm_conv_bias": 0.3 * jax.random.normal(ks[1], (channels,)),
         "dt_bias": jax.random.normal(ks[2], (heads,)), "A_log": 0.3 * jax.random.normal(ks[3], (heads,)),
         "ssm_D": 1 + 0.3 * jax.random.normal(ks[4], (heads,)), "ssm_norm": 1 + 0.3 * jax.random.normal(ks[5], (inner,))}
    cots = [normal(8, inner, F32), normal(9, inner, F32), normal(10, bc, F32), normal(11, bc, F32), normal(12, heads, F32)]
    return (normal(6, channels), normal(7, heads)), cots, tuple(normal(13 + i, inner) for i in range(4)), w


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30), np.abs(got - want).max() / np.abs(want).max()


def _before_kernels(p, tile):
    return lambda u, dt_raw, w: ssm_mix.before(u, dt_raw, w["ssm_conv"].T, w["ssm_conv_bias"], w["dt_bias"], w["A_log"],
                                               head_dim=p, tile=tile, interpret=True)


CASES = [  # batch, positions, heads of 64, groups, rows a grid step, type, tolerance
    (2, 96, 8, 2, 32, jnp.float32, 2e-6),    # two lane tiles a column block: two blocks of x, one of B, one of C; three tiles long
    (2, 96, 8, 2, 16, jnp.float32, 2e-6),    # the smallest tile: a step's rows are the halo's
    (1, 64, 4, 2, 64, jnp.float32, 2e-6),    # one tile the sequence: no tile before, none after
    (1, 32, 16, 4, 32, jnp.float32, 2e-6),   # four lane tiles a column block, as the cell's: two blocks of x
    (2, 128, 8, 2, 32, jnp.bfloat16, 1e-2),
]


@pytest.mark.parametrize("batch,seq,heads,groups,tile,dtype,tol", CASES)
def test_before_the_scan_against_the_xla_half(batch, seq, heads, groups, tile, dtype, tol) -> None:
    """x, dt * x, B, C and the log decay, and the gradients of u, dt_raw, the
    taps, the convolution's bias, `dt_bias` and `A_log`."""
    p, state = 64, 128 * groups
    (u, dt_raw), cots, _, w = _inputs(1, batch, seq, heads, p, groups, 128, dtype)
    _, cfg = _cfg(heads, p, groups, 128)
    assert ssm_mix.tile_of(seq, tile) == tile and u.shape[2] == heads * p + 2 * state
    assert ssm_mix._lanes(heads * p, state) == (4 if groups == 4 else 2)

    def loss(fn):
        def inner(u, dt_raw, w):
            outs = fn(u, dt_raw, w)
            return sum(jnp.sum(x.astype(F32) * c) for x, c in zip(outs, cots)), outs
        return jax.value_and_grad(inner, argnums=range(3), has_aux=True)

    (_, got), got_grads = loss(_before_kernels(p, tile))(u, dt_raw, w)
    (_, want), want_grads = loss(lambda u, dt_raw, w: mamba._before(u, dt_raw, w, cfg, heads)[:5])(u, dt_raw, w)
    for x, y in zip(got, want):
        assert x.dtype == y.dtype and x.shape == y.shape
        _close(x, y, tol)
    assert got[4].dtype == F32 and [x.shape[2] for x in got] == [heads * p, heads * p, state, state, heads]
    for x, y in zip(got_grads[:2], want_grads[:2]):
        assert x.dtype == dtype
        _close(x, y, tol)
    for name in BEFORE_LEAVES:
        _close(got_grads[2][name], want_grads[2][name], max(tol, 1e-5) if dtype == jnp.float32 else 2e-3)
    for name in AFTER_LEAVES:
        assert not np.asarray(got_grads[2][name]).any()


@pytest.mark.parametrize("batch,seq,heads,groups,tile,dtype,tol", CASES)
def test_after_the_scan_against_the_xla_half(batch, seq, heads, groups, tile, dtype, tol) -> None:
    """The skip, the gate and the group norm, and the gradients of y, x, the
    gate's projection, `ssm_D` and `ssm_norm`."""
    p = 64
    _, _, (y, x, z, dout), w = _inputs(2, batch, seq, heads, p, groups, 128, dtype)
    _, cfg = _cfg(heads, p, groups, 128)
    kernels = lambda y, x, z, w: ssm_mix.after(  # noqa: E731
        y, x, z, w["ssm_D"], w["ssm_norm"], groups=groups, eps=1e-5, tile=tile, interpret=True)
    xla = lambda y, x, z, w: mamba._after(y, x, z, w, cfg, heads)  # noqa: E731
    loss = lambda fn: jax.value_and_grad(  # noqa: E731
        lambda *xs: (lambda out: (jnp.sum(out.astype(F32) * dout.astype(F32)), out))(fn(*xs)), argnums=range(4), has_aux=True)
    (_, got), got_grads = loss(kernels)(y, x, z, w)
    (_, want), want_grads = loss(xla)(y, x, z, w)
    assert got.dtype == dtype and got.shape == y.shape
    _close(got, want, tol)
    for a, b in zip(got_grads[:3], want_grads[:3]):
        assert a.dtype == dtype
        _close(a, b, tol)
    for name in AFTER_LEAVES:
        _close(got_grads[3][name], want_grads[3][name], max(tol, 1e-5) if dtype == jnp.float32 else 2e-3)
    for name in BEFORE_LEAVES:
        assert not np.asarray(got_grads[3][name]).any()


@pytest.mark.parametrize("tile", [16, 32, 64])
@pytest.mark.parametrize("back", [1, 2, 3])
def test_the_convolution_s_rows_cross_a_tile_s_edge_and_stop_at_position_0(tile, back) -> None:
    """With the one tap `back` positions back at 1, the others at 0 and no
    bias, x, B and C are SiLU of the row `back` before: written out, position
    by position, over a sequence of four tiles — zeros before position 0, the
    tile before's last rows at every edge — and the gradient the mirrored
    move: the cotangent `back` rows LATER times SiLU's slope, nothing after the
    sequence's end."""
    heads, p, groups, state = 8, 64, 2, 128
    seq, inner = 4 * tile, heads * p
    (u, dt_raw), (dx, _, db, dc, _), _, w = _inputs(3, 1, seq, heads, p, groups, state, jnp.float32)
    taps = jnp.zeros((4, u.shape[2])).at[3 - back].set(1.0)
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731

    def parts(u):
        x, _, bm, cm, _ = ssm_mix.before(u, dt_raw, taps, jnp.zeros(u.shape[2]), w["dt_bias"], w["A_log"], head_dim=p,
                                         tile=tile, interpret=True)
        return jnp.concatenate([x, bm, cm], axis=-1)

    got, pull = jax.vjp(parts, u)
    z = np.asarray(u, np.float64)[0]                                               # [S, channels]
    shifted = np.concatenate([np.zeros((back, z.shape[1])), z[:seq - back]])
    np.testing.assert_allclose(np.asarray(got[0]), silu(shifted), rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[0, :back]).any()                                     # SiLU(0) = 0 before position 0
    for edge in range(tile, seq, tile):                                            # the first row of a tile reads the tile before
        np.testing.assert_allclose(np.asarray(got[0, edge]), silu(z[edge - back]), rtol=1e-5, atol=1e-6)
    cot = jnp.concatenate([dx, db, dc], axis=-1)
    (du,) = pull(cot)
    sig = 1 / (1 + np.exp(-shifted))
    dconv = np.asarray(cot, np.float64)[0] * sig * (1 + shifted * (1 - sig))
    want = np.concatenate([dconv[back:], np.zeros((back, z.shape[1]))])
    np.testing.assert_allclose(np.asarray(du[0]), want, rtol=1e-5, atol=1e-6)
    assert not np.asarray(du[0, seq - back:]).any()                                # no position after the end
    for edge in range(tile, seq, tile):                                            # the last row of a tile reads the tile after
        np.testing.assert_allclose(np.asarray(du[0, edge - 1]), dconv[edge - 1 + back], rtol=1e-5, atol=1e-6)
    assert inner + 2 * groups * state == z.shape[1]


@pytest.mark.parametrize("heads,p", [(8, 64), (16, 32), (4, 128)])
def test_a_head_s_columns_get_its_dt(heads, p) -> None:
    """Two heads of 64 columns share a lane tile (four of 32; one of 128 fills
    it) and dt differs between them: dt * x is x times ITS head's dt bit for
    bit — the product with the 0/1 matrix adds nothing to the float32 dt — and
    dt_raw's gradient is the sum over the head's own columns."""
    groups, state, seq = 2, 128, 32
    (u, dt_raw), (_, dxdt, _, _, _), _, w = _inputs(4, 2, seq, heads, p, groups, state, jnp.float32)
    dt_raw = dt_raw + jnp.arange(heads, dtype=F32)                                 # every head its own step
    zeros = jnp.zeros(u.shape[2])

    def xdt_of(dt_raw):
        x, xdt, _, _, _ = ssm_mix.before(u, dt_raw, w["ssm_conv"].T, zeros, w["dt_bias"], w["A_log"], head_dim=p, tile=16,
                                         interpret=True)
        return xdt, x

    xdt, pull, x = jax.vjp(xdt_of, dt_raw, has_aux=True)
    dt = np.asarray(jax.nn.softplus(dt_raw + w["dt_bias"]))                        # [B, S, H]
    assert len({float(v) for v in dt[0, 0]}) == heads
    assert np.array_equal(np.asarray(xdt), np.asarray(x) * np.repeat(dt, p, axis=-1))
    (got,) = pull(dxdt)
    by_head = (np.asarray(dxdt, np.float64) * np.asarray(x, np.float64)).reshape(2, seq, heads, p).sum(-1)
    want = by_head / (1 + np.exp(-np.asarray(dt_raw + w["dt_bias"], np.float64)))  # softplus' slope
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=1e-6)


def test_rows_a_grid_step_and_columns_a_block() -> None:
    assert ssm_mix.tile_of(16_384) == 1024 and ssm_mix._after_tile(16_384, 512, None) == 1024
    assert ssm_mix._after_tile(16_384, 2048, None) == 256 and ssm_mix._after_tile(96, 128, None) == 32
    assert ssm_mix._lanes(4096, 1024) == 4 and ssm_mix._lanes(512, 256) == 2 and ssm_mix._lanes(384, 128) == 1
    e, et = ssm_mix._spread_matrices(64, 64, 4)
    assert e.shape == (8, 4, 128, 128) and et.shape == e.shape and e.dtype == jnp.bfloat16
    picks = np.asarray(e, np.float32)
    assert (picks.sum(axis=2) == 1).all() and picks[:, :, 64:].sum() == 0           # a head a column; the padding picks nothing
    assert picks[3, 2, 3 * 8 + 2 * 2, :64].all() and picks[3, 2, 3 * 8 + 2 * 2 + 1, 64:].all()
    for name in KERNELS:
        assert "tpuft_ssd_" not in name                                            # the benchmark books that to the scan


def _mixer(heads: int, p: int, seq: int, groups: int = 2, state: int = 128, **more):
    kind, cfg = _cfg(heads, p, groups, state, **more)
    w = jax.tree.map(lambda x: x[0], init_params(jax.random.PRNGKey(3), cfg)["layers"])
    rng = np.random.default_rng(3)
    inner = heads * p
    w = dict(w, ssm_conv_bias=jnp.asarray(0.3 * rng.standard_normal(inner + 2 * groups * state), jnp.float32),
             ssm_D=jnp.asarray(1 + 0.3 * rng.standard_normal(heads), jnp.float32),
             ssm_norm=jnp.asarray(1 + 0.3 * rng.standard_normal(inner), jnp.float32))
    return kind, cfg, w, jnp.asarray(rng.standard_normal((2, seq, 24)), jnp.float32)


def _interpreted(monkeypatch):
    """`mamba2_mixer` on the kernels' path on the CPU: the backend reads as a
    TPU and the kernels (the scan's too) run interpreted; returns the calls made."""
    calls = []
    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    monkeypatch.setattr(ssd.ssd, "__kwdefaults__", dict(ssd.ssd.__kwdefaults__, interpret=True))
    for name in ("before", "after"):
        real = getattr(ssm_mix, name)
        monkeypatch.setattr(ssm_mix, name, lambda *a, _real=real, _name=name, **k: (
            calls.append(_name), _real(*a, **k, interpret=True))[1])
    return calls


def test_the_mixer_through_the_kernels_is_the_mixer_through_xla(monkeypatch) -> None:
    """Heads of 64 in groups of whole lane tiles on a TPU's one-device
    program: both halves go through the kernels; output, the decay's mean and
    every weight's gradient agree with the XLA halves' (the scan between them
    is the same chunk form)."""
    kind, cfg, w, h = _mixer(8, 64, 32)

    def run(h, w):
        out, decay = mamba.mamba2_mixer(cfg, kind, None, h, w)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size, dtype=F32).reshape(out.shape))), (out, decay)

    (_, (want, want_decay)), want_grads = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(h, w)
    calls = _interpreted(monkeypatch)
    (_, (got, got_decay)), got_grads = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(h, w)
    assert calls == ["before", "after"]
    _close(got, want, 1e-5)
    assert float(got_decay) == pytest.approx(float(want_decay), rel=1e-6)
    for x, y in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        _close(x, y, 5e-5)


@pytest.mark.parametrize("why", ["not_a_tpu", "a_mesh_of_two", "heads_of_48", "half_a_lane_tile_a_group", "a_state_of_64",
                                 "no_tile", "kernel_of_3"])
def test_the_xla_halves_where_the_kernels_do_not_apply(why, monkeypatch) -> None:
    """Off the TPU, under a mesh of more than one device, at a head width
    that does not divide the lanes, at a group that is no whole lane tile, at a
    state that is none, at a sequence no tile divides, at another
    convolution: `mamba2_mixer` calls no kernel and gives what `_before`, the
    scan and `_after` give (a checkpoint each), bit for bit."""
    heads, p, seq, state, more = 8, 64, 32, 128, {}
    if why == "heads_of_48":
        p = 48
    elif why == "half_a_lane_tile_a_group":
        heads = 2
    elif why == "a_state_of_64":
        state = 64
    elif why == "no_tile":
        seq = 24
    elif why == "kernel_of_3":
        more = {"ssm_conv": 3}
    kind, cfg, w, h = _mixer(heads, p, seq, state=state, **more)
    mesh = None
    if why != "not_a_tpu":
        calls = _interpreted(monkeypatch)
    else:
        calls = []
        monkeypatch.setattr(ssm_mix, "before", lambda *a, **k: calls.append("before"))
    if why == "a_mesh_of_two":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
        assert mesh.size == 2
    with pytest.warns(UserWarning, match="pallas kernels are off") if why == "a_mesh_of_two" else contextlib.nullcontext():
        assert not ssm_mix.applies(seq, p, heads // 2, state, cfg.ssm_conv, mesh)
        got, decay = mamba.mamba2_mixer(cfg, kind, mesh, h, w)
    assert calls == []
    if why not in ("not_a_tpu", "a_mesh_of_two"):                                   # the one thing wrong is what is named
        assert ssm_mix.applies(32, 64, 4, 128, 4, None)
    dt_ = cfg.dtype
    inner, channels = mamba.widths(cfg, heads)
    w_in = w["ssm_in"].astype(dt_)
    z, u, dt_raw = h @ w_in[:, :inner], h @ w_in[:, inner:inner + channels], h @ w_in[:, inner + channels:]
    small = {name: w[name] for name in mamba._SMALL}
    x, xdt, bm, cm, la, want_decay = jax.checkpoint(lambda *a: mamba._before(*a, cfg, heads))(u, dt_raw, small)
    y = ssd.ssd(xdt, bm, cm, la, head_dim=p, groups=2, chunk=cfg.ssm_chunk, mesh=mesh)
    want = jax.checkpoint(lambda *a: mamba._after(*a, cfg, heads))(y, x, z, small) @ w["ssm_out"].astype(dt_)
    assert np.array_equal(np.asarray(got), np.asarray(want)) and float(decay) == float(want_decay)


# -- the benchmark's count and reader ------------------------------------------------------------


def test_the_part_s_bytes_from_shapes_and_its_reader(monkeypatch) -> None:
    """ISSUE 57's table at the cell's shapes: 2 x (4 + 4) + 5.5 + 7 arrays of
    [16,384, 4,096] bf16 a block and the [16,384, 64] arrays beside them, four
    blocks; bound by HBM by the counts; the reader sets that against the
    `tpuft_ssmmix_*` instructions' time and the scan's kernels are not among
    them; None where no such kernel ran."""
    from benchmark import device_parts, program_spans

    bench = Benchmark(ROOT)
    config, traffic = bench.config("nemotron-twotower-30b-a3b"), bench.traffic("steady-1g-16k")
    count = bench.flops("tpuft_ssmmix")
    array = 16_384 * 4_096 * 2
    assert array == 134_217_728 and count.blocks_within_depth(config) == 4
    assert sum(count._RUNS[k] * count.UNITS[k] for k in count.UNITS) == 28.5
    a_position = count.bytes_per_position(config)
    heads_bytes = {"before_forward": 6 * 64, "before_backward": 8 * 64, "after_forward": 0, "after_backward": 0}
    assert {k: a_position[k] * 16_384 for k in count.UNITS} == {k: count.UNITS[k] * array + heads_bytes[k] * 16_384 for k in count.UNITS}
    need = count.per_step(config, traffic)
    assert need["bytes"] == 4 * (28.5 * array + 16_384 * 64 * (2 * 6 + 8)) == 15_384_707_072
    peaks = bench.peaks("TPU v5 lite")
    assert need["bytes"] / peaks["hbm_bytes_per_s"] > 50 * need["flops"] / peaks["bf16_flops_per_s"]
    assert need["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(18.78e-3, rel=1e-3)
    by_name = {m["name"]: m for m in bench.doc["per_layer"]}
    reader, metric = bench.reader("ssm_mix_roofline"), by_name["ssm_mix_roofline"]
    names = list(by_name)  # appended after PR 56's metrics, and later PRs' after it
    assert names.index("ssm_mix_roofline") == names.index("gqa16_attn_roofline") + 1
    assert metric["workloads"] == ["nemotron-twotower-30b-a3b.steady-1g-16k"]
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"], metric["source"]) == ("kernels", "%", "tokens_per_s", "device_trace")
    ctx = {"peaks": peaks, "bench": bench, "config": config, "traffic": traffic}
    kernels = {"tpuft_ssmmix_fwd.1": 4.0, "tpuft_ssmmix_fwd.2": 4.0, "tpuft_ssmmix_bwd.1": 7.0, "tpuft_ssmmix_out_fwd.1": 3.5,
               "tpuft_ssmmix_out_fwd.2": 3.5, "tpuft_ssmmix_out_bwd.1": 6.0}
    others = {"tpuft_ssd_fwd.9": 6.1, "tpuft_ssd_bwd.4": 6.2, "fusion.336": 30.0}

    def table(instructions):
        return {"programs": {program_spans.GRAD_PROGRAM: {"instructions": {k: {"ms": v} for k, v in instructions.items()}}}}

    monkeypatch.setattr(device_parts, "of_run", lambda ctx: table({**kernels, **others}))
    assert reader.read(ctx) == pytest.approx(100 * 18.78 / 28.0, rel=1e-3)
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: table(others))         # the parent: the XLA halves ran
    assert reader.read(ctx) is None
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: None)                  # no trace, no op map
    assert reader.read(ctx) is None
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: table(kernels))
    assert reader.read(dict(ctx, config=bench.config("kimi-linear-48b-a3b"))) is None
    assert reader.read(dict(ctx, peaks=None)) is None
