"""`ops/kda_mix.py` — the `tpuft_kdamix_*` kernels around Kimi Delta
Attention's scan and around Gated DeltaNet's — on the CPU (``interpret``),
against the XLA halves they stand for (`models/kda.py::_kda_before`,
`_kda_after`; `models/gdn.py::_gdn_before`, `_gdn_after`: key heads under
twice as many value heads, no decay a channel, SiLU for the gate): forward
values and every gradient, the small leaves' included; the convolution's rows
across a tile's edge and at the sequence's start, both directions; which path
`_kda_mixer` and `_gdn_mixer` take; and the benchmark's counts of the parts and
their readers (`benchmark/flops/tpuft_kdamix.py`, `tpuft_gdnmix.py`,
`benchmark/layer_metrics/kda_mix_roofline.py`, `gdn_mix_roofline.py`).
Nothing is timed."""

import contextlib
import dataclasses
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
from torchft_tpu.models import LayerKind, TransformerConfig, init_params  # noqa: E402
from torchft_tpu.models.gdn import _GDN_SMALL, _gdn_after, _gdn_before, _gdn_mixer  # noqa: E402
from torchft_tpu.models.kda import _KDA_SMALL, _kda_after, _kda_before, _kda_mixer  # noqa: E402
from torchft_tpu.ops import _pallas_util, kda_mix  # noqa: E402

D = kda_mix.LANE
BEFORE_LEAVES, AFTER_LEAVES = _KDA_SMALL[:5], _KDA_SMALL[5:]
F32 = jnp.float32


def _inputs(seed: int, batch: int, seq: int, heads: int, dtype, form: str = "kda"):
    """Kimi's form: `heads` heads, each its own key, a decay a channel.  Gated
    DeltaNet's: `heads` KEY heads under twice as many value heads, a decay a
    value head."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 24)
    values = heads * (2 if form == "gdn" else 1)
    columns = (heads * D, heads * D, values * D, values * (D if form == "kda" else 1), values * D, values * D)
    joined = [jax.random.normal(ks[i], (batch, seq, n)).astype(dtype) for i, n in enumerate(columns)]   # q0 k0 v0 a gate dout
    major = [jax.random.normal(ks[6 + i], (batch, values if i in (0, 3) else heads, seq, D)) for i in range(5)]  # o dq dk dv dg
    b = jax.random.normal(ks[11], (batch, seq, values)).astype(dtype)
    taps = lambda i, n: 0.5 * jax.random.normal(ks[12 + i], (4, n * D))  # noqa: E731
    if form == "gdn":
        w = {"gdn_conv_q": taps(0, heads), "gdn_conv_k": taps(1, heads), "gdn_conv_v": taps(2, values),
             "A_log": 0.3 * jax.random.normal(ks[15], (values,)), "dt_bias": jax.random.normal(ks[16], (values,)),
             "gdn_norm": 1 + 0.3 * jax.random.normal(ks[17], (D,))}
    else:
        w = {"kda_conv_q": taps(0, heads), "kda_conv_k": taps(1, heads), "kda_conv_v": taps(2, heads),
             "A_log": 0.3 * jax.random.normal(ks[15], (heads,)), "dt_bias": jax.random.normal(ks[16], (heads * D,)),
             "kda_norm": 1 + 0.3 * jax.random.normal(ks[17], (D,)), "kda_g_bias": jax.random.normal(ks[18], (heads * D,))}
    return joined, major, b, w


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30), np.abs(got - want).max() / np.abs(want).max()


# form, batch, positions, (key) heads, rows a grid step, type, tolerance: several tiles long, batch > 1
CASES = [
    ("kda", 2, 96, 2, 32, jnp.float32, 2e-6), ("kda", 2, 96, 2, 16, jnp.float32, 2e-6), ("kda", 1, 64, 3, 64, jnp.float32, 2e-6),
    ("kda", 2, 128, 1, 32, jnp.bfloat16, 1e-2),
    ("gdn", 2, 96, 2, 32, jnp.float32, 2e-6), ("gdn", 1, 128, 2, 16, jnp.bfloat16, 1e-2),
]
# the halves by form: the kernels' call, the XLA half, the leaves each half reads
BEFORE = {
    "kda": (lambda q0, k0, v0, a, w, **kw: kda_mix.before(q0, k0, v0, a, *(w[n] for n in BEFORE_LEAVES), **kw),
            lambda q0, k0, v0, a, b, w, heads: _kda_before(q0, k0, v0, a, b, w, heads)[:4], BEFORE_LEAVES),
    "gdn": (lambda q0, k0, v0, a, w, **kw: kda_mix.before(q0, k0, v0, None, *(w[n] for n in _GDN_SMALL[:3]), **kw),
            lambda q0, k0, v0, a, b, w, heads: _gdn_before(q0, k0, v0, a, b, w, heads, 2 * heads)[:3], _GDN_SMALL[:3]),
}
AFTER = {
    "kda": (lambda o, gate, w, **kw: kda_mix.after(o, gate, w["kda_norm"], w["kda_g_bias"], eps=1e-5, **kw),
            lambda o, gate, w: _kda_after(o, gate, w, 1e-5), AFTER_LEAVES),
    "gdn": (lambda o, gate, w, **kw: kda_mix.after(o, gate, w["gdn_norm"], None, eps=1e-5, **kw),
            lambda o, gate, w: _gdn_after(o, gate, w, 1e-5), ("gdn_norm",)),
}


@pytest.mark.parametrize("form,batch,seq,heads,tile,dtype,tol", CASES)
def test_before_the_scan_against_the_xla_half(form, batch, seq, heads, tile, dtype, tol) -> None:
    """q, k, v (and Kimi's g) and the gradients of the projections and of the
    three taps' arrays (and Kimi's `A_log` and `dt_bias`); under Gated
    DeltaNet's shapes q and k are the key heads' and v twice as many heads'."""
    (q0, k0, v0, a, _, _), (_, *cots), b, w = _inputs(1, batch, seq, heads, dtype, form)
    kernels, xla, leaves = BEFORE[form]
    assert kda_mix.tile_of(seq, tile) == tile and seq // tile >= 1

    def loss(fn):
        def inner(q0, k0, v0, a, w):
            outs = fn(q0, k0, v0, a, w)
            return sum(jnp.sum(x.astype(F32) * c) for x, c in zip(outs, cots)), outs
        return jax.value_and_grad(inner, argnums=range(5), has_aux=True)

    (_, got), got_grads = loss(lambda *xs: kernels(*xs, tile=tile, interpret=True))(q0, k0, v0, a, w)
    (_, want), want_grads = loss(lambda q0, k0, v0, a, w: xla(q0, k0, v0, a, b, w, heads))(q0, k0, v0, a, w)
    assert len(got) == len(want) == (3 if form == "gdn" else 4)
    for x, y, n in zip(got, want, (heads, heads, heads * (2 if form == "gdn" else 1), heads)):
        assert x.dtype == y.dtype and x.shape == (batch, n, seq, D)
        _close(x, y, tol)
    assert got[-1].dtype == (dtype if form == "gdn" else F32)
    for x, y in zip(got_grads[:4], want_grads[:4]):
        assert x.dtype == dtype
        _close(x, y, tol)
    for name in w:
        if name in leaves:
            _close(got_grads[4][name], want_grads[4][name], max(tol, 1e-5) if dtype == jnp.float32 else 1e-3)
        else:
            assert not np.asarray(got_grads[4][name]).any()


@pytest.mark.parametrize("form,batch,seq,heads,tile,dtype,tol", CASES)
def test_after_the_scan_against_the_xla_half(form, batch, seq, heads, tile, dtype, tol) -> None:
    """The gated head norm and the gradients of o, the gate's projection and
    the norm's weight: under a sigmoid with a bias (and its gradient), and
    under SiLU with none."""
    (_, _, _, _, gate, dout), (o, *_), _, w = _inputs(2, batch, seq, heads, dtype, form)
    o = o.astype(dtype)
    kernels, xla, leaves = AFTER[form]
    loss = lambda fn: jax.value_and_grad(  # noqa: E731
        lambda *xs: (lambda out: (jnp.sum(out.astype(F32) * dout.astype(F32)), out))(fn(*xs)), argnums=range(3), has_aux=True)
    (_, got), got_grads = loss(lambda *xs: kernels(*xs, tile=tile, interpret=True))(o, gate, w)
    (_, want), want_grads = loss(xla)(o, gate, w)
    assert got.dtype == dtype and got.shape == gate.shape == (batch, seq, o.shape[1] * D)
    _close(got, want, tol)
    for x, y in zip(got_grads[:2], want_grads[:2]):
        assert x.dtype == dtype
        _close(x, y, tol)
    for name in leaves:
        _close(got_grads[2][name], want_grads[2][name], max(tol, 1e-5) if dtype == jnp.float32 else 1e-3)


@pytest.mark.parametrize("tile", [16, 32, 64])
@pytest.mark.parametrize("back", [1, 2, 3])
def test_the_convolution_s_rows_cross_a_tile_s_edge_and_stop_at_position_0(tile, back) -> None:
    """With the one tap `back` positions back at 1 and the others at 0, v is
    SiLU of the row `back` before: written out, position by position, over a
    sequence of four tiles — zeros before position 0, the tile before's last
    rows at every edge — and its gradient the mirrored move: the cotangent
    `back` rows LATER times SiLU's slope, nothing after the sequence's end."""
    seq, heads = 4 * tile, 2
    (q0, k0, v0, a, _, _), (_, _, _, dv, _), _, w = _inputs(3, 1, seq, heads, jnp.float32)
    taps = jnp.zeros((4, heads * D)).at[3 - back].set(1.0)
    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731

    def v_of(v0):
        return kda_mix.before(q0, k0, v0, a, taps, taps, taps, w["A_log"], w["dt_bias"], tile=tile, interpret=True)[2]

    got, pull = jax.vjp(v_of, v0)
    z = np.asarray(v0, np.float64)[0]                                              # [S, H * D]
    shifted = np.concatenate([np.zeros((back, heads * D)), z[:seq - back]])
    want = silu(shifted).reshape(seq, heads, D).transpose(1, 0, 2)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[0, :, :back]).any()                                  # SiLU(0) = 0 before position 0
    for edge in range(tile, seq, tile):                                            # the first row of a tile reads the tile before
        np.testing.assert_allclose(np.asarray(got[0, :, edge]), silu(z[edge - back]).reshape(heads, D), rtol=1e-5, atol=1e-6)
    (dz,) = pull(dv)
    sig = 1 / (1 + np.exp(-shifted))
    dc = np.asarray(dv, np.float64)[0].transpose(1, 0, 2).reshape(seq, heads * D) * sig * (1 + shifted * (1 - sig))
    want_dz = np.concatenate([dc[back:], np.zeros((back, heads * D))])
    np.testing.assert_allclose(np.asarray(dz[0]), want_dz, rtol=1e-5, atol=1e-6)
    assert not np.asarray(dz[0, seq - back:]).any()                                # no position after the end
    for edge in range(tile, seq, tile):                                            # the last row of a tile reads the tile after
        np.testing.assert_allclose(np.asarray(dz[0, edge - 1]), dc[edge - 1 + back], rtol=1e-5, atol=1e-6)


def test_rows_a_grid_step() -> None:
    assert kda_mix.tile_of(16_384) == 1024 and kda_mix.tile_of(16_384, 512) == 512
    assert kda_mix.tile_of(96) == 32 and kda_mix.tile_of(96, 16) == 16 and kda_mix.tile_of(48) == 16
    assert kda_mix.tile_of(72) is None and kda_mix.tile_of(11) is None            # no tile of 16 rows divides them
    for name in ("tpuft_kdamix_fwd", "tpuft_kdamix_bwd", "tpuft_kdamix_out_fwd", "tpuft_kdamix_out_bwd"):
        assert "tpuft_kda_" not in name                                            # the benchmark books that to the scan


def _mixer(heads: int, width: int, seq: int, dtype=jnp.float32, form: str = "kda"):
    """One layer's mixer, its leaves and an input: Kimi Delta Attention at
    `heads` heads, or Gated DeltaNet at `heads` KEY heads under twice as many
    value heads."""
    sizes = (dict(kda_head_dim=width) if form == "kda" else
             dict(gdn_key_heads=heads, gdn_key_dim=width, gdn_value_dim=width))
    values = heads * (2 if form == "gdn" else 1)
    cfg = TransformerConfig(vocab_size=32, d_model=24, n_layers=1, n_heads=values, n_kv_heads=values, d_ff=16, dtype=dtype,
                            rms_eps=1e-5, pattern=(LayerKind("layers", False, values, 1e4, mixer=form),), **sizes)
    w = jax.tree.map(lambda x: x[0], init_params(jax.random.PRNGKey(3), cfg)["layers"])
    rng = np.random.default_rng(3)
    if form == "kda":
        w = dict(w, kda_g_bias=jnp.asarray(rng.standard_normal(heads * width), jnp.float32))
    w = dict(w, **{form + "_norm": jnp.asarray(1 + 0.3 * rng.standard_normal(width), jnp.float32)})
    h = jnp.asarray(rng.standard_normal((2, seq, 24)), dtype)
    return cfg, w, h


MIXERS = {"kda": _kda_mixer, "gdn": _gdn_mixer}


def _interpreted(monkeypatch):
    """A mixer on the kernels' path on the CPU: the backend reads as a
    TPU and the kernels run interpreted; returns the calls made."""
    calls = []
    monkeypatch.setattr(_pallas_util, "on_tpu", lambda: True)
    for name in ("before", "after"):
        real = getattr(kda_mix, name)
        monkeypatch.setattr(kda_mix, name, lambda *a, _real=real, _name=name, **k: (
            calls.append(_name), _real(*a, **k, interpret=True))[1])
    return calls


@pytest.mark.parametrize("form", ["kda", "gdn"])
def test_the_mixer_through_the_kernels_is_the_mixer_through_xla(form, monkeypatch) -> None:
    """Heads of 128 on a TPU's one-device program: both halves go through the
    kernels; output, the decay's mean and every weight's gradient agree with
    the XLA halves' (the scan between them is the same call) — Kimi Delta
    Attention's mixer, and Gated DeltaNet's at 2 key heads under 4 value heads."""
    from torchft_tpu.ops import delta_attention

    cfg, w, h = _mixer(2, D, 32, form=form)
    monkeypatch.setattr(delta_attention.kda, "__kwdefaults__", dict(delta_attention.kda.__kwdefaults__, chunk=16, interpret=True))

    def run(h, w):
        out, alpha = MIXERS[form](cfg, cfg.pattern[0], None, h, w)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size, dtype=F32).reshape(out.shape))), (out, alpha)

    (_, (want, want_alpha)), want_grads = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(h, w)
    calls = _interpreted(monkeypatch)
    (_, (got, got_alpha)), got_grads = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(h, w)
    assert calls == ["before", "after"]
    _close(got, want, 1e-5)
    assert float(got_alpha) == pytest.approx(float(want_alpha), rel=1e-6)
    for x, y in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        _close(x, y, 5e-5)


@pytest.mark.parametrize("form,why", [("kda", why) for why in ("not_a_tpu", "a_mesh_of_two", "heads_of_64", "no_tile", "kernel_of_3")]
                         + [("gdn", why) for why in ("not_a_tpu", "a_mesh_of_two", "heads_of_64", "kernel_of_3")])
def test_the_xla_halves_where_the_kernels_do_not_apply(form, why, monkeypatch) -> None:
    """Off the TPU, under a mesh of more than one device, at a head width
    that is no lane tile, at a sequence no tile divides, at another
    convolution: the mixer calls no kernel and gives what its XLA half before
    the scan, the scan and its XLA half after it give (a checkpoint each), bit
    for bit."""
    from torchft_tpu.ops.delta_attention import kda

    from torchft_tpu.ops import delta_attention

    width, seq = (64 if why == "heads_of_64" else D), (24 if why == "no_tile" else 32)
    cfg, w, h = _mixer(2, width, seq, form=form)
    mesh = None
    if why != "not_a_tpu":
        calls = _interpreted(monkeypatch)
        monkeypatch.setattr(delta_attention.kda, "__kwdefaults__", dict(delta_attention.kda.__kwdefaults__, interpret=True))
    else:
        calls = []
        monkeypatch.setattr(kda_mix, "before", lambda *a, **k: calls.append("before"))
    if why == "a_mesh_of_two":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
        assert mesh.size == 2
    if why == "kernel_of_3":
        cfg = dataclasses.replace(cfg, **{form + "_conv": 3})
        w = dict(w, **{name: w[name][1:] for name in (_KDA_SMALL if form == "kda" else _GDN_SMALL)[:3]})
    with pytest.warns(UserWarning, match="pallas kernels are off") if why == "a_mesh_of_two" else contextlib.nullcontext():
        assert kda_mix.applies(seq, width, mesh) == (why == "kernel_of_3")
        got, alpha = MIXERS[form](cfg, cfg.pattern[0], mesh, h, w)
    assert calls == []
    dt = cfg.dtype
    q0, k0, v0 = (h @ w[name].astype(dt) for name in ("wq", "wk", "wv"))
    if form == "kda":
        a = (h @ w["kda_a_down"].astype(dt)) @ w["kda_a_up"].astype(dt)
        gate = (h @ w["kda_g_down"].astype(dt)) @ w["kda_g_up"].astype(dt)
        small = {name: w[name] for name in _KDA_SMALL}
        q, k, v, g, beta, want_alpha = jax.checkpoint(lambda *xs: _kda_before(*xs, 2))(
            q0, k0, v0, a, h @ w["kda_beta"].astype(dt), small)
        want = jax.checkpoint(lambda *xs: _kda_after(*xs, cfg.rms_eps))(kda(q, k, v, g, beta, mesh=mesh), gate, small)
    else:
        gate, a, b = (h @ w[name].astype(dt) for name in ("wz", "gdn_a", "gdn_b"))
        small = {name: w[name] for name in _GDN_SMALL}
        q, k, v, g, beta, want_alpha = jax.checkpoint(lambda *xs: _gdn_before(*xs, 2, 4))(q0, k0, v0, a, b, small)
        want = jax.checkpoint(lambda *xs: _gdn_after(*xs, cfg.rms_eps))(kda(q, k, v, g, beta, mesh=mesh), gate, small)
    assert np.array_equal(np.asarray(got), np.asarray(want)) and float(alpha) == float(want_alpha)


def test_v_in_a_call_of_its_own_is_v_beside_its_key_head() -> None:
    """What `tools/kdamix_probe.py` times as the other choice for Gated
    DeltaNet's v — q and k in one call at the key heads, v in one at the value
    heads, a stream that is None not in the call — gives the riding call's q,
    k, v and gradients."""
    (q0, k0, v0, _, _, _), (_, dq, dk, dv, _), _, w = _inputs(4, 1, 64, 2, jnp.float32, "gdn")
    rides = lambda q0, k0, v0, w: kda_mix.before(q0, k0, v0, None, *(w[n] for n in _GDN_SMALL[:3]), tile=32, interpret=True)  # noqa: E731

    def apart(q0, k0, v0, w):
        f32 = lambda name: w[name].astype(F32)  # noqa: E731
        q, k, _, _ = kda_mix._before(q0, k0, None, None, jnp.stack([f32("gdn_conv_q"), f32("gdn_conv_k")]), None, None, 32, True)
        _, _, v, _ = kda_mix._before(None, None, v0, None, f32("gdn_conv_v")[None], None, None, 32, True)
        return q, k, v

    loss = lambda fn: jax.value_and_grad(  # noqa: E731
        lambda *xs: (lambda outs: (sum(jnp.sum(x * c) for x, c in zip(outs, (dq, dk, dv))), outs))(fn(*xs)), argnums=range(4), has_aux=True)
    (_, got), got_grads = loss(apart)(q0, k0, v0, w)
    (_, want), want_grads = loss(rides)(q0, k0, v0, w)
    for x, y in zip(jax.tree.leaves((got, got_grads)), jax.tree.leaves((want, want_grads))):
        _close(x, y, 1e-6)


# -- the benchmark's count and reader ------------------------------------------------------------


def test_the_part_s_bytes_from_shapes_and_its_reader(monkeypatch) -> None:
    """ISSUE 49's table at the cell's shapes: 9 + 13 + 3 + 5 arrays of
    [16,384, 4,096] bf16 a layer, four layers; bound by HBM by the counts; the
    reader sets that against the `tpuft_kdamix_*` instructions' time and the
    scan's kernels are not among them; None where no such kernel ran."""
    from benchmark import device_parts, program_spans

    bench = Benchmark(ROOT)
    config, traffic = bench.config("kimi-linear-48b-a3b"), bench.traffic("steady-1g-16k")
    count = bench.flops("tpuft_kdamix")
    array = 16_384 * 4_096 * 2
    assert array == 134_217_728 and count.layers_within_depth(config) == 4
    need = count.per_step(config, traffic)
    assert need["bytes"] == 4 * (9 + 13 + 3 + 5) * array == 16_106_127_360
    assert [count.UNITS[k] * array for k in ("before_forward", "after_forward", "before_backward", "after_backward")] == [
        1_207_959_552, 402_653_184, 1_744_830_464, 671_088_640]                    # 1.21, 0.40, 1.75, 0.67 GB
    peaks = bench.peaks("TPU v5 lite")
    assert need["bytes"] / peaks["hbm_bytes_per_s"] > 50 * need["flops"] / peaks["bf16_flops_per_s"]
    assert need["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(19.67e-3, rel=1e-3)
    by_name = {m["name"]: m for m in bench.doc["per_layer"]}
    reader, metric = bench.reader("kda_mix_roofline"), by_name["kda_mix_roofline"]
    names = list(by_name)  # appended after PR 48's metrics, and later PRs' after it
    assert names.index("kda_mix_roofline") == names.index("kda_alpha_mean") + 1
    assert metric["workloads"] == ["kimi-linear-48b-a3b.steady-1g-16k"]
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"], metric["source"]) == ("kernels", "%", "tokens_per_s", "device_trace")
    ctx = {"peaks": peaks, "bench": bench, "config": config, "traffic": traffic}
    kernels = {"tpuft_kdamix_fwd.1": 2.2, "tpuft_kdamix_fwd.2": 2.2, "tpuft_kdamix_bwd.1": 2.9, "tpuft_kdamix_out_fwd.1": 0.8,
               "tpuft_kdamix_out_fwd.2": 0.8, "tpuft_kdamix_out_bwd.1": 1.3}
    others = {"tpuft_kda_fwd.9": 17.4, "tpuft_kda_bwd.4": 23.7, "fusion.12": 3.0}

    def table(instructions):
        return {"programs": {program_spans.GRAD_PROGRAM: {"instructions": {k: {"ms": v} for k, v in instructions.items()}}}}

    monkeypatch.setattr(device_parts, "of_run", lambda ctx: table({**kernels, **others}))
    assert reader.read(ctx) == pytest.approx(100 * 19.67 / 10.2, rel=1e-3)
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: table(others))         # the parent: no such kernel
    assert reader.read(ctx) is None
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: None)                  # no trace, no op map
    assert reader.read(ctx) is None
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: table(kernels))
    assert reader.read(dict(ctx, config=bench.config("moonlight-16b-a3b"))) is None
    assert reader.read(dict(ctx, peaks=None)) is None


def test_gated_deltanet_s_part_s_bytes_from_shapes_and_its_reader(monkeypatch) -> None:
    """ISSUE 69's count at the Qwen3-Next cell's shapes: 147,456 bytes a
    position and layer (16 key heads and 32 value heads of 128: no decay a
    channel is read or written), three layers of 16,384 positions; bound by
    HBM by the counts; the reader sets that against the `tpuft_kdamix_*`
    instructions' time — the same kernels as Kimi's, under the cell's own
    count — and the scan's kernels are not among them; None where no such
    kernel ran, and in a model without a Gated DeltaNet layer."""
    from benchmark import device_parts, program_spans

    bench = Benchmark(ROOT)
    cell = "qwen3-next-80b-a3b.steady-1g-16k"
    config, traffic = bench.config("qwen3-next-80b-a3b"), bench.traffic("steady-1g-16k")
    count = bench.flops("tpuft_gdnmix")
    assert count.layers_within_depth(config) == 3 and count.columns(config) == {"key": 2_048, "value": 4_096}
    assert count.bytes_per_position(config) == {
        "before_forward": 32_768, "before_backward": 49_152, "after_forward": 24_576, "after_backward": 40_960}
    need = count.per_step(config, traffic)
    assert need["bytes"] == 147_456 * 16_384 * 3 == 7_247_757_312 == 54 * 16_384 * 4_096 * 2       # 18 arrays a layer
    peaks = bench.peaks("TPU v5 lite")
    assert need["bytes"] / peaks["hbm_bytes_per_s"] > 50 * need["flops"] / peaks["bf16_flops_per_s"]
    assert need["bytes"] / peaks["hbm_bytes_per_s"] == pytest.approx(8.85e-3, rel=1e-3)
    by_name = {m["name"]: m for m in bench.doc["per_layer"]}
    reader, metric = bench.reader("gdn_mix_roofline"), by_name["gdn_mix_roofline"]
    names = list(by_name)  # appended after PR 68's metrics, and later PRs' after it
    assert names.index("gdn_mix_roofline") == names.index("moe_shared_gate_mean") + 1
    assert metric["workloads"] == [cell] and "gdn_mix_roofline" in {m["name"] for m in bench.per_layer(cell)}
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"], metric["source"]) == ("kernels", "%", "tokens_per_s", "device_trace")
    ctx = {"peaks": peaks, "bench": bench, "config": config, "traffic": traffic}
    kernels = {"tpuft_kdamix_fwd.1": 2.0, "tpuft_kdamix_fwd.2": 2.0, "tpuft_kdamix_bwd.1": 3.0, "tpuft_kdamix_out_fwd.1": 0.8,
               "tpuft_kdamix_out_fwd.2": 0.8, "tpuft_kdamix_out_bwd.1": 1.4}
    others = {"tpuft_kda_fwd.9": 7.5, "tpuft_kda_bwd.4": 11.8, "fusion.12": 3.0}

    def table(instructions):
        return {"programs": {program_spans.GRAD_PROGRAM: {"instructions": {k: {"ms": v} for k, v in instructions.items()}}}}

    monkeypatch.setattr(device_parts, "of_run", lambda ctx: table({**kernels, **others}))
    assert reader.read(ctx) == pytest.approx(100 * 8.85 / 10.0, rel=1e-3)
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: table(others))         # the parent: the halves are XLA fusions
    assert reader.read(ctx) is None
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: None)                  # no trace, no op map
    assert reader.read(ctx) is None
    monkeypatch.setattr(device_parts, "of_run", lambda ctx: table(kernels))
    assert reader.read(dict(ctx, config=bench.config("kimi-linear-48b-a3b"))) is None      # Kimi's cell reads `kda_mix_roofline`
    assert bench.reader("kda_mix_roofline").read(ctx) is None                               # and this cell not Kimi's
    assert reader.read(dict(ctx, peaks=None)) is None
    # the cell's scan family is `"tpuft_kda_" in op`: no mix kernel is booked to `gdn_scan_ms`
    assert not any(bench.program("gdn_moe_lm").kernel_names()["gdn"](name) for name in kernels)
