"""Gated DeltaNet (arXiv:2412.06464, as Qwen3-Next's linear layers have it) as
a layer kind's mixer (``LayerKind.mixer == "gdn"``): no softmax and no position
term — a gated delta rule with ONE decay a head and position over the kind's
``n_heads`` VALUE heads of ``gdn_value_dim``, which read ``gdn_key_heads`` key
heads of ``gdn_key_dim`` (value head j reads key head ``j // (n_heads //
gdn_key_heads)``), under one short causal convolution (kernel ``gdn_conv``) over
q~, k~ and v~ and a full-rank SiLU gate over the head norm.  `_gdn_mixer` has
the layer; `_init_gdn` its leaves.

The published layer fuses its projections (`in_proj_qkvz`: hidden -> q | k | v
| z interleaved a key-head group; `in_proj_ba`: hidden -> b | a) and runs ONE
convolution over the 8,192 channels of (q, k, v).  Here they are four matrices
and two, and three tap arrays: a permutation of columns, a departure of layout
and not of mathematics.

What stands around the scan (`gdn_mix`) runs in one of two places.  On a TPU's
program over one device, with key and value heads of 128 columns and the
kernel-4 convolution, both halves are `ops/kda_mix.py`'s pallas kernels (the
ones Kimi Delta Attention's layer runs, under these operands' shapes) and only
the decay a head — `_gdn_decay`, [B, S, 32] — stays XLA's.  Everywhere else
(the CPU, a mesh of several devices, another kernel size or head width) they
are the XLA halves `_gdn_before` and `_gdn_after` under a checkpoint each,
which are also what the tests hold the kernels to."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from torchft_tpu.models.mixer import Mixer, _causal_conv, _l2, _norm_init
from torchft_tpu.ops.delta_attention import SAVED_NAMES


def _init_gdn(key: jax.Array, cfg, L: int, kind) -> Dict[str, Any]:
    """A stack of Gated DeltaNet mixers, from the stack's key: the decay's
    leaves float32, `A_log` = log U(0, 16) a value head (the published
    layer's) and `dt_bias` the inverse softplus of log-uniform steps in
    [0.001, 0.1] a value head (gated delta networks' own initialisation; the
    benchmark's configuration file says why not the published ones)."""
    pd, E, H, Hk = cfg.param_dtype, cfg.d_model, kind.n_heads, cfg.gdn_key_heads
    wide_k, wide_v, T = Hk * cfg.gdn_key_dim, H * cfg.gdn_value_dim, cfg.gdn_conv
    keys = iter(jax.random.split(jax.random.fold_in(key, 6), 12))

    def normal(shape, fan_in):
        return _norm_init(next(keys), (L,) + shape, fan_in, pd)

    steps = jnp.exp(jax.random.uniform(next(keys), (L, H), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "wq": normal((E, wide_k), E), "wk": normal((E, wide_k), E), "wv": normal((E, wide_v), E),
        "wz": normal((E, wide_v), E), "wo": normal((wide_v, E), wide_v),
        "gdn_b": normal((E, H), E), "gdn_a": normal((E, H), E),
        # [tap, channel]: the last tap the position itself
        "gdn_conv_q": normal((T, wide_k), T), "gdn_conv_k": normal((T, wide_k), T), "gdn_conv_v": normal((T, wide_v), T),
        "A_log": jnp.log(jax.random.uniform(next(keys), (L, H), jnp.float32, 1e-4, 16.0)),
        "dt_bias": steps + jnp.log(-jnp.expm1(-steps)),  # the inverse of softplus
        "gdn_norm": jnp.ones((L, cfg.gdn_value_dim), pd),
    }


def _axes(cfg, kind) -> Dict[str, Any]:
    axes = {name: ("layers", "embed", "heads") for name in ("wq", "wk", "wv", "wz")}
    axes.update({name: ("layers", None, "heads") for name in ("gdn_conv_q", "gdn_conv_k", "gdn_conv_v")})
    axes.update({"wo": ("layers", "heads", "embed"), "gdn_b": ("layers", "embed", None), "gdn_a": ("layers", "embed", None),
                 "A_log": ("layers", None), "dt_bias": ("layers", None), "gdn_norm": ("layers", None)})
    return axes


_GDN_SMALL = ("gdn_conv_q", "gdn_conv_k", "gdn_conv_v", "A_log", "dt_bias", "gdn_norm")


def _gdn_decay(a, b, w):
    """g and beta [B, H, S] float32 — ONE number each a value head and
    position — from a, b [B, S, H], and the decay's mean: two small fusions
    that stay XLA's on either path."""
    f32 = jnp.float32
    g = -jnp.exp(w["A_log"].astype(f32)) * jax.nn.softplus(a.astype(f32) + w["dt_bias"].astype(f32))  # [B, S, H]
    alpha = jnp.mean(jnp.exp(jax.lax.stop_gradient(g)))
    beta = jax.nn.sigmoid(b.astype(f32))
    return g.transpose(0, 2, 1), beta.transpose(0, 2, 1), alpha


def _gdn_before(q0, k0, v0, a, b, w, Hk, H):
    """`gdn_mix` before the scan, in XLA: the projections q0, k0 [B, S, Hk *
    Dk], v0 [B, S, H * Dv] and a, b [B, S, H] to the scan's q, k [B, Hk, S, Dk]
    and v [B, H, S, Dv] in their type, g and beta [B, H, S] float32, and the
    decay's mean."""
    B, S, dt, f32 = q0.shape[0], q0.shape[1], q0.dtype, jnp.float32

    def major(y):  # [B, S, n, D] -> [B, n, S, D] in the scan's type
        return y.astype(dt).transpose(0, 2, 1, 3)

    with jax.named_scope("gdn_mix"):
        q, k, v = (jax.nn.silu(_causal_conv(z.astype(f32), w[name].astype(f32)))
                   for z, name in ((q0, "gdn_conv_q"), (k0, "gdn_conv_k"), (v0, "gdn_conv_v")))
        Dk = q.shape[-1] // Hk
        q, k = _l2(q.reshape(B, S, Hk, Dk)) * Dk ** -0.5, _l2(k.reshape(B, S, Hk, Dk))
        return major(q), major(k), major(v.reshape(B, S, H, -1)), *_gdn_decay(a, b, w)


def _gdn_after(o, z, w, eps):
    """`gdn_mix` after the scan, in XLA: o [B, H, S, Dv] under the head norm
    (a plain weight of Dv, no unit offset) times SiLU of the gate's projection
    z [B, S, H * Dv], in the gate's type."""
    B, H, S, D = o.shape
    f32 = jnp.float32
    with jax.named_scope("gdn_mix"):
        o = o.transpose(0, 2, 1, 3).astype(f32)                                  # [B, S, H, Dv]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w["gdn_norm"].astype(f32)
        return (o.reshape(B, S, H * D) * jax.nn.silu(z.astype(f32))).astype(z.dtype)


def _gdn_mixer(cfg, kind, mesh, h, w):
    """Gated DeltaNet from the normed input h [B, S, E] to the value heads'
    joined output [B, S, H * Dv], before `wo`.  The projections are
    `attn_proj`'s, the recurrence `gdn_scan`'s (`ops.delta_attention.kda`,
    which reads the decay's rank and the key heads' count off its operands),
    and `gdn_mix` is what lies between: a causal convolution of kernel
    `gdn_conv` and SiLU on each of q~, k~, v~; an L2 norm a head on q (times
    Dk**-0.5) and k; ``g = -exp(A_log) softplus(a + dt_bias)`` and ``beta =
    sigmoid(b)``, ONE number each a value head and position, float32; after
    the scan an RMSNorm over each head's columns (one plain weight of Dv)
    times SiLU(z).  Also returns the mean of the decay exp(g) over the layer
    (`gdn_alpha_mean`'s term).  Where `ops.kda_mix.applies` (a TPU's program
    over one device, key and value heads of 128 columns, a sequence with a
    tile) and the convolution has its four taps, the two halves around the
    scan are `ops/kda_mix.py`'s kernels — `tpuft_kdamix_fwd` / `_bwd` before
    it, v's two lane tiles riding with the key head that reads them, and
    `tpuft_kdamix_out_fwd` / `_bwd` after it with SiLU for the gate — and the
    decay's two small fusions stay XLA's.  Everywhere else (the CPU, a mesh of
    several devices, another kernel size or head width) they are the XLA
    halves `_gdn_before` and `_gdn_after`, checkpoints that keep their INPUTS
    and nothing between (models/kda.py says what a layer holds otherwise)."""
    from torchft_tpu.ops import kda_mix
    from torchft_tpu.ops.delta_attention import kda

    H, Hk, dt = kind.n_heads, cfg.gdn_key_heads, cfg.dtype
    with jax.named_scope("attn_proj"):
        q0, k0, v0, z, a, b = (h @ w[name].astype(dt) for name in ("wq", "wk", "wv", "wz", "gdn_a", "gdn_b"))
    small = {name: w[name] for name in _GDN_SMALL}
    kernels = (cfg.gdn_conv == kda_mix.TAPS and cfg.gdn_key_dim == cfg.gdn_value_dim
               and kda_mix.applies(h.shape[1], cfg.gdn_value_dim, mesh))
    if kernels:
        with jax.named_scope("gdn_mix"):
            q, k, v = kda_mix.before(q0, k0, v0, None, *(small[name] for name in _GDN_SMALL[:3]))
            g, beta, alpha = _gdn_decay(a, b, small)
    else:
        q, k, v, g, beta, alpha = jax.checkpoint(lambda *xs: _gdn_before(*xs, Hk, H))(q0, k0, v0, a, b, small)
    with jax.named_scope("gdn_scan"):
        o = kda(q, k, v, g, beta, mesh=mesh)
    if kernels:
        with jax.named_scope("gdn_mix"):
            return kda_mix.after(o, z, small["gdn_norm"], None, eps=cfg.rms_eps), alpha
    return jax.checkpoint(lambda *xs: _gdn_after(*xs, cfg.rms_eps))(o, z, small), alpha


def _forward(cfg, kind, mesh, rules, h, w, positions):
    o, alpha = _gdn_mixer(cfg, kind, mesh, h, w)
    with jax.named_scope("attn_proj"):
        return o @ w["wo"].astype(cfg.dtype), {"gdn_alpha": alpha}


def _check(cfg, kind) -> None:
    assert kind.n_heads % cfg.gdn_key_heads == 0, "a key head serves a whole number of value heads"
    assert not (cfg.qk_norm or cfg.attn_head_gate), "the delta rule has no QK-norm and no head gate of the model's"
    # the per-head QK-norm and the column gate are the attention KIND's leaves: a pattern without one asks for nothing
    assert any(k.mixer == "attention" for k in cfg.pattern) or not (cfg.qk_norm_per_head or cfg.attn_out_gate), (
        "a per-head QK-norm and an output gate a column are attention's alone, and no layer here is attention")


GDN = Mixer(_init_gdn, _axes, _forward, SAVED_NAMES, mean_statistic=("gdn_alpha", "gdn_alpha_mean"), check=_check)
