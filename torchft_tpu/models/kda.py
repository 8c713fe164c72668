"""Kimi Delta Attention (arXiv:2510.26692) as a layer kind's mixer
(``LayerKind.mixer == "kda"``): no softmax and no position term — a gated
delta rule with a decay a channel over the kind's ``n_heads`` heads of
``kda_head_dim``, under short causal convolutions (kernel ``kda_conv``) and
low-rank gates.  `_kda_mixer` has the layer; `_init_kda` its leaves."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from torchft_tpu.models.mixer import Mixer, _causal_conv, _l2, _norm_init
from torchft_tpu.ops.delta_attention import SAVED_NAMES


def _init_kda(key: jax.Array, cfg, L: int, kind) -> Dict[str, Any]:
    """A stack of Kimi Delta Attention mixers, from the stack's key: the
    published layer's initialisation of the decay (A = log U(1, 16) a head,
    dt_bias the inverse softplus of log-uniform steps in [0.001, 0.1] a
    channel), both float32."""
    pd, E, H, D, T = cfg.param_dtype, cfg.d_model, kind.n_heads, cfg.kda_head_dim, cfg.kda_conv
    keys = iter(jax.random.split(jax.random.fold_in(key, 5), 14))

    def normal(shape, fan_in):
        return _norm_init(next(keys), (L,) + shape, fan_in, pd)

    steps = jnp.exp(jax.random.uniform(next(keys), (L, H * D), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "wq": normal((E, H * D), E), "wk": normal((E, H * D), E), "wv": normal((E, H * D), E),
        "wo": normal((H * D, E), H * D),
        # [tap, channel]: the last tap the position itself
        "kda_conv_q": normal((T, H * D), T), "kda_conv_k": normal((T, H * D), T), "kda_conv_v": normal((T, H * D), T),
        "kda_a_down": normal((E, D), E), "kda_a_up": normal((D, H * D), D),
        "A_log": jnp.log(jax.random.uniform(next(keys), (L, H), jnp.float32, 1.0, 16.0)),
        "dt_bias": steps + jnp.log(-jnp.expm1(-steps)),  # the inverse of softplus
        "kda_beta": normal((E, H), E),
        "kda_g_down": normal((E, D), E), "kda_g_up": normal((D, H * D), D),
        "kda_g_bias": jnp.zeros((L, H * D), pd), "kda_norm": jnp.ones((L, D), pd),
    }


def _axes(cfg, kind) -> Dict[str, Any]:
    axes = {"wq": ("layers", "embed", "heads"), "wk": ("layers", "embed", "heads"), "wv": ("layers", "embed", "heads"),
            "wo": ("layers", "heads", "embed")}
    axes.update({name: ("layers", None, "heads") for name in ("kda_conv_q", "kda_conv_k", "kda_conv_v",
                                                             "kda_a_up", "kda_g_up")})
    axes.update({"kda_a_down": ("layers", "embed", None), "kda_g_down": ("layers", "embed", None),
                 "kda_beta": ("layers", "embed", None), "A_log": ("layers", None), "dt_bias": ("layers", "heads"),
                 "kda_g_bias": ("layers", "heads"), "kda_norm": ("layers", None)})
    return axes


_KDA_SMALL = ("kda_conv_q", "kda_conv_k", "kda_conv_v", "A_log", "dt_bias", "kda_norm", "kda_g_bias")


def _kda_before(q0, k0, v0, a, b, w, H):
    """`kda_mix` before the scan, in XLA: the projections q0, k0, v0, a
    [B, S, H * D] and b [B, S, H] to the scan's q, k, v [B, H, S, D] in their
    type, g [B, H, S, D] and beta [B, H, S] float32, and the decay's mean."""
    B, S, D, dt, f32 = q0.shape[0], q0.shape[1], q0.shape[2] // H, q0.dtype, jnp.float32

    def heads(y):  # [B, S, H * D] -> [B, H, S, D]
        return y.reshape(B, S, H, D).transpose(0, 2, 1, 3)

    with jax.named_scope("kda_mix"):
        q, k, v = (jax.nn.silu(_causal_conv(z.astype(f32), w[name].astype(f32)))
                   for z, name in ((q0, "kda_conv_q"), (k0, "kda_conv_k"), (v0, "kda_conv_v")))
        q, k = _l2(q.reshape(B, S, H, D)) * D ** -0.5, _l2(k.reshape(B, S, H, D))
        rate = jnp.repeat(jnp.exp(w["A_log"].astype(f32)), D)                    # [H * D]
        g = -rate * jax.nn.softplus(a.astype(f32) + w["dt_bias"].astype(f32))    # [B, S, H * D]
        alpha = jnp.mean(jnp.exp(jax.lax.stop_gradient(g)))
        beta = jax.nn.sigmoid(b.astype(f32)).transpose(0, 2, 1)                  # [B, H, S]
        return heads(q.astype(dt)), heads(k.astype(dt)), heads(v.astype(dt)), heads(g), beta, alpha


def _kda_after(o, gate, w, eps):
    """`kda_mix` after the scan, in XLA: o [B, H, S, D] under the head norm
    and the sigmoid of the gate's projection [B, S, H * D], in the gate's type."""
    B, H, S, D = o.shape
    f32 = jnp.float32
    with jax.named_scope("kda_mix"):
        o = o.transpose(0, 2, 1, 3).astype(f32)                                  # [B, S, H, D]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w["kda_norm"].astype(f32)
        gate_ = jax.nn.sigmoid(gate.astype(f32) + w["kda_g_bias"].astype(f32))
        return (o.reshape(B, S, H * D) * gate_).astype(gate.dtype)


def _kda_mixer(cfg, kind, mesh, h, w):
    """Kimi Delta Attention from the normed input h [B, S, E] to the heads'
    joined output [B, S, H * D], before `wo` (arXiv:2510.26692).  The projections are `attn_proj`'s, the recurrence
    `kda_scan`'s (`ops.delta_attention.kda`), and `kda_mix` is what lies
    between: a causal convolution of kernel `kda_conv` and SiLU on each of
    q~, k~, v~; an L2 norm a head on q (times D**-0.5) and k; the decay
    ``g = -exp(A_log) softplus(a + dt_bias)`` a channel and ``beta =
    sigmoid(.)`` a head, both float32; after the scan an RMSNorm over each
    head's columns (one weight of D) under a sigmoid gate.  Also returns the
    mean of the decay exp(g) over the layer (`kda_alpha_mean`'s term).
    Elementwise work is float32 inside its fusion — or, where
    `ops.kda_mix.applies` (a TPU's program over one device, heads of 128
    columns), inside the `tpuft_kdamix_*` kernels' tile — and lands in the
    compute type."""
    from torchft_tpu.ops import kda_mix
    from torchft_tpu.ops.delta_attention import kda

    S = h.shape[1]
    H, D, dt, f32 = kind.n_heads, cfg.kda_head_dim, cfg.dtype, jnp.float32
    with jax.named_scope("attn_proj"):
        q0, k0, v0 = (h @ w[name].astype(dt) for name in ("wq", "wk", "wv"))
        a = (h @ w["kda_a_down"].astype(dt)) @ w["kda_a_up"].astype(dt)
        gate = (h @ w["kda_g_down"].astype(dt)) @ w["kda_g_up"].astype(dt)
        b = h @ w["kda_beta"].astype(dt)

    small = {name: w[name] for name in _KDA_SMALL}
    kernels = cfg.kda_conv == kda_mix.TAPS and kda_mix.applies(S, D, mesh)
    if kernels:
        with jax.named_scope("kda_mix"):
            q, k, v, g = kda_mix.before(q0, k0, v0, a, *(small[name] for name in _KDA_SMALL[:5]))
            alpha = jnp.mean(jnp.exp(jax.lax.stop_gradient(g)))
            beta = jax.nn.sigmoid(b.astype(f32)).transpose(0, 2, 1)                  # [B, H, S]
    else:
        # The XLA halves keep their INPUTS for the backward pass and nothing between (a checkpoint each): left to
        # autodiff, a layer holds some twenty float32 arrays of [S, H * D] at once (the convolutions' sums, SiLU's
        # and softplus' arguments, the norms' squares), 268 MB each at the benchmark's size.
        q, k, v, g, beta, alpha = jax.checkpoint(lambda *xs: _kda_before(*xs, H))(q0, k0, v0, a, b, small)
    with jax.named_scope("kda_scan"):
        o = kda(q, k, v, g, beta, mesh=mesh)
    if kernels:
        with jax.named_scope("kda_mix"):
            o = kda_mix.after(o, gate, small["kda_norm"], small["kda_g_bias"], eps=cfg.rms_eps)
    else:
        o = jax.checkpoint(lambda *xs: _kda_after(*xs, cfg.rms_eps))(o, gate, small)
    return o, alpha


def _forward(cfg, kind, mesh, rules, h, w, positions):
    o, alpha = _kda_mixer(cfg, kind, mesh, h, w)
    with jax.named_scope("attn_proj"):
        return o @ w["wo"].astype(cfg.dtype), {"kda_alpha": alpha}


def _check(cfg, kind) -> None:
    assert not (cfg.qk_norm or cfg.qk_norm_per_head or cfg.attn_head_gate or cfg.attn_out_gate), (
        "delta attention has no QK-norm and no head gate of the model's")


KDA = Mixer(_init_kda, _axes, _forward, SAVED_NAMES, mean_statistic=("kda_alpha", "kda_alpha_mean"), check=_check)
