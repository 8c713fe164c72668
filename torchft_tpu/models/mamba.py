"""The Mamba-2 mixer (state-space duality, arXiv:2405.21060) as a layer kind's
mixer (``LayerKind.mixer == "mamba2"``): its leaves, their axes, and the block
from the normed input to the residual's addend.

H heads (the kind's ``n_heads``) of P = ``ssm_head_dim`` columns, G =
``ssm_groups`` groups of H / G heads that share B and C, a state of N =
``ssm_state`` rows a head, a depthwise causal convolution of kernel
``ssm_conv`` with a bias, chunks of ``ssm_chunk`` positions:

    [z | u | dt] = h W_in            W_in: E -> H P + (H P + 2 G N) + H;  u = [x | B | C]
    u   = SiLU(conv(u) + b_conv)     a weight a channel and tap, zeros before the first position
    dt_t = softplus(dt_t + dt_bias)  float32
    la_t = -exp(A_log) dt_t          the log of the decay a head, <= 0
    S_t = exp(la_t) S_{t-1} + B_t (dt_t x_t)^T,   y_t = S_t^T C_t + D x_t      (`ops.ssd`)
    o   = RMSNorm_groups(y * SiLU(z); w_n)   the gate FIRST, then the norm over each group's H P / G columns
    out = o W_out

The products are `attn_proj`'s, the recurrence `ssm_scan`'s, and `ssm_mix` is
what stands between: the convolution, SiLU, softplus and the decay before the
scan, the skip, the gate and the group norm after it — float32 inside, the
compute type out.  Where `ops.ssm_mix.applies` (a TPU's program over one
device, a convolution of four taps, shapes of whole lane tiles) each half is a
pallas kernel pair, `tpuft_ssmmix_*`: one pass over HBM a direction, the
backward recomputing its tile from the half's inputs.  Elsewhere — the CPU, a
mesh of several devices, shapes the kernels do not tile — the halves are the
XLA fusions `_before` and `_after`, the kernels' yardstick: each keeps its
INPUTS for the backward pass and nothing between (a checkpoint each, as
`kda_mix`'s XLA halves): left to autodiff a block holds a dozen float32 arrays
of [S, H P + 2 G N] at once.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.mixer import Mixer, _causal_conv
from torchft_tpu.ops.ssd import SAVED_NAMES

_SMALL = ("ssm_conv", "ssm_conv_bias", "dt_bias", "A_log", "ssm_D", "ssm_norm")


def widths(cfg, heads: int) -> Tuple[int, int]:
    """(the heads' joined width H P, the convolution's channels H P + 2 G N)."""
    inner = heads * cfg.ssm_head_dim
    return inner, inner + 2 * cfg.ssm_groups * cfg.ssm_state


def mamba2_axes(cfg, kind) -> Dict[str, Any]:
    """Logical axis names of the mixer's leaves (``param_axes``)."""
    return {"ssm_in": ("layers", "embed", None), "ssm_conv": ("layers", None, None), "ssm_conv_bias": ("layers", None),
            "dt_bias": ("layers", None), "A_log": ("layers", None), "ssm_D": ("layers", None),
            "ssm_norm": ("layers", None), "ssm_out": ("layers", None, "embed")}


def init_mamba2(key: jax.Array, cfg, L: int, kind) -> Dict[str, Any]:
    """A stack of Mamba-2 mixers, from the stack's key: the published layer's
    initialisation of the decay (A = log U(1, 16) a head, dt_bias the inverse
    softplus of log-uniform steps in [0.001, 0.1] floored at 1e-4), D at one,
    the taps [channel, tap] normal at kernel**-0.5 with a zero bias."""
    pd, E, T, heads = cfg.param_dtype, cfg.d_model, cfg.ssm_conv, kind.n_heads
    inner, channels = widths(cfg, heads)
    k_in, k_conv, k_a, k_dt, k_out = jax.random.split(jax.random.fold_in(key, 6), 5)
    steps = jnp.maximum(jnp.exp(jax.random.uniform(k_dt, (L, heads), jnp.float32, jnp.log(0.001), jnp.log(0.1))), 1e-4)
    normal = lambda k, shape, fan_in: (jax.random.normal(k, (L,) + shape, pd) * fan_in ** -0.5).astype(pd)   # noqa: E731
    return {
        "ssm_in": normal(k_in, (E, inner + channels + heads), E),
        "ssm_conv": normal(k_conv, (channels, T), T),                # [channel, tap]: the last tap the position itself
        "ssm_conv_bias": jnp.zeros((L, channels), pd),
        "dt_bias": steps + jnp.log(-jnp.expm1(-steps)),              # the inverse of softplus
        "A_log": jnp.log(jax.random.uniform(k_a, (L, heads), jnp.float32, 1.0, 16.0)),
        "ssm_D": jnp.ones((L, heads), pd),
        "ssm_norm": jnp.ones((L, inner), pd),
        "ssm_out": normal(k_out, (inner, E), inner),
    }


def _before(u, dt_raw, w, cfg, heads: int):
    """`ssm_mix` before the scan: the convolved channels u [B, S, C] and the
    step's projection dt_raw [B, S, H] to x and dt * x [B, S, H P], B and C
    [B, S, G N] in u's type, the log decay [B, S, H] float32, and the decay's
    mean."""
    f32, dt_ = jnp.float32, u.dtype
    B, S, _ = u.shape
    P, inner = cfg.ssm_head_dim, widths(cfg, heads)[0]
    state = cfg.ssm_groups * cfg.ssm_state
    with jax.named_scope("ssm_mix"):
        a = jax.nn.silu(_causal_conv(u.astype(f32), w["ssm_conv"].astype(f32).T) + w["ssm_conv_bias"].astype(f32))
        x, bm, cm = a[..., :inner], a[..., inner:inner + state], a[..., inner + state:]
        dt = jax.nn.softplus(dt_raw.astype(f32) + w["dt_bias"].astype(f32))               # [B, S, H]
        la = -jnp.exp(w["A_log"].astype(f32)) * dt
        decay = jnp.mean(jnp.exp(jax.lax.stop_gradient(la)))
        xdt = (x.reshape(B, S, heads, P) * dt[..., None]).reshape(B, S, inner)
        return x.astype(dt_), xdt.astype(dt_), bm.astype(dt_), cm.astype(dt_), la, decay


def _after(y, x, z, w, cfg, heads: int):
    """`ssm_mix` after the scan: y + D x under SiLU(z), then the RMS norm over
    each group's columns, in z's type."""
    f32 = jnp.float32
    B, S, inner = y.shape
    P, G = cfg.ssm_head_dim, cfg.ssm_groups
    with jax.named_scope("ssm_mix"):
        skip = (x.astype(f32).reshape(B, S, heads, P) * w["ssm_D"].astype(f32)[:, None]).reshape(B, S, inner)
        o = ((y.astype(f32) + skip) * jax.nn.silu(z.astype(f32))).reshape(B, S, G, inner // G)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps)
        return (o.reshape(B, S, inner) * w["ssm_norm"].astype(f32)).astype(z.dtype)


def mamba2_mixer(cfg, kind, mesh, h: jax.Array, w: Dict[str, Any]) -> Tuple[jax.Array, jax.Array]:
    """The mixer from the normed input h [B, S, E] to the residual's addend
    [B, S, E], and the mean of the decay exp(la) over the block
    (`ssm_decay_mean`'s term).  W_in's three parts are products of their own:
    one product would write z, u and dt side by side and the split would copy
    each out again."""
    from torchft_tpu.ops import ssm_mix
    from torchft_tpu.ops.ssd import ssd

    heads, dt_ = kind.n_heads, cfg.dtype
    inner, channels = widths(cfg, heads)
    with jax.named_scope("attn_proj"):
        w_in = w["ssm_in"].astype(dt_)
        z, u = h @ w_in[:, :inner], h @ w_in[:, inner:inner + channels]
        dt_raw = h @ w_in[:, inner + channels:]
    small = {name: w[name] for name in _SMALL}
    kernels = ssm_mix.applies(h.shape[1], cfg.ssm_head_dim, heads // cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv, mesh)
    if kernels:
        with jax.named_scope("ssm_mix"):
            x, xdt, bm, cm, la = ssm_mix.before(u, dt_raw, small["ssm_conv"].T, small["ssm_conv_bias"], small["dt_bias"],
                                                small["A_log"], head_dim=cfg.ssm_head_dim)
            decay = jnp.mean(jnp.exp(jax.lax.stop_gradient(la)))
    else:
        x, xdt, bm, cm, la, decay = jax.checkpoint(lambda *a: _before(*a, cfg, heads))(u, dt_raw, small)
    with jax.named_scope("ssm_scan"):
        y = ssd(xdt, bm, cm, la, head_dim=cfg.ssm_head_dim, groups=cfg.ssm_groups, chunk=cfg.ssm_chunk, mesh=mesh)
    if kernels:
        with jax.named_scope("ssm_mix"):
            o = ssm_mix.after(y, x, z, small["ssm_D"], small["ssm_norm"], groups=cfg.ssm_groups, eps=cfg.rms_eps)
    else:
        o = jax.checkpoint(lambda *a: _after(*a, cfg, heads))(y, x, z, small)
    with jax.named_scope("attn_proj"):
        return o @ w["ssm_out"].astype(dt_), decay


def _forward(cfg, kind, mesh, rules, h, w, positions):
    out, decay = mamba2_mixer(cfg, kind, mesh, h, w)
    return out, {"ssm_decay": decay}


def _check(cfg, kind) -> None:
    assert kind.n_heads % cfg.ssm_groups == 0, "a group is a whole number of heads"


MAMBA2 = Mixer(init_mamba2, mamba2_axes, _forward, SAVED_NAMES, mean_statistic=("ssm_decay", "ssm_decay_mean"),
               check=_check)
