"""What a mixer is to the model, and what the mixers' files share.

A decoder block is norm -> mixer -> residual merge -> (feed-forward).  The
model (`models/transformer.py`) knows a mixer by its entry in
`models/mixers.MIXERS` alone — a `Mixer`: the leaves it owns and their axes,
its forward pass from the normed input to what the residual takes, what a
rematerialised layer keeps of it, the statistic it counts, the configurations
it refuses.  A mixer's file builds its entry; nothing here or there imports
the model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Mixer:
    """A mixer as the model knows it: an entry of `models/mixers.MIXERS`."""

    # (key, cfg, L, kind) -> the mixer's leaves for a stack of L layers, each [L, ...].  `key` is the STACK'S key:
    # the mixer splits or folds it as its leaves always were drawn.
    init: Callable[..., Dict[str, Any]]
    # (cfg, kind) -> the logical axis names of exactly those leaves (parallel/sharding.py)
    axes: Callable[..., Dict[str, Any]]
    # (cfg, kind, mesh, rules, h, w, positions) -> (what the residual takes [B, S, E], statistics {name: value} or
    # None) from the normed input h [B, S, E], the layer's leaves w and the positions [B, S]
    forward: Callable[..., Tuple[jax.Array, Optional[Dict[str, jax.Array]]]]
    # what `remat_keeps_attention` keeps of a layer: `checkpoint_name`s (ops/*.SAVED_NAMES)
    saved_names: Tuple[str, ...] = ()
    # (the statistic's name among `forward`'s, its counter's in `loss_and_counters`): a scalar a layer, reported as
    # its mean over the layers of this mixer — or None
    mean_statistic: Optional[Tuple[str, str]] = None
    # (cfg, kind): asserts what the mixer needs of the model's configuration — or None: it refuses nothing
    check: Optional[Callable[..., None]] = None


def _norm_init(k, shape, fan_in, pd):
    return (jax.random.normal(k, shape, pd) * (fan_in ** -0.5)).astype(pd)


def _unit(cfg, w):
    """A norm's weight as `rms_norm` takes it: w, or `1 + w` (float32) where the
    model's norm weights are zero-centred (`cfg.norm_unit_offset`: a block's two
    norms, the final norm and the per-head QK-norm's two — never a mixer's own
    head norm)."""
    return w.astype(jnp.float32) + 1.0 if cfg.norm_unit_offset else w


def _norm_start(cfg):
    """What such a norm's weight starts at: `jnp.zeros` where the norm is `1 + w`, else `jnp.ones`."""
    return jnp.zeros if cfg.norm_unit_offset else jnp.ones


def _l2(x):
    """x over its last axis' L2 norm (float32 in, float32 out): the delta-rule mixers' q and k, a head."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _causal_conv(z, taps):
    """A depthwise causal convolution over the sequence: z [B, S, C], taps
    [T, C] float32 with the LAST tap the position's own, ``out_t = sum_i
    taps[i] * z_{t - (T - 1) + i}``, zeros before the first position (`lax.pad`
    with a negative edge; its transpose is the same move the other way)."""
    n = taps.shape[0]
    out = taps[n - 1] * z
    for back in range(1, n):
        shifted = jax.lax.pad(z, jnp.zeros((), z.dtype), [(0, 0, 0), (back, -back, 0), (0, 0, 0)])
        out = out + taps[n - 1 - back] * shifted
    return out
