"""Rotary position embedding as the model's layers turn q and k: half-split
pairs turned as whole heads (`_turn_whole`, with a backward pass of its own),
over a leading share of a head's columns, at theta's powers or YaRN's
frequencies (`_rotary`: what a `LayerKind` says of it)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _swap_halves(x: jax.Array, half: int) -> jax.Array:
    """x with the two ``half``-column halves of each block of ``2 * half``
    columns exchanged: the whole last axis moved ``half`` columns up and down
    (`lax.pad` with one negative edge: zeros enter, nothing of x is cut out
    or joined) and one of the two chosen by column.  On the TPU XLA fuses
    this into its consumer as lane rotations of whole vregs; `jnp.roll`'s
    slices and `concatenate` leave the fusion as 64-lane arrays in HBM
    (`tools/rope_probe.py`; PERF.md section 6, PR 39)."""
    edge, zero = [(0, 0, 0)] * (x.ndim - 1), jnp.zeros((), x.dtype)
    up = jax.lax.pad(x, zero, edge + [(half, -half, 0)])    # up[..., i] = x[..., i - half]
    down = jax.lax.pad(x, zero, edge + [(-half, half, 0)])  # down[..., i] = x[..., i + half]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where(lane % (2 * half) < half, down, up)


def _turned(x: jax.Array, swapped: jax.Array, cos: jax.Array, sin: jax.Array, rot: int) -> jax.Array:
    """``x * cos + swapped * sin`` in float32, cast back to x's dtype; the
    columns from ``rot`` on are x's own."""
    xf = x.astype(jnp.float32)
    out = xf * cos + swapped * sin
    if rot < x.shape[-1]:  # chosen, not multiplied by (1, 0): what is not finite there stays where it was
        out = jnp.where(jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) < rot, out, xf)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _turn_whole(x: jax.Array, cos: jax.Array, sin: jax.Array, half: int, rot: int) -> jax.Array:
    """A head turned as a whole: ``x * cos + swapped(x) * sin`` in float32
    under tables as wide as the head, cos = [c, c, 1] and sin = [-s, s, 0]
    over (first half, second half, columns that pass).  x is cast first and
    swapped in float32: on the TPU XLA hands the product's float32 result to
    the turn unrounded, as it did to the two-halves form, and a swap of x in
    its own dtype would make the product round it first."""
    return _turned(x, _swap_halves(x.astype(jnp.float32), half), cos, sin, rot)


def _turn_whole_bwd(half: int, rot: int, tables, g: jax.Array):
    """The transpose of a turn is the turn by the opposite angle:
    swapped(g * sin) = swapped(g) * -sin, element by element what
    differentiating the two halves gives, in one fused pass where autodiff's
    transposes of the two pads are three.  g arrives in x's dtype from a
    kernel or a sum, rounded already, so it is swapped as it is and cast
    after: the same values, and half the bytes read.  The tables are
    constants of the program (positions and frequencies): no gradient."""
    cos, sin = tables
    return _turned(g, _swap_halves(g, half).astype(jnp.float32), cos, -sin, rot), None, None


_turn_whole.defvjp(lambda x, cos, sin, half, rot: (_turn_whole(x, cos, sin, half, rot), (cos, sin)), _turn_whole_bwd)


def _turn(x: jax.Array, positions: jax.Array, inv_freq: jax.Array, factor: float, rot: int) -> jax.Array:
    """The leading ``rot`` columns of x [B, S, H, D] turned by positions
    [B, S] x inv_freq [rot / 2] in half-split pairs (i, i + rot / 2), cos and
    sin times ``factor``; the other columns pass through.  Float32, two
    products and one sum an element, under tables as wide as the head, so
    that no piece of q or k is narrower than the head is (`_turn_whole`)."""
    import numpy as np

    D, half = x.shape[-1], rot // 2
    lane = np.arange(D)
    angles = positions[..., None].astype(jnp.float32) * jnp.take(inv_freq, lane % half)  # [B, S, D]
    cos = jnp.where(lane < rot, jnp.cos(angles) * np.float32(factor), 1.0)
    sin = jnp.where(lane < rot, jnp.sin(angles) * np.where(lane < half, -factor, factor).astype(np.float32), 0.0)
    return _turn_whole(x, cos[:, :, None, :], sin[:, :, None, :], half, rot)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: [B, S, H, Dh], positions: [B, S] (global)."""
    d_half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    return _turn(x, positions, freqs, 1.0, x.shape[-1])


def yarn_frequencies(theta: float, rot_dim: int, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies for the rot_dim / 2 rotary pairs, float64 on
    the host: pair i turns by theta**(-2i/rot_dim) where it makes more than
    beta_fast turns over the original length, by that over ``factor`` where it
    makes fewer than beta_slow, and by their blend along a linear ramp between
    the two correction dimensions (rounded outward, as the published code)."""
    import math

    import numpy as np

    def correction_dim(turns: float) -> float:
        return rot_dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot_dim - 1)
    pair = np.arange(rot_dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * pair / rot_dim)
    ramp = np.clip((pair - low) / ((high if high != low else high + 0.001) - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rotary(x: jax.Array, positions: jax.Array, kind) -> jax.Array:
    """RoPE as the layer's kind has it: over the leading ``rotary_fraction``
    of a head's columns (half-split pairs inside that part, the rest passes
    through), at theta's powers or YaRN's frequencies — a constant of the
    program — with cos and sin times YaRN's attention factor."""
    if kind.rotary_fraction == 1.0 and kind.yarn is None:
        return _rope(x, positions, kind.rope_theta)
    import numpy as np

    rot = int(x.shape[-1] * kind.rotary_fraction)
    half = rot // 2
    if kind.yarn is None:
        inv_freq, factor = kind.rope_theta ** (-np.arange(half, dtype=np.float64) / half), 1.0
    else:
        inv_freq, factor = yarn_frequencies(kind.rope_theta, rot, *kind.yarn[:4]), kind.yarn[4]
    return _turn(x, positions, jnp.asarray(inv_freq, jnp.float32), factor, rot)
