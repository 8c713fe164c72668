"""The softmax-attention mixers (`LayerKind.mixer`), three entries that share
a tail — q, k, v position-major as their projections leave them, [B, S, heads,
D] -> the attention call (or, where the model has an indexer, the keys it
selects), which reads and writes a head as a column block of a position's row
-> the gate (a head's, or a column's) -> `wo`:

"attention": q, k and v are products of the layer's normed input, position by
position, under the model's QK-norm and the kind's RoPE.

"mla": latent attention (the model's `mla_*` widths; `_mla_qkv`) at the kind's
heads, its `mla_rope_dim` columns rotated where `rotary_fraction` is not 0 and
plain content where it is (NoPE).

"cca": attention inside a compressed latent (compressed convolutional
attention, arXiv:2510.04476; `_cca_qkv`) — between the projections and the
attention call a causal convolution a channel, one a head over sequence and
channels (both of kernel 2), the mean of the un-convolved q and k added back,
half of the KV heads' values taken from the position before, and an L2 norm a
head.

Learned sparse attention is no kind's: where `cfg.dsa_index_heads > 0` every
layer of the model carries an indexer (`_index_operands`, its `wi_*` leaves)
and attends to the keys it selects (`_sparse_attention`).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from torchft_tpu.models.mixer import Mixer, _norm_init, _norm_start, _unit
from torchft_tpu.models.rope import _rope, _rotary
from torchft_tpu.ops import flash_attention, rms_norm
from torchft_tpu.ops.attention import SAVED_NAMES, heads_indicator
from torchft_tpu.ops.sparse_attention import SAVED_NAMES as DSA_SAVED_NAMES
from torchft_tpu.parallel.sharding import constrain


def _attention(cfg, mesh, q, k, v, kind):
    """q/k/v: [B, S, H|KV, Dh] -> [B, S, H, Dv].  The sequence-parallel forms
    (no cell runs them) take and give head-major, and are turned to here."""
    seq_parallel = (
        cfg.attention in ("ring", "ulysses")
        and mesh is not None
        and "sequence" in mesh.axis_names
        and mesh.shape["sequence"] > 1
    )
    if cfg.attention != "flash" and not seq_parallel:
        # Trace-time (once per compile), not per step.
        import warnings

        warnings.warn(
            f"attention={cfg.attention!r} requested but the mesh has no "
            ">1-sized 'sequence' axis; falling back to single-shard flash "
            "attention",
            stacklevel=2,
        )
    if seq_parallel:
        if cfg.attention == "ring":
            from torchft_tpu.ops.ring_attention import ring_attention_sharded as fn

            # The ring body assumes equal q/kv head counts.
            broadcast_gqa = cfg.n_kv_heads != kind.n_heads
        else:
            from torchft_tpu.ops.ulysses import ulysses_attention_sharded as fn

            # Ulysses keeps GQA compressed through the all_to_all (the local
            # flash kernel broadcasts groups afterwards) unless the kv heads
            # PER TENSOR-PARALLEL SHARD don't tile the sequence axis — the
            # divisibility the local body actually requires.
            tp = mesh.shape.get("tensor", 1) if "tensor" in mesh.axis_names else 1
            broadcast_gqa = (
                cfg.n_kv_heads != kind.n_heads
                and (cfg.n_kv_heads // tp) % mesh.shape["sequence"] != 0
            )
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        if broadcast_gqa:
            rep = kind.n_heads // cfg.n_kv_heads
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        kwargs = {}
        if cfg.attention == "ring":
            kwargs["layout"] = cfg.ring_layout
        return fn(
            mesh, q, k, v, causal=True,
            batch_axis="data" if "data" in mesh.axis_names else None,
            head_axis="tensor" if "tensor" in mesh.axis_names else None,
            seq_axis="sequence",
            **kwargs,
        ).transpose(0, 2, 1, 3)
    return flash_attention(q, k, v, causal=True, mesh=mesh, window=kind.window, block_length=cfg.bd_block_length)


def _mla_qkv(cfg, kind, h, w, positions):
    """Latent attention's q, k [B, S, H, nope + rope] and v [B, S, H, v]
    from the normed input h [B, S, E]: the keys' and values' content
    through the low-rank path, one rotary key for all heads — or, where the
    kind has no rotation (`rotary_fraction` 0), those columns as they are
    projected: content like the others, the one key still every head's."""
    B, S, _ = h.shape
    H, Dn, Dr, Dv, R = kind.n_heads, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim, cfg.mla_kv_rank
    q = (h @ w["wq"].astype(cfg.dtype)).reshape(B, S, H, Dn + Dr)
    latent = h @ w["wkv_a"].astype(cfg.dtype)                       # [B, S, R + Dr]
    with jax.named_scope("norm"):
        kv = rms_norm(latent[..., :R], w["kv_norm"], cfg.rms_eps)
    kv = kv @ w["wkv_b"].astype(cfg.dtype)
    kv = kv.reshape(B, S, H, Dn + Dv)
    if not kind.rotary_fraction:
        k = jnp.concatenate([kv[..., :Dn], jnp.broadcast_to(latent[..., None, R:], (B, S, H, Dr))], axis=-1)
        return q, k, kv[..., Dn:]
    q_rope = _rope(q[..., Dn:], positions, kind.rope_theta)
    k_rope = _rope(latent[..., None, R:], positions, kind.rope_theta)  # [B, S, 1, Dr]
    q = jnp.concatenate([q[..., :Dn], q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :Dn], jnp.broadcast_to(k_rope, (B, S, H, Dr))], axis=-1)
    return q, k, kv[..., Dn:]


def _index_operands(cfg, h, w, positions):
    """The indexer's operands from the normed input h [B, S, E], which it
    reads DETACHED: index queries [B, J, S, Di] (64 columns, no lane multiple: head-major) and the one index key head
    [B, S, Di], both after RoPE, and the per-query head weights [B, S, J] f32
    with the two scale factors (J**-0.5, Di**-0.5) in them."""
    B, S, _ = h.shape
    J, Di = cfg.dsa_index_heads, cfg.dsa_index_dim
    hd = jax.lax.stop_gradient(h)
    a = _rope((hd @ w["wi_q"].astype(cfg.dtype)).reshape(B, S, J, Di), positions, cfg.rope_theta)
    b = hd @ w["wi_k"].astype(cfg.dtype)
    with jax.named_scope("norm"):
        b = _layer_norm(b, w["wi_k_norm"], w["wi_k_bias"], cfg.rms_eps)
    b = _rope(b[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    weights = (hd @ w["wi_w"].astype(cfg.dtype)).astype(jnp.float32) * (J ** -0.5 * Di ** -0.5)
    return a.transpose(0, 2, 1, 3), b, weights


def _sparse_attention(cfg, mesh, h, w, positions, q, k, v):
    """Attention over the keys the layer's indexer selects; q/k/v [B, S, H|KV, Dh].
    Returns (attention [B, S, H, Dh], {"dsa_index_loss", "dsa_selected"})."""
    from torchft_tpu.ops.sparse_attention import sparse_attention

    with jax.named_scope("dsa_index"):
        a, b, weights = _index_operands(cfg, h, w, positions)
    # `sparse_attention` names its own parts: dsa_select, attn, dsa_index
    attn, index_loss, selected = sparse_attention(q, k, v, a, b, weights, topk=cfg.dsa_topk, mesh=mesh)
    return attn, {"dsa_index_loss": index_loss, "dsa_selected": selected.astype(jnp.uint32)}


def _layer_norm(x, w, b, eps):
    """LayerNorm over the last axis, f32 statistics."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _before(x: jax.Array) -> jax.Array:
    """x [B, H, S, D] one position on: ``out[t] = x[t - 1]``, zeros before the
    first (`lax.pad` with a negative edge; its transpose is the same move the
    other way)."""
    return jax.lax.pad(x, jnp.zeros((), x.dtype), [(0, 0, 0), (0, 0, 0), (1, -1, 0), (0, 0, 0)])


def _cca_qkv(cfg, kind, h, w, positions):
    """Compressed convolutional attention's q [B, S, H, D] and k, v
    [B, S, G, D] from the normed input h [B, S, E] (arXiv:2510.04476), worked
    head-major (its convolutions are batched products with the heads leading)
    and turned once where it hands them over.  The projections are `attn_proj`'s;
    what lies between them and RoPE — `cca_mix` — mixes positions and
    channels, all of it linear but the norm, so its backward pass is the
    mirrored shifts and the transposed products:

        z = [q~ ; k~], the H + G projected heads side by side
        z0_t = a1 * z_t + a0 * z_{t-1} + b0                (a weight a channel and tap)
        z1_{t,h} = z0_{t,h} A_{h,1} + z0_{t-1,h} A_{h,0} + b1_h    (a [D, D] matrix a head and tap)
        mu_j = (q~_j + k~_{g(j)}) / 2;  q_j = z1_{q,j} + mu_j;  k_g = z1_{k,g} + mean_{j in g} mu_j
        q^ = sqrt(D) q / |q|;  k^ = tau_g sqrt(D) k / |k|   (float32)
        v = the first half of the KV heads' values as projected, the second half's from the position before

    Elementwise work is float32 inside its fusion and lands in the compute
    type; the convolution a head is one batched product over both taps."""
    B, S, _ = h.shape
    H, G, D, dt = kind.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.dtype
    f32 = jnp.float32

    def heads(y, n):  # [B, S, n * D] -> [B, n, S, D]
        return y.reshape(B, S, n, D).transpose(0, 2, 1, 3)

    q0, k0 = heads(h @ w["wq"].astype(dt), H), heads(h @ w["wk"].astype(dt), G)
    v = heads(h @ w["wv"].astype(dt), G)
    with jax.named_scope("cca_mix"):
        v = jnp.concatenate([v[:, : G // 2], _before(v[:, G // 2:])], axis=1)
        z = jnp.concatenate([q0, k0], axis=1)                                  # [B, H + G, S, D]
        taps = w["cca_conv0"].astype(f32).reshape(2, H + G, 1, D)
        bias0 = w["cca_bias0"].astype(f32).reshape(H + G, 1, D)
        z0 = (taps[1] * z.astype(f32) + taps[0] * _before(z).astype(f32) + bias0).astype(dt)
        # both taps in one product a head: [z0_{t-1} ; z0_t] [S, 2D] times [A_0 ; A_1] [2D, D]
        mats = w["cca_conv1"].astype(dt).reshape(H + G, 2 * D, D)
        z1 = jnp.einsum("bhsc,hcd->bhsd", jnp.concatenate([_before(z0), z0], axis=-1), mats)
        z1 = z1.astype(f32) + w["cca_bias1"].astype(f32)[:, None, :]
        mu = 0.5 * (q0.astype(f32).reshape(B, G, H // G, S, D) + k0.astype(f32)[:, :, None])
        q = z1[:, :H] + mu.reshape(B, H, S, D)
        k = z1[:, H:] + jnp.mean(mu, axis=2)
        q = q * (jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True)) * D ** 0.5)
        k = k * (jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True)) * D ** 0.5
                 * w["cca_temp"].astype(f32)[:, None, None])
    q, k = (_rotary(a.transpose(0, 2, 1, 3), positions, kind).astype(dt) for a in (q, k))
    return q, k, v.transpose(0, 2, 1, 3)


def _plain_qkv(cfg, kind, h, w, positions):
    """q [B, S, H, Dh] and k, v [B, S, KV, Dh]: products of the normed input h
    [B, S, E] under the model's QK-norm and the kind's RoPE."""
    B, S, _ = h.shape
    H, KV, Dh = kind.n_heads, cfg.n_kv_heads, cfg.d_head
    q = h @ w["wq"].astype(cfg.dtype)
    if cfg.qk_norm:
        with jax.named_scope("norm"):
            q = rms_norm(q, w["q_norm"], cfg.rms_eps)
    q = q.reshape(B, S, H, Dh)
    k = h @ w["wk"].astype(cfg.dtype)
    if cfg.qk_norm:
        with jax.named_scope("norm"):
            k = rms_norm(k, w["k_norm"], cfg.rms_eps)
    k = k.reshape(B, S, KV, Dh)
    if cfg.qk_norm_per_head:
        with jax.named_scope("norm"):
            q, k = rms_norm(q, _unit(cfg, w["q_norm"]), cfg.rms_eps), rms_norm(k, _unit(cfg, w["k_norm"]), cfg.rms_eps)
    v = (h @ w["wv"].astype(cfg.dtype)).reshape(B, S, KV, Dh)
    if kind.rotary_fraction:  # 0: no position term, q and k are the projections
        q = _rotary(q, positions, kind)
        k = _rotary(k, positions, kind)
    return q, k, v


def _forward(qkv):
    """A `Mixer.forward` around ``qkv(cfg, kind, h, w, positions)``, which
    gives q, k, v [B, S, heads, D]."""

    def forward(cfg, kind, mesh, rules, h, w, positions):
        B, S, _ = h.shape
        with jax.named_scope("attn_proj"):
            q, k, v = qkv(cfg, kind, h, w, positions)
            if cfg.attn_head_gate:
                head_gate = jax.nn.sigmoid((h @ w["attn_gate"].astype(cfg.dtype)).astype(jnp.float32)).astype(cfg.dtype)
            if cfg.attn_out_gate:  # a gate a column, [B, S, H * Dv] as the attention call leaves its output
                out_gate = jax.nn.sigmoid((h @ w["attn_out_gate"].astype(cfg.dtype)).astype(jnp.float32)).astype(cfg.dtype)
            q = constrain(q, ("batch", "seq", "heads", None), mesh, rules)
            k = constrain(k, ("batch", "seq", "kv_heads", None), mesh, rules)
            v = constrain(v, ("batch", "seq", "kv_heads", None), mesh, rules)
        dsa = None
        if cfg.dsa_index_heads:
            attn, dsa = _sparse_attention(cfg, mesh, h, w, positions, q, k, v)
        else:
            scope = "bd_attn" if cfg.bd_block_length is not None else "attn" if kind.window is None else "attn_window"
            with jax.named_scope(scope):
                attn = _attention(cfg, mesh, q, k, v, kind)  # [B, S, H, Dv]
        with jax.named_scope("attn_proj"):
            d_v = attn.shape[-1]
            attn = attn.reshape(B, S, kind.n_heads * d_v)
            if cfg.attn_head_gate:  # a head's gate over its columns: a product with 0 / 1, exact, and no [B, S, H, Dv] array
                attn = attn * jnp.einsum("bsh,hc->bsc", head_gate, heads_indicator(kind.n_heads, d_v).astype(cfg.dtype),
                                         precision=jax.lax.Precision.HIGHEST)
            if cfg.attn_out_gate:
                attn = attn * out_gate
            return attn @ w["wo"].astype(cfg.dtype), dsa

    return forward


# -- the leaves: the stack's key split in eight gives the four projections theirs (the feed-forward draws from the
# other four), the model-wide additions each a key folded out of it

def _shared_axes(cfg) -> Dict[str, Any]:
    """The axes of what the model's switches add to every attention layer: the indexer, the head gate."""
    axes: Dict[str, Any] = {}
    if cfg.dsa_index_heads:
        axes.update({"wi_q": ("layers", "embed", None), "wi_k": ("layers", "embed", None),
                     "wi_k_norm": ("layers", None), "wi_k_bias": ("layers", None),
                     "wi_w": ("layers", "embed", None)})
    if cfg.attn_head_gate:
        axes["attn_gate"] = ("layers", "embed", "heads")
    if cfg.attn_out_gate:
        axes["attn_out_gate"] = ("layers", "embed", "heads")
    return axes


def _init_shared(key, cfg, L: int, kind) -> Dict[str, Any]:
    pd, E = cfg.param_dtype, cfg.d_model
    layers: Dict[str, Any] = {}
    if cfg.dsa_index_heads:
        J, Di = cfg.dsa_index_heads, cfg.dsa_index_dim
        kq, kk, kw = jax.random.split(jax.random.fold_in(key, 2), 3)
        layers.update(
            {
                "wi_q": _norm_init(kq, (L, E, J * Di), E, pd),
                "wi_k": _norm_init(kk, (L, E, Di), E, pd),
                "wi_k_norm": jnp.ones((L, Di), pd),
                "wi_k_bias": jnp.zeros((L, Di), pd),
                "wi_w": _norm_init(kw, (L, E, J), E, pd),
            }
        )
    if cfg.attn_head_gate:
        layers["attn_gate"] = _norm_init(jax.random.fold_in(key, 3), (L, E, kind.n_heads), E, pd)
    if cfg.attn_out_gate:  # as wide as the heads' joined VALUES: the plain kind's d_head, what its `wo` takes
        layers["attn_out_gate"] = _norm_init(jax.random.fold_in(key, 7), (L, E, kind.n_heads * cfg.d_head), E, pd)
    return layers


def _plain_axes(cfg, kind) -> Dict[str, Any]:
    axes = {"wq": ("layers", "embed", "heads"), "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"), "wo": ("layers", "heads", "embed")}
    if cfg.qk_norm:
        axes.update({"q_norm": ("layers", "heads"), "k_norm": ("layers", "kv_heads")})
    if cfg.qk_norm_per_head:
        axes.update({"q_norm": ("layers", None), "k_norm": ("layers", None)})
    return dict(axes, **_shared_axes(cfg))


def _init_plain(key, cfg, L: int, kind) -> Dict[str, Any]:
    pd, E, H, KV, Dh = cfg.param_dtype, cfg.d_model, kind.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 8)
    layers = {
        "wq": _norm_init(ks[0], (L, E, H * Dh), E, pd),
        "wk": _norm_init(ks[1], (L, E, KV * Dh), E, pd),
        "wv": _norm_init(ks[2], (L, E, KV * Dh), E, pd),
        "wo": _norm_init(ks[3], (L, H * Dh, E), H * Dh, pd),
    }
    if cfg.qk_norm:
        layers.update({"q_norm": jnp.ones((L, H * Dh), pd), "k_norm": jnp.ones((L, KV * Dh), pd)})
    if cfg.qk_norm_per_head:
        layers.update({"q_norm": _norm_start(cfg)((L, Dh), pd), "k_norm": _norm_start(cfg)((L, Dh), pd)})
    return dict(layers, **_init_shared(key, cfg, L, kind))


def _mla_axes(cfg, kind) -> Dict[str, Any]:
    # with the shared leaves: a model without a pattern may gate its latent heads (`check` is a pattern's)
    return dict({"wq": ("layers", "embed", "heads"), "wkv_a": ("layers", "embed", None), "kv_norm": ("layers", None),
                 "wkv_b": ("layers", None, "heads"), "wo": ("layers", "heads", "embed")}, **_shared_axes(cfg))


def _init_mla(key, cfg, L: int, kind) -> Dict[str, Any]:
    pd, E, H = cfg.param_dtype, cfg.d_model, kind.n_heads
    R, Dq = cfg.mla_kv_rank, cfg.mla_nope_dim + cfg.mla_rope_dim
    ks = jax.random.split(key, 8)
    return dict({
        "wq": _norm_init(ks[0], (L, E, H * Dq), E, pd),
        "wkv_a": _norm_init(ks[1], (L, E, R + cfg.mla_rope_dim), E, pd),
        "kv_norm": jnp.ones((L, R), pd),
        "wkv_b": _norm_init(ks[2], (L, R, H * (cfg.mla_nope_dim + cfg.mla_v_dim)), R, pd),
        "wo": _norm_init(ks[3], (L, H * cfg.mla_v_dim, E), H * cfg.mla_v_dim, pd),
    }, **_init_shared(key, cfg, L, kind))


def _cca_axes(cfg, kind) -> Dict[str, Any]:
    return dict(_plain_axes(cfg, kind), **{
        "cca_conv0": ("layers", None, None), "cca_bias0": ("layers", None),
        "cca_conv1": ("layers", None, None, None, None), "cca_bias1": ("layers", None, None),
        "cca_temp": ("layers", None)})


def _init_cca(key, cfg, L: int, kind) -> Dict[str, Any]:
    pd, KV, Dh = cfg.param_dtype, cfg.n_kv_heads, cfg.d_head
    C = kind.n_heads + KV  # the convolutions run over q's and k's heads side by side
    k0, k1 = jax.random.split(jax.random.fold_in(key, 4))
    return dict(_init_plain(key, cfg, L, kind), **{
        "cca_conv0": _norm_init(k0, (L, 2, C * Dh), 2, pd),         # [tap, channel]: tap 1 the position itself
        "cca_bias0": jnp.zeros((L, C * Dh), pd),
        "cca_conv1": _norm_init(k1, (L, C, 2, Dh, Dh), 2 * Dh, pd),  # [head, tap, channel in, channel out]
        "cca_bias1": jnp.zeros((L, C, Dh), pd),
        "cca_temp": jnp.ones((L, KV), pd),
    })


def _no_norm_no_gate(cfg, why: str) -> None:
    assert not (cfg.qk_norm or cfg.qk_norm_per_head or cfg.attn_head_gate or cfg.attn_out_gate), why


def _check_mla(cfg, kind) -> None:
    assert cfg.mla_kv_rank, "the latent widths are the model's, the layers that use them the pattern's"
    _no_norm_no_gate(cfg, "latent attention has no QK-norm and no head gate of the model's")


def _check_cca(cfg, kind) -> None:
    _no_norm_no_gate(cfg, "compressed attention norms its own heads and has no gate")
    assert cfg.n_kv_heads % 2 == 0 and kind.n_heads % cfg.n_kv_heads == 0


# What a rematerialised layer keeps: the attention output and row statistics, the indexer's selection where there is one.
_KEPT = SAVED_NAMES + DSA_SAVED_NAMES
ATTENTION = Mixer(_init_plain, _plain_axes, _forward(_plain_qkv), _KEPT)
MLA = Mixer(_init_mla, _mla_axes, _forward(_mla_qkv), _KEPT, check=_check_mla)
CCA = Mixer(_init_cca, _cca_axes, _forward(_cca_qkv), _KEPT, check=_check_cca)
