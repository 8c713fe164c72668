"""Flagship model: decoder-only transformer LM (Llama-3-class shape).

TPU-first design choices:
  - parameters are plain pytrees of jax.Arrays with per-layer weights
    *stacked* along a leading "layers" axis so the decoder runs as one
    ``lax.scan`` — one compiled layer body instead of L unrolled copies;
  - compute in bfloat16 (MXU-native), parameters and reductions in float32;
  - hot ops route through torchft_tpu.ops: fused pallas RMSNorm and flash
    attention; ring attention over the "sequence" mesh axis for long
    context;
  - ``jax.checkpoint`` on the layer body: rematerialize instead of storing
    per-layer activations (HBM is the bottleneck);
  - every array axis has a logical name; sharding is applied by annotation
    (parallel/sharding.py), never hand-placed collectives.

Reference parity note: torchft trains user torch models (CIFAR CNN in
train_ddp.py; Llama via torchtitan, README.md:67-74); this module is the TPU
build's first-party equivalent of that model class.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops import flash_attention, rms_norm
from torchft_tpu.parallel.sharding import ShardingRules, constrain


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1408
    max_seq: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16          # activation/compute dtype (MXU-native)
    param_dtype: Any = jnp.float32
    remat: bool = True
    # Attention backend: "flash" (pallas kernel / XLA fallback), "ring"
    # (sequence-parallel K/V rotation), or "ulysses" (all-to-all head<->seq
    # resharding) — the latter two engage over the mesh "sequence" axis.
    attention: str = "flash"
    # Sequence layout for attention="ring": "contiguous" or "zigzag"
    # (balanced causal work, ops/ring_attention.py).  With "zigzag" the
    # CALLER feeds tokens/targets already permuted by
    # ops.ring_attention.to_zigzag(..., n_shards=mesh sequence size); the
    # model ropes with the matching original positions internally, and the
    # mean CE loss is permutation-invariant so training needs no other
    # change.
    ring_layout: str = "contiguous"
    # Unroll factor for the scan-over-layers (1 = pure scan).  Unrolling
    # lets XLA fuse/pipeline across layer boundaries at the cost of compile
    # time; worthwhile on the perf path, keep 1 for fast test iteration.
    # >= n_layers switches to a static Python loop (constant-folded layer
    # indexing — see forward_with_aux), the fastest measured form.
    scan_unroll: int = 1
    # Mixture-of-experts: > 0 replaces the dense MLP with moe_experts
    # experts (stacked, shardable over the "expert" mesh axis).
    # moe_capacity_factor None = dropless: the sorted path with grouped
    # matmuls, every expert on one device (models/moe.py); a number = the
    # capacity-bound dense dispatch an "expert" mesh axis runs.
    # moe_norm_topk: renormalise the k kept gates (OLMoE does not).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: Optional[float] = 1.25
    moe_norm_topk: bool = True
    moe_aux_coef: float = 0.01       # load-balance loss
    moe_z_coef: float = 0.0          # router z-loss
    # RMSNorm over the whole projected query and key, each with a weight of
    # its own, before the split into heads and RoPE (OLMoE's attention).
    qk_norm: bool = False
    rms_eps: float = 1e-6

    def __post_init__(self) -> None:
        assert self.attention in ("flash", "ring", "ulysses"), (
            f"unknown attention backend {self.attention!r}; "
            "expected 'flash', 'ring', or 'ulysses'"
        )
        assert self.ring_layout in ("contiguous", "zigzag"), (
            f"unknown ring_layout {self.ring_layout!r}"
        )

    @property
    def d_head(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


# Logical axis names for every parameter (see parallel/sharding.py).
def param_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Logical axis names for every parameter, keyed like init_params'
    tree — feed to FTMesh.shard_params to place the model on a mesh."""
    layer = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if cfg.qk_norm:
        layer.update({"q_norm": ("layers", "heads"), "k_norm": ("layers", "kv_heads")})
    if cfg.moe_experts > 0:
        layer.update(
            {
                "router": ("layers", "embed", "expert"),
                "w_gate": ("layers", "expert", "embed", "mlp"),
                "w_up": ("layers", "expert", "embed", "mlp"),
                "w_down": ("layers", "expert", "mlp", "embed"),
            }
        )
    return {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(key: jax.Array, cfg: TransformerConfig) -> Dict[str, Any]:
    """Initializes the transformer parameter pytree (layers stacked on a
    leading axis for the scan-over-layers; param_dtype precision)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    pd = cfg.param_dtype
    E, H, KV, Dh, F, L = (
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff,
        cfg.n_layers,
    )

    def norm_init(k, shape, fan_in):
        return (jax.random.normal(k, shape, pd) * (fan_in ** -0.5)).astype(pd)

    ks = jax.random.split(k_layers, 8)
    layers = {
        "attn_norm": jnp.ones((L, E), pd),
        "wq": norm_init(ks[0], (L, E, H * Dh), E),
        "wk": norm_init(ks[1], (L, E, KV * Dh), E),
        "wv": norm_init(ks[2], (L, E, KV * Dh), E),
        "wo": norm_init(ks[3], (L, H * Dh, E), H * Dh),
        "mlp_norm": jnp.ones((L, E), pd),
    }
    if cfg.qk_norm:
        layers.update({"q_norm": jnp.ones((L, H * Dh), pd), "k_norm": jnp.ones((L, KV * Dh), pd)})
    if cfg.moe_experts > 0:
        X = cfg.moe_experts
        kr, kg, ku, kd = jax.random.split(ks[7], 4)
        layers.update(
            {
                "router": norm_init(kr, (L, E, X), E),
                "w_gate": norm_init(kg, (L, X, E, F), E),
                "w_up": norm_init(ku, (L, X, E, F), E),
                "w_down": norm_init(kd, (L, X, F, E), F),
            }
        )
    else:
        layers.update(
            {
                "w_gate": norm_init(ks[4], (L, E, F), E),
                "w_up": norm_init(ks[5], (L, E, F), E),
                "w_down": norm_init(ks[6], (L, F, E), F),
            }
        )
    return {
        "embed": norm_init(k_embed, (cfg.vocab_size, E), E),
        "layers": layers,
        "final_norm": jnp.ones((E,), pd),
        "lm_head": norm_init(k_head, (E, cfg.vocab_size), E),
    }


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: [B, S, H, Dh], positions: [B, S] (global)."""
    d_half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _attention(cfg: TransformerConfig, mesh, q, k, v):
    """q/k/v: [B, H|KV, S, Dh] head-major."""
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
            broadcast_gqa = cfg.n_kv_heads != cfg.n_heads
        else:
            from torchft_tpu.ops.ulysses import ulysses_attention_sharded as fn

            # Ulysses keeps GQA compressed through the all_to_all (the local
            # flash kernel broadcasts groups afterwards) unless the kv heads
            # PER TENSOR-PARALLEL SHARD don't tile the sequence axis — the
            # divisibility the local body actually requires.
            tp = mesh.shape.get("tensor", 1) if "tensor" in mesh.axis_names else 1
            broadcast_gqa = (
                cfg.n_kv_heads != cfg.n_heads
                and (cfg.n_kv_heads // tp) % mesh.shape["sequence"] != 0
            )
        if broadcast_gqa:
            rep = cfg.n_heads // cfg.n_kv_heads
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
        )
    return flash_attention(q, k, v, causal=True, mesh=mesh)


def _layer(cfg: TransformerConfig, mesh, rules: ShardingRules, x, w, positions):
    """One decoder block; x: [B, S, E]."""
    B, S, E = x.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head

    h = rms_norm(x, w["attn_norm"], cfg.rms_eps)
    q = h @ w["wq"].astype(cfg.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, w["q_norm"], cfg.rms_eps)
    q = q.reshape(B, S, H, Dh)
    k = h @ w["wk"].astype(cfg.dtype)
    if cfg.qk_norm:
        k = rms_norm(k, w["k_norm"], cfg.rms_eps)
    k = k.reshape(B, S, KV, Dh)
    v = (h @ w["wv"].astype(cfg.dtype)).reshape(B, S, KV, Dh)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    q = constrain(q.transpose(0, 2, 1, 3), ("batch", "heads", "seq", None), mesh, rules)
    k = constrain(k.transpose(0, 2, 1, 3), ("batch", "kv_heads", "seq", None), mesh, rules)
    v = constrain(v.transpose(0, 2, 1, 3), ("batch", "kv_heads", "seq", None), mesh, rules)
    attn = _attention(cfg, mesh, q, k, v)            # [B, H, S, Dh]
    attn = attn.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
    x = x + (attn @ w["wo"].astype(cfg.dtype))
    x = constrain(x, ("batch", "seq", "embed"), mesh, rules)

    h = rms_norm(x, w["mlp_norm"], cfg.rms_eps)
    if cfg.moe_experts > 0:
        from torchft_tpu.models.moe import moe_layer

        y, aux = moe_layer(
            h,
            w["router"],
            w["w_gate"],
            w["w_up"],
            w["w_down"],
            top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor,
            norm_topk=cfg.moe_norm_topk,
            dtype=cfg.dtype,
            mesh=mesh,
            rules=rules,
        )
        x = x + y
    else:
        gate = jax.nn.silu(h @ w["w_gate"].astype(cfg.dtype))
        up = h @ w["w_up"].astype(cfg.dtype)
        x = x + ((gate * up) @ w["w_down"].astype(cfg.dtype))
        aux = jnp.zeros((), jnp.float32)
    return constrain(x, ("batch", "seq", "embed"), mesh, rules), aux


def _decoder(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Embedding + decoder stack (everything before the lm head).
    tokens: [B, S] int32 -> (hidden [B, S, E], aux).  For a dense model aux
    is a zero scalar; for an MoE model it is the layers' router statistics
    (models/moe.py ``moe_layer``): ``balance``, ``z`` and ``dropped`` summed
    over the layers, ``tokens_per_expert`` [n_layers, n_experts] and
    ``chosen`` [n_layers, B, S, k]."""
    rules = rules or ShardingRules()
    B, S = tokens.shape
    pos = jnp.arange(S, dtype=jnp.int32)
    if (
        cfg.attention == "ring"
        and cfg.ring_layout == "zigzag"
        and mesh is not None
        and "sequence" in mesh.axis_names
        and mesh.shape["sequence"] > 1
    ):
        # Tokens arrive zigzag-permuted (see TransformerConfig.ring_layout);
        # rope must see each slot's ORIGINAL position.
        from torchft_tpu.ops.ring_attention import zigzag_permutation

        pos = jnp.asarray(
            zigzag_permutation(S, mesh.shape["sequence"]), dtype=jnp.int32
        )
    positions = jnp.broadcast_to(pos, (B, S))

    x = params["embed"].astype(cfg.dtype)[tokens]
    x = constrain(x, ("batch", "seq", "embed"), mesh, rules)

    def body(x, w):
        x, aux = _layer(cfg, mesh, rules, x, w, positions)
        return x, aux

    if cfg.remat:
        body = jax.checkpoint(body)
    if cfg.scan_unroll > 1 and cfg.scan_unroll >= cfg.n_layers:
        # Full unroll as a STATIC Python loop rather than lax.scan(unroll=L):
        # scan's internal layer slicing survives as dynamic-update-slice
        # fusions in the backward (profiled: ~17 ms/step of DUS on the v5e
        # flagship config); static integer indexing lets XLA constant-fold
        # the slices and fold the per-layer grad writes, measured ~4 ms/step
        # faster end-to-end.  Same math, different op association — results
        # agree with the scan path to fusion-order rounding, not bitwise
        # (pinned by test_scan_unroll_matches_scan).
        aux_total = jnp.zeros((), jnp.float32)
        aux_layers = []
        for i in range(cfg.n_layers):
            w_i = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            x, aux = body(x, w_i)
            if cfg.moe_experts > 0:
                aux_layers.append(aux)
            else:
                aux_total = aux_total + aux
        if cfg.moe_experts > 0:
            return x, _over_layers(jax.tree.map(lambda *a: jnp.stack(a), *aux_layers))
        return x, aux_total
    x, aux_layers = jax.lax.scan(
        body, x, params["layers"], unroll=cfg.scan_unroll
    )
    if cfg.moe_experts > 0:
        return x, _over_layers(aux_layers)
    return x, jnp.sum(aux_layers)


def _over_layers(stats: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The MoE layers' statistics stacked on a leading axis -> sums over
    the layers, the per-expert counts left per layer."""
    return {
        name: value if name in ("tokens_per_expert", "chosen") else jnp.sum(value, axis=0)
        for name, value in stats.items()
    }


def forward_with_aux(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> Tuple[jax.Array, jax.Array]:
    """tokens: [B, S] int32 -> (logits [B, S, vocab] f32, aux scalar f32 —
    the summed MoE load-balance loss; zero for dense models)."""
    x, aux = _decoder(params, tokens, cfg, mesh, rules)
    if cfg.moe_experts > 0:
        aux = aux["balance"]
    return head(params, x, cfg, mesh, rules), aux


def head(
    params: Dict[str, Any],
    x: jax.Array,
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> jax.Array:
    """Final norm + lm head: decoder output [B, S, E] -> logits [B, S, V].

    Shared by the dense path (forward_with_aux) and the pipelined path
    (parallel/pipeline.pipeline_loss_fn) so the two can never diverge."""
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    # bf16 operands on the MXU, f32 accumulation/output: full systolic-array
    # rate with f32 logits (an f32xf32 matmul runs at a fraction of MXU peak).
    logits = jnp.matmul(
        x, params["lm_head"].astype(cfg.dtype), preferred_element_type=jnp.float32
    )
    return constrain(logits, ("batch", "seq", "vocab"), mesh, rules)


def token_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token CE, computed as logsumexp - target_logit rather than
    materializing the full [B, S, vocab] log-softmax: the logits array is
    the single biggest activation, and one extra copy is pure HBM traffic."""
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - tgt)


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> jax.Array:
    """tokens: [B, S] int32 -> logits [B, S, vocab] (f32)."""
    return forward_with_aux(params, tokens, cfg, mesh, rules)[0]


def lm_head_loss(
    params: Dict[str, Any],
    x: jax.Array,
    cfg: TransformerConfig,
    targets: jax.Array,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> jax.Array:
    """Mean next-token CE from decoder output x [B, S, E].

    On a single TPU device this fuses the lm-head matmul with the CE
    reduction (ops/cross_entropy.py) so the f32 [B, S, vocab] logits —
    the single biggest activation, ~2 GB at the flagship config — never
    reach HBM in either direction of autodiff.  Sharded meshes and
    off-TPU backends keep the plain XLA formulation, whose shardings
    (e.g. vocab-parallel logsumexp) propagate natively."""
    from torchft_tpu.ops.cross_entropy import (
        fused_ce_applicable,
        fused_linear_cross_entropy,
    )

    B, S, E = x.shape
    if fused_ce_applicable(B * S, E, cfg.vocab_size, mesh):
        h = rms_norm(x, params["final_norm"], cfg.rms_eps)
        w = params["lm_head"].astype(cfg.dtype)
        return fused_linear_cross_entropy(
            h.reshape(B * S, E), w, targets.reshape(B * S)
        )
    return token_cross_entropy(head(params, x, cfg, mesh, rules), targets)


def loss_fn(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> jax.Array:
    """Next-token cross entropy; batch: {"tokens": [B,S], "targets": [B,S]}.

    MoE configs add moe_aux_coef * load-balance loss (Switch-style) and
    moe_z_coef * router z-loss.
    """
    return loss_and_counters(params, batch, cfg, mesh, rules)[0]


def loss_and_counters(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``loss_fn`` and what the model counted on the way, for a
    ``TrainStep(loss_has_counters=True)``: for an MoE model
    ``moe_tokens_per_expert`` ([n_layers, n_experts] int32, assignments sent
    to each expert) and ``moe_dropped`` (int32, assignments that reached no
    expert); for a dense model nothing."""
    x, aux = _decoder(params, batch["tokens"], cfg, mesh, rules)
    ce = lm_head_loss(params, x, cfg, batch["targets"], mesh, rules)
    if cfg.moe_experts == 0:
        return ce, {}
    loss = ce + cfg.moe_aux_coef * aux["balance"]
    if cfg.moe_z_coef:
        loss = loss + cfg.moe_z_coef * aux["z"]
    return loss, {"moe_tokens_per_expert": aux["tokens_per_expert"], "moe_dropped": aux["dropped"]}
