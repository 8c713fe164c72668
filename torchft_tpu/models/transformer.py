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
import functools
import itertools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.ops import flash_attention, rms_norm
from torchft_tpu.parallel.sharding import ShardingRules, constrain


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One kind of decoder layer: its mixer, its feed-forward, and the subtree
    of the parameters its layers are stacked under (``params[stack]``, in
    their order in the model).  A 64-head and a 48-head layer cannot share one
    stacked array, so a model has one stack a kind."""

    stack: str
    sparse: bool                       # the feed-forward: mixture of experts, or dense
    n_heads: int                       # query heads (the KV heads are the model's)
    rope_theta: float
    window: Optional[int] = None       # a query at t sees 0 <= t - s < window; None: all of the past
    rotary_fraction: float = 1.0       # RoPE turns this leading share of a head's columns
    # YaRN (arXiv:2309.00071): (factor, original length, beta_fast, beta_slow,
    # attention_factor) — `yarn_frequencies` in place of theta's powers, cos
    # and sin times the attention factor.
    yarn: Optional[Tuple[float, int, float, float, float]] = None
    # "attention": q, k and v are products of the layer's normed input,
    # position by position.  "cca": attention inside a compressed latent
    # (compressed convolutional attention, arXiv:2510.04476; `_cca_qkv`) —
    # between the projections and the attention call a causal convolution a
    # channel, one a head over sequence and channels (both of kernel 2), the
    # mean of the un-convolved q and k added back, half of the KV heads'
    # values taken from the position before, and an L2 norm a head.
    # "mla": latent attention (the model's `mla_*` widths; `_mla_qkv`) at this
    # kind's heads, its `mla_rope_dim` columns rotated where `rotary_fraction`
    # is not 0 and plain content where it is (NoPE).  "kda": Kimi Delta
    # Attention (arXiv:2510.26692; `_kda_mixer`) — no softmax and no position
    # term: a gated delta rule with a decay a channel over `n_heads` heads of
    # `kda_head_dim`, under short causal convolutions and low-rank gates.
    # "mamba2": a Mamba-2 state-space mixer (arXiv:2405.21060; models/mamba.py)
    # over `n_heads` heads of the model's `ssm_*` sizes.  "none": the block has
    # no mixer — it is a feed-forward alone under its one norm (`mlp_norm`).
    mixer: str = "attention"
    # False: the block is a mixer alone under its one norm (`attn_norm`): no
    # second norm, no feed-forward, no such leaves in its stack.
    feed_forward: bool = True


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
    # With remat: keep each layer's attention output and row statistics
    # (ops.attention.SAVED_NAMES; [B, H, S, Dv] in the compute type a layer),
    # so that the backward pass recomputes the projections and the
    # feed-forward but does not run the attention forward kernel twice.
    remat_keeps_attention: bool = False
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
    # Mixture-of-experts: > 0 replaces the dense MLP with a router over
    # moe_experts experts of width d_ff (stacked, shardable over the
    # "expert" mesh axis).  Two ways to the experts (models/moe.py):
    # moe_capacity_factor None = dropless, the sorted path with grouped
    # matmuls on ONE device, which holds every expert or, with moe_held =
    # (first, count), only that share of them — the router still scores all
    # moe_experts, the layer's result is this device's part of the sum, and
    # the parameter tree holds `count` experts a layer; a number = the
    # capacity-bound dense dispatch an "expert" mesh axis runs (every
    # expert, over-capacity assignments dropped).
    # moe_norm_topk: renormalise the k kept gates (OLMoE does not).
    # moe_score "softmax" (OLMoE) or "sigmoid": sigmoid scores, the choice
    # made on score + a constant bias handed to the loss beside the weights
    # (`router_bias`: not a trained leaf), the gates the chosen scores
    # renormalised and times moe_route_scale, the balance loss over k
    # (DeepSeek-V3 / Moonlight).
    # moe_shared_experts: a SwiGLU of width moe_shared_experts * d_ff that
    # every token passes beside the routed experts.
    # moe_dense_layers: that many LEADING layers keep a dense feed-forward of
    # width dense_d_ff; they are stacked apart, under params["dense_layers"].
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: Optional[float] = 1.25
    moe_norm_topk: bool = True
    moe_aux_coef: float = 0.01       # load-balance loss
    moe_z_coef: float = 0.0          # router z-loss
    moe_score: str = "softmax"
    moe_route_scale: float = 1.0
    moe_shared_experts: int = 0
    moe_held: Optional[Tuple[int, int]] = None
    moe_dense_layers: int = 0
    dense_d_ff: int = 0
    # RMSNorm over the whole projected query and key, each with a weight of
    # its own, before the split into heads and RoPE (OLMoE's attention).
    qk_norm: bool = False
    rms_eps: float = 1e-6
    # Latent attention (MLA, DeepSeek-V2/V3): mla_kv_rank > 0 replaces wk/wv
    # by a low-rank path — wkv_a: embed -> mla_kv_rank + mla_rope_dim, an
    # RMSNorm over the rank, wkv_b: rank -> heads * (mla_nope_dim +
    # mla_v_dim) — with ONE rotary key of mla_rope_dim columns shared by all
    # heads.  A query and key head is mla_nope_dim + mla_rope_dim wide, a
    # value head mla_v_dim, and d_head is not used.
    mla_kv_rank: int = 0
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    # A head's width where it is not d_model / n_heads (0: it is).
    head_dim: int = 0
    # RMSNorm over each head's columns of the projected query and key, one
    # weight of d_head for all query heads and one for all key heads, before
    # RoPE (Qwen3's attention).
    qk_norm_per_head: bool = False
    # Learned sparse attention (DeepSeek-V3.2's lightning indexer;
    # ops/sparse_attention.py): dsa_index_heads > 0 gives every layer an
    # indexer — wi_q: embed -> dsa_index_heads * dsa_index_dim, wi_k: embed
    # -> ONE key head of dsa_index_dim under a LayerNorm (wi_k_norm,
    # wi_k_bias), wi_w: embed -> a weight per index head — that scores every
    # visible (query, key) pair; each query attends to its dsa_topk best
    # keys alone.  The indexer reads a DETACHED input and learns from a loss
    # of its own (the KL of its softmax over the selection from the heads'
    # summed attention probabilities, times dsa_loss_coef, summed over the
    # layers); the language-model loss sees the selection as a constant.
    dsa_index_heads: int = 0
    dsa_index_dim: int = 64
    dsa_topk: int = 2048
    dsa_loss_coef: float = 1.0
    # The layer pattern as data: every layer's kind, first to last (window
    # and full attention mixed, head counts and RoPE by kind, dense and
    # sparse feed-forwards in any order).  Empty: `moe_dense_layers` leading
    # layers with a dense feed-forward under params["dense_layers"], then the
    # model's own kind under params["layers"] — a way of writing a pattern.
    pattern: Tuple[LayerKind, ...] = ()
    # A sigmoid gate a head on attention's output, from the layer's normed
    # input: o_head * sigmoid(h W_g)_head, W_g: embed -> heads ("attn_gate";
    # arXiv:2505.06708's head-wise form).
    attn_head_gate: bool = False
    # The router as a function with a state (ZAYA1's, arXiv:2511.17127;
    # models/moe.py `state_router_logits`): moe_router_state > 0 is the width
    # of a state the layer loop carries beside x — layer l's is its input's
    # down-projection plus a learned vector times layer l - 1's — which an
    # RMSNorm and a three-layer GELU MLP of that width turn into the scores;
    # `router` is then a subtree, not a matrix.
    moe_router_state: int = 0
    # The router's last output is a choice that takes NO expert: the position
    # gets no row and adds nothing (counted in `moe_skipped`, never dropped).
    moe_skip: bool = False
    # The residual merge with learned vectors, `(x + b_r) * a_r + (y + b_o) *
    # a_o` in place of `x + y`, four vectors of d_model a sublayer
    # ("attn_merge", "mlp_merge": rows a_r, b_r, a_o, b_o; 1 and 0 at the start).
    scaled_merge: bool = False
    # The head is the embedding itself: no `lm_head` leaf, logits = h embed^T.
    tied_head: bool = False
    # Kimi Delta Attention (LayerKind.mixer "kda"): a head's width, which is
    # also the rank of the two low-rank gates, and the kernel of the depthwise
    # causal convolutions on q, k and v.
    kda_head_dim: int = 128
    kda_conv: int = 4
    # The router reads the LAYER'S INPUT — the residual stream before the
    # layer's first norm and its mixer — and not the experts' own input
    # (SmallThinker's `moe_enable_early_router`): the choice and the gates are
    # made before attention, the experts take them with the normed stream after
    # it, and the gates' cotangent flows into the stream the router read.
    moe_router_early: bool = False
    # The activation of every gated feed-forward (experts, shared expert,
    # dense layers): "silu" (SwiGLU) or "relu" (ReGLU).  Under "relu" the held
    # experts' rows count their hidden units that are not zero
    # (`moe_active_units` of `moe_units_held`, loss_and_counters).  "relu2":
    # UN-GATED feed-forwards, `max(h W_up, 0)**2 W_down` — two matrices and no
    # `w_gate` / `shared_gate` leaf (Nemotron-H's experts); counted as "relu".
    moe_activation: str = "silu"
    # The Mamba-2 mixer's sizes (LayerKind.mixer "mamba2"; the heads are the
    # kind's): a head's width, the groups that share B and C, the state's rows
    # a head, the kernel of the depthwise causal convolution, the scan's chunk.
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128

    def __post_init__(self) -> None:
        assert self.attention in ("flash", "ring", "ulysses"), (
            f"unknown attention backend {self.attention!r}; "
            "expected 'flash', 'ring', or 'ulysses'"
        )
        assert self.ring_layout in ("contiguous", "zigzag"), (
            f"unknown ring_layout {self.ring_layout!r}"
        )
        assert self.moe_score in ("softmax", "sigmoid"), f"unknown moe_score {self.moe_score!r}"
        assert self.moe_activation in ("silu", "relu", "relu2"), f"unknown moe_activation {self.moe_activation!r}"
        if self.moe_router_early:
            assert self.moe_experts > 0 and not self.moe_router_state, (
                "an early router is one matrix over the layer's input: a router with a state reads the experts'"
            )
        if self.mla_kv_rank:
            assert self.attention == "flash" and not self.qk_norm, (
                "latent attention runs the flash backend, without a QK-norm"
            )
        if self.dsa_index_heads:
            assert self.attention == "flash" and not self.mla_kv_rank, (
                "the indexer selects keys for the flash backend's plain heads"
            )
            assert not self.moe_dense_layers, "the leading dense layers carry no indexer statistics"
        assert not (self.qk_norm and self.qk_norm_per_head), "one QK-norm or the other"
        if self.moe_dense_layers:
            assert self.moe_experts > 0 and 0 < self.moe_dense_layers < self.n_layers and self.dense_d_ff > 0
        if self.moe_held is not None:
            first, count = self.moe_held
            assert self.moe_capacity_factor is None and 0 <= first and first + count <= self.moe_experts, (
                "a share of the experts is held on the dropless path"
            )
        if self.pattern:
            assert len(self.pattern) == self.n_layers and not self.moe_dense_layers, "one kind a layer"
            assert self.attention == "flash" and not self.dsa_index_heads, "a pattern's kinds run the flash backend"
            assert all(kind.mixer in ("attention", "cca", "mla", "kda", "mamba2", "none") for kind in self.pattern)
            assert all((kind.feed_forward or not kind.sparse) and (kind.feed_forward or kind.mixer != "none")
                       for kind in self.pattern), "a block is a mixer, a feed-forward, or both"
            assert all(kind.n_heads % self.ssm_groups == 0 for kind in self.pattern if kind.mixer == "mamba2")
            assert len({"kda", "mamba2"} & {kind.mixer for kind in self.pattern}) < 2, "one decay's mean is counted"
            assert not (self.moe_router_early and any(kind.mixer == "none" for kind in self.pattern)), (
                "an early router reads the input of a block that has a mixer"
            )
            assert bool(self.mla_kv_rank) == any(kind.mixer == "mla" for kind in self.pattern), (
                "the latent widths are the model's, the layers that use them the pattern's"
            )
            if any(kind.mixer in ("mla", "kda") for kind in self.pattern):
                assert not (self.qk_norm or self.qk_norm_per_head or self.attn_head_gate), (
                    "latent and delta attention have no QK-norm and no head gate of the model's"
                )
            if any(kind.mixer == "cca" for kind in self.pattern):
                assert not (self.qk_norm or self.qk_norm_per_head or self.attn_head_gate), (
                    "compressed attention norms its own heads and has no gate"
                )
                assert self.n_kv_heads % 2 == 0 and all(k.n_heads % self.n_kv_heads == 0 for k in self.pattern)
            assert all(a == b for a in self.pattern for b in self.pattern if a.stack == b.stack), "one kind a stack"
            assert all(self.moe_experts > 0 for kind in self.pattern if kind.sparse)
        if self.moe_router_state or self.moe_skip:
            assert self.moe_experts > 0 and self.moe_capacity_factor is None, (
                "a router with a state, or with a choice that takes no expert, routes on the dropless path"
            )

    @property
    def layers(self) -> Tuple[LayerKind, ...]:
        """Every layer's kind, first to last."""
        if self.pattern:
            return self.pattern
        own = LayerKind("layers", self.moe_experts > 0, self.n_heads, self.rope_theta,
                        mixer="mla" if self.mla_kv_rank else "attention")
        dense = dataclasses.replace(own, stack="dense_layers", sparse=False)
        return (dense,) * self.moe_dense_layers + (own,) * (self.n_layers - self.moe_dense_layers)

    @property
    def stacks(self) -> Dict[str, Tuple[LayerKind, int]]:
        """stack -> (its kind, how many layers it holds), in order of first appearance."""
        out: Dict[str, Tuple[LayerKind, int]] = {}
        for kind in self.layers:
            out[kind.stack] = (kind, out.get(kind.stack, (kind, 0))[1] + 1)
        return out

    @property
    def n_sparse_layers(self) -> int:
        """Layers with experts, in a model that has them (the rows of
        ``router_bias`` and of the per-layer statistics); all of a dense
        model's."""
        if self.moe_experts == 0:
            return self.n_layers
        return sum(kind.sparse for kind in self.layers)

    @property
    def n_held_experts(self) -> int:
        return self.moe_held[1] if self.moe_held is not None else self.moe_experts

    @property
    def n_router_outputs(self) -> int:
        """The router's choices: the experts, and the one that takes none."""
        return self.moe_experts + int(self.moe_skip)

    @property
    def d_head(self) -> int:
        if self.head_dim:
            return self.head_dim
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


# Logical axis names for every parameter (see parallel/sharding.py).
def _layer_axes(cfg: TransformerConfig, kind: LayerKind) -> Dict[str, Any]:
    sparse = kind.sparse
    layer = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if kind.mixer == "mla":
        layer.update({"wkv_a": ("layers", "embed", None), "kv_norm": ("layers", None),
                      "wkv_b": ("layers", None, "heads")})
    elif kind.mixer == "kda":
        layer.update({"wk": ("layers", "embed", "heads"), "wv": ("layers", "embed", "heads")})
        layer.update({name: ("layers", None, "heads") for name in ("kda_conv_q", "kda_conv_k", "kda_conv_v",
                                                                    "kda_a_up", "kda_g_up")})
        layer.update({"kda_a_down": ("layers", "embed", None), "kda_g_down": ("layers", "embed", None),
                      "kda_beta": ("layers", "embed", None), "A_log": ("layers", None), "dt_bias": ("layers", "heads"),
                      "kda_g_bias": ("layers", "heads"), "kda_norm": ("layers", None)})
    elif kind.mixer == "mamba2":
        from torchft_tpu.models.mamba import mamba2_axes

        layer.update(mamba2_axes())
    else:
        layer.update({"wk": ("layers", "embed", "kv_heads"), "wv": ("layers", "embed", "kv_heads")})
    if cfg.qk_norm:
        layer.update({"q_norm": ("layers", "heads"), "k_norm": ("layers", "kv_heads")})
    if cfg.qk_norm_per_head:
        layer.update({"q_norm": ("layers", None), "k_norm": ("layers", None)})
    if cfg.dsa_index_heads:
        layer.update({"wi_q": ("layers", "embed", None), "wi_k": ("layers", "embed", None),
                      "wi_k_norm": ("layers", None), "wi_k_bias": ("layers", None),
                      "wi_w": ("layers", "embed", None)})
    if cfg.attn_head_gate:
        layer["attn_gate"] = ("layers", "embed", "heads")
    if kind.mixer == "cca":
        layer.update({"cca_conv0": ("layers", None, None), "cca_bias0": ("layers", None),
                      "cca_conv1": ("layers", None, None, None, None), "cca_bias1": ("layers", None, None),
                      "cca_temp": ("layers", None)})
    if cfg.scaled_merge:
        layer.update({"attn_merge": ("layers", None, "embed"), "mlp_merge": ("layers", None, "embed")})
    if sparse:
        layer.update(
            {
                "router": _state_router_axes() if cfg.moe_router_state else ("layers", "embed", "expert"),
                "w_gate": ("layers", "expert", "embed", "mlp"),
                "w_up": ("layers", "expert", "embed", "mlp"),
                "w_down": ("layers", "expert", "mlp", "embed"),
            }
        )
        if cfg.moe_shared_experts:
            layer.update({"shared_gate": ("layers", "embed", "mlp"), "shared_up": ("layers", "embed", "mlp"),
                          "shared_down": ("layers", "mlp", "embed")})
    return _leaves_of_the_kind(cfg, kind, layer)


_MIXER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo")
_FEED_FORWARD_LEAVES = ("mlp_norm", "w_gate", "w_up", "w_down")


def _leaves_of_the_kind(cfg: TransformerConfig, kind: LayerKind, layer: Dict[str, Any]) -> Dict[str, Any]:
    """``layer`` (a stack's leaves, or their axes) without what the kind does
    not have: the mixer's of a block that is a feed-forward alone, the
    feed-forward's of a block that is a mixer alone, attention's projections
    of a Mamba-2 block, the gate matrices of un-gated feed-forwards."""
    drop = set()
    if kind.mixer == "none":
        drop.update(_MIXER_LEAVES)
    if kind.mixer == "mamba2":
        drop.update(_MIXER_LEAVES[1:])
    if not kind.feed_forward:
        drop.update(_FEED_FORWARD_LEAVES)
    if cfg.moe_activation == "relu2":
        drop.update(("w_gate", "shared_gate"))
    return {name: leaf for name, leaf in layer.items() if name not in drop} if drop else layer


def _state_router_axes() -> Dict[str, Any]:
    return {"down": ("layers", "embed", None), "down_bias": ("layers", None), "carry": ("layers", None),
            "norm": ("layers", None), "w1": ("layers", None, None), "b1": ("layers", None),
            "w2": ("layers", None, None), "b2": ("layers", None), "w3": ("layers", None, "expert")}


def param_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Logical axis names for every parameter, keyed like init_params'
    tree — feed to FTMesh.shard_params to place the model on a mesh."""
    axes = {"embed": ("vocab", "embed"), "final_norm": ("embed",)}
    if not cfg.tied_head:
        axes["lm_head"] = ("embed", "vocab")
    for stack, (kind, _) in cfg.stacks.items():
        axes[stack] = _layer_axes(cfg, kind)
    return axes


def _norm_init(k, shape, fan_in, pd):
    return (jax.random.normal(k, shape, pd) * (fan_in ** -0.5)).astype(pd)


def _init_layers(key: jax.Array, cfg: TransformerConfig, L: int, kind: LayerKind) -> Dict[str, Any]:
    pd = cfg.param_dtype
    E, H, KV, sparse = cfg.d_model, kind.n_heads, cfg.n_kv_heads, kind.sparse

    def norm_init(k, shape, fan_in):
        return _norm_init(k, shape, fan_in, pd)

    ks = jax.random.split(key, 8)
    layers = {"attn_norm": jnp.ones((L, E), pd), "mlp_norm": jnp.ones((L, E), pd)}
    if kind.mixer == "kda":
        layers.update(_init_kda(jax.random.fold_in(key, 5), cfg, L, H))
    elif kind.mixer == "mamba2":
        from torchft_tpu.models.mamba import init_mamba2

        layers.update(init_mamba2(jax.random.fold_in(key, 6), cfg, L, H))
    elif kind.mixer == "mla":
        R, Dq = cfg.mla_kv_rank, cfg.mla_nope_dim + cfg.mla_rope_dim
        layers.update(
            {
                "wq": norm_init(ks[0], (L, E, H * Dq), E),
                "wkv_a": norm_init(ks[1], (L, E, R + cfg.mla_rope_dim), E),
                "kv_norm": jnp.ones((L, R), pd),
                "wkv_b": norm_init(ks[2], (L, R, H * (cfg.mla_nope_dim + cfg.mla_v_dim)), R),
                "wo": norm_init(ks[3], (L, H * cfg.mla_v_dim, E), H * cfg.mla_v_dim),
            }
        )
    else:
        Dh = cfg.d_head
        layers.update(
            {
                "wq": norm_init(ks[0], (L, E, H * Dh), E),
                "wk": norm_init(ks[1], (L, E, KV * Dh), E),
                "wv": norm_init(ks[2], (L, E, KV * Dh), E),
                "wo": norm_init(ks[3], (L, H * Dh, E), H * Dh),
            }
        )
        if cfg.qk_norm:
            layers.update({"q_norm": jnp.ones((L, H * Dh), pd), "k_norm": jnp.ones((L, KV * Dh), pd)})
        if cfg.qk_norm_per_head:
            layers.update({"q_norm": jnp.ones((L, Dh), pd), "k_norm": jnp.ones((L, Dh), pd)})
    if cfg.dsa_index_heads:
        J, Di = cfg.dsa_index_heads, cfg.dsa_index_dim
        kq, kk, kw = jax.random.split(jax.random.fold_in(key, 2), 3)
        layers.update(
            {
                "wi_q": norm_init(kq, (L, E, J * Di), E),
                "wi_k": norm_init(kk, (L, E, Di), E),
                "wi_k_norm": jnp.ones((L, Di), pd),
                "wi_k_bias": jnp.zeros((L, Di), pd),
                "wi_w": norm_init(kw, (L, E, J), E),
            }
        )
    if cfg.attn_head_gate:
        layers["attn_gate"] = norm_init(jax.random.fold_in(key, 3), (L, E, H), E)
    if kind.mixer == "cca":
        C, Dh = H + KV, cfg.d_head  # the convolutions run over q's and k's heads side by side
        k0, k1 = jax.random.split(jax.random.fold_in(key, 4))
        layers.update(
            {
                "cca_conv0": norm_init(k0, (L, 2, C * Dh), 2),         # [tap, channel]: tap 1 the position itself
                "cca_bias0": jnp.zeros((L, C * Dh), pd),
                "cca_conv1": norm_init(k1, (L, C, 2, Dh, Dh), 2 * Dh),  # [head, tap, channel in, channel out]
                "cca_bias1": jnp.zeros((L, C, Dh), pd),
                "cca_temp": jnp.ones((L, KV), pd),
            }
        )
    if cfg.scaled_merge:
        merge = jnp.broadcast_to(jnp.asarray([1.0, 0.0, 1.0, 0.0], pd)[None, :, None], (L, 4, E))
        layers.update({"attn_merge": merge, "mlp_merge": jnp.array(merge)})  # two buffers: a step donates each leaf
    if sparse:
        F, X, held = cfg.d_ff, cfg.n_router_outputs, cfg.n_held_experts
        kr, kg, ku, kd = jax.random.split(ks[7], 4)
        if cfg.moe_router_state:
            R = cfg.moe_router_state
            k_down, k_1, k_2, k_3 = jax.random.split(kr, 4)
            router = {
                "down": norm_init(k_down, (L, E, R), E), "down_bias": jnp.zeros((L, R), pd),
                "carry": jnp.ones((L, R), pd), "norm": jnp.ones((L, R), pd),
                "w1": norm_init(k_1, (L, R, R), R), "b1": jnp.zeros((L, R), pd),
                "w2": norm_init(k_2, (L, R, R), R), "b2": jnp.zeros((L, R), pd),
                "w3": norm_init(k_3, (L, R, X), R),
            }
        else:
            router = norm_init(kr, (L, E, X), E)
        layers.update(
            {
                "router": router,
                "w_gate": norm_init(kg, (L, held, E, F), E),
                "w_up": norm_init(ku, (L, held, E, F), E),
                "w_down": norm_init(kd, (L, held, F, E), F),
            }
        )
        if cfg.moe_shared_experts:
            Fs = cfg.moe_shared_experts * F
            kg, ku, kd = jax.random.split(jax.random.fold_in(ks[7], 1), 3)
            layers.update({"shared_gate": norm_init(kg, (L, E, Fs), E), "shared_up": norm_init(ku, (L, E, Fs), E),
                           "shared_down": norm_init(kd, (L, Fs, E), Fs)})
    else:
        F = cfg.dense_d_ff or cfg.d_ff
        layers.update(
            {
                "w_gate": norm_init(ks[4], (L, E, F), E),
                "w_up": norm_init(ks[5], (L, E, F), E),
                "w_down": norm_init(ks[6], (L, F, E), F),
            }
        )
    return _leaves_of_the_kind(cfg, kind, layers)


def _init_kda(key: jax.Array, cfg: TransformerConfig, L: int, H: int) -> Dict[str, Any]:
    """A stack of Kimi Delta Attention mixers: the published layer's
    initialisation of the decay (A = log U(1, 16) a head, dt_bias the inverse
    softplus of log-uniform steps in [0.001, 0.1] a channel), both float32."""
    pd, E, D, T = cfg.param_dtype, cfg.d_model, cfg.kda_head_dim, cfg.kda_conv
    keys = iter(jax.random.split(key, 14))

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


def init_params(key: jax.Array, cfg: TransformerConfig) -> Dict[str, Any]:
    """Initializes the transformer parameter pytree (layers stacked on a
    leading axis for the scan-over-layers; param_dtype precision): one
    stacked subtree a kind of layer (``cfg.stacks``) — "layers" for a model
    of one kind, with its leading dense layers apart under "dense_layers"."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    pd = cfg.param_dtype
    E = cfg.d_model
    params = {
        "embed": _norm_init(k_embed, (cfg.vocab_size, E), E, pd),
        "final_norm": jnp.ones((E,), pd),
    }
    if not cfg.tied_head:
        params["lm_head"] = _norm_init(k_head, (E, cfg.vocab_size), E, pd)
    # The last kind's stack draws from `k_layers` itself, each kind before it
    # from a key folded out of it (a model of one kind with leading dense
    # layers: "layers", then "dense_layers").
    for i, (stack, (kind, count)) in enumerate(reversed(cfg.stacks.items())):
        params[stack] = _init_layers(jax.random.fold_in(k_layers, i) if i else k_layers, cfg, count, kind)
    return params


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


def _turn(x: jax.Array, positions: jax.Array, inv_freq: jax.Array, factor: float, rot: int,
          head_major: bool = False) -> jax.Array:
    """The leading ``rot`` columns of x [B, S, H, D] ([B, H, S, D] where
    ``head_major``) turned by positions
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
    if head_major:
        return _turn_whole(x, cos[:, None], sin[:, None], half, rot)
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


def _rotary(x: jax.Array, positions: jax.Array, kind: LayerKind, head_major: bool = False) -> jax.Array:
    """RoPE as the layer's kind has it: over the leading ``rotary_fraction``
    of a head's columns (half-split pairs inside that part, the rest passes
    through), at theta's powers or YaRN's frequencies — a constant of the
    program — with cos and sin times YaRN's attention factor."""
    if kind.rotary_fraction == 1.0 and kind.yarn is None and not head_major:
        return _rope(x, positions, kind.rope_theta)
    import numpy as np

    rot = int(x.shape[-1] * kind.rotary_fraction)
    half = rot // 2
    if kind.yarn is None:
        inv_freq, factor = kind.rope_theta ** (-np.arange(half, dtype=np.float64) / half), 1.0
    else:
        inv_freq, factor = yarn_frequencies(kind.rope_theta, rot, *kind.yarn[:4]), kind.yarn[4]
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    if head_major:
        return _turn(x, positions, inv_freq, factor, rot, head_major=True)
    return _turn(x, positions, inv_freq, factor, rot)


def _attention(cfg: TransformerConfig, mesh, q, k, v, kind: LayerKind):
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
        )
    return flash_attention(q, k, v, causal=True, mesh=mesh, window=kind.window)


def _mla_qkv(cfg: TransformerConfig, kind: LayerKind, h, w, positions):
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


def _index_operands(cfg: TransformerConfig, h, w, positions):
    """The indexer's operands from the normed input h [B, S, E], which it
    reads DETACHED: index queries [B, J, S, Di] and the one index key head
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


def _sparse_attention(cfg: TransformerConfig, mesh, h, w, positions, q, k, v):
    """Attention over the keys the layer's indexer selects; q/k/v head-major.
    Returns (attention [B, H, S, Dh], {"dsa_index_loss", "dsa_selected"})."""
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


def _cca_qkv(cfg: TransformerConfig, kind: LayerKind, h, w, positions):
    """Compressed convolutional attention's q [B, H, S, D] and k, v
    [B, G, S, D], head-major, from the normed input h [B, S, E]
    (arXiv:2510.04476; LayerKind.mixer).  The projections are `attn_proj`'s;
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
    q, k = (_rotary(a, positions, kind, head_major=True).astype(dt) for a in (q, k))
    return q, k, v


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


def _l2(x):
    """x over its last axis' L2 norm (float32 in, float32 out)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


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


def _kda_mixer(cfg: TransformerConfig, kind: LayerKind, mesh, h, w):
    """Kimi Delta Attention from the normed input h [B, S, E] to the heads'
    joined output [B, S, H * D], before `wo` (arXiv:2510.26692;
    LayerKind.mixer).  The projections are `attn_proj`'s, the recurrence
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


def _merge(x, y, vectors=None):
    """The residual merge: ``x + y``, or with the sublayer's four learned
    vectors [4, E] ``(x + b_r) * a_r + (y + b_o) * a_o`` (float32 inside the
    fusion, the stream's type out)."""
    if vectors is None:
        return x + y
    a_r, b_r, a_o, b_o = vectors.astype(jnp.float32)
    return ((x.astype(jnp.float32) + b_r) * a_r + (y.astype(jnp.float32) + b_o) * a_o).astype(x.dtype)


def _layer(cfg: TransformerConfig, mesh, rules: ShardingRules, x, w, positions, kind=None, router_bias=None):
    """One decoder block; x: [B, S, E] — or, where the router carries a state
    (`cfg.moe_router_state`), the pair (x, r) with r [B, S, state] float32
    the layer before's, and the same pair comes back.  `kind`: the layer's
    (default: the model's last layer's, the one kind of a model without a
    pattern); `router_bias` [router outputs]: the router's choice bias for
    this layer, or None."""
    router_state = None
    if cfg.moe_router_state:
        x, router_state = x
    B, S, E = x.shape
    kind = cfg.layers[-1] if kind is None else kind
    H, KV = kind.n_heads, cfg.n_kv_heads
    if kind.mixer == "none":  # a feed-forward alone: its one norm is `_feed_forward`'s
        return _feed_forward(cfg, mesh, rules, x, w, kind, router_bias, router_state, None)

    routed = None
    if cfg.moe_router_early and kind.sparse:
        from torchft_tpu.models.moe import routing

        routed = routing(x, w["router"], **_router_form(cfg, router_bias, router_state))
    # The scopes are the parts a profile's device time is booked to
    # (obs/spans.PARTS); they name the work and change no instruction.
    with jax.named_scope("norm"):
        h = rms_norm(x, w["attn_norm"], cfg.rms_eps)
    if kind.mixer == "kda":
        attn, alpha = _kda_mixer(cfg, kind, mesh, h, w)
        with jax.named_scope("attn_proj"):
            x = _merge(x, attn @ w["wo"].astype(cfg.dtype), w.get("attn_merge"))
            x = constrain(x, ("batch", "seq", "embed"), mesh, rules)
        return _feed_forward(cfg, mesh, rules, x, w, kind, router_bias, router_state, {"kda_alpha": alpha}, routed)
    if kind.mixer == "mamba2":
        from torchft_tpu.models.mamba import mamba2_mixer

        out, decay = mamba2_mixer(cfg, kind, mesh, h, w)
        with jax.named_scope("attn_proj"):
            x = constrain(_merge(x, out, w.get("attn_merge")), ("batch", "seq", "embed"), mesh, rules)
        return _after_the_mixer(cfg, mesh, rules, x, w, kind, router_bias, router_state, {"ssm_decay": decay}, routed)
    with jax.named_scope("attn_proj"):
        if kind.mixer == "cca":
            q, k, v = _cca_qkv(cfg, kind, h, w, positions)
        elif kind.mixer == "mla":
            q, k, v = _mla_qkv(cfg, kind, h, w, positions)
            KV = H
        else:
            Dh = cfg.d_head
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
                    q, k = rms_norm(q, w["q_norm"], cfg.rms_eps), rms_norm(k, w["k_norm"], cfg.rms_eps)
            v = (h @ w["wv"].astype(cfg.dtype)).reshape(B, S, KV, Dh)
            if kind.rotary_fraction:  # 0: no position term, q and k are the projections
                q = _rotary(q, positions, kind)
                k = _rotary(k, positions, kind)
        if cfg.attn_head_gate:
            head_gate = jax.nn.sigmoid((h @ w["attn_gate"].astype(cfg.dtype)).astype(jnp.float32)).astype(cfg.dtype)
        # compressed attention's heads are head-major already
        major = (lambda a: a) if kind.mixer == "cca" else (lambda a: a.transpose(0, 2, 1, 3))
        q = constrain(major(q), ("batch", "heads", "seq", None), mesh, rules)
        k = constrain(major(k), ("batch", "kv_heads", "seq", None), mesh, rules)
        v = constrain(major(v), ("batch", "kv_heads", "seq", None), mesh, rules)
    dsa = None
    if cfg.dsa_index_heads:
        attn, dsa = _sparse_attention(cfg, mesh, h, w, positions, q, k, v)
    else:
        with jax.named_scope("attn" if kind.window is None else "attn_window"):
            attn = _attention(cfg, mesh, q, k, v, kind)  # [B, H, S, Dv]
    with jax.named_scope("attn_proj"):
        attn = attn.transpose(0, 2, 1, 3)
        if cfg.attn_head_gate:
            attn = attn * head_gate[..., None]
        attn = attn.reshape(B, S, H * attn.shape[-1])
        x = _merge(x, attn @ w["wo"].astype(cfg.dtype), w.get("attn_merge"))
        x = constrain(x, ("batch", "seq", "embed"), mesh, rules)
    return _after_the_mixer(cfg, mesh, rules, x, w, kind, router_bias, router_state, dsa, routed)


def _after_the_mixer(cfg: TransformerConfig, mesh, rules: ShardingRules, x, w, kind: LayerKind, router_bias,
                     router_state, mixer_stats, routed):
    """What follows a block's mixer: the kind's feed-forward, or — a block
    that is a mixer alone — nothing: the stream and the mixer's statistics."""
    if kind.feed_forward:
        return _feed_forward(cfg, mesh, rules, x, w, kind, router_bias, router_state, mixer_stats, routed)
    aux = mixer_stats if mixer_stats is not None else jnp.zeros((), jnp.float32)
    return ((x, router_state) if cfg.moe_router_state else x), aux


def _router_form(cfg: TransformerConfig, router_bias, router_state) -> Dict[str, Any]:
    """The router's settings as `moe.routing` and `moe.moe_layer` take them."""
    return dict(top_k=cfg.moe_top_k, norm_topk=cfg.moe_norm_topk, score=cfg.moe_score, route_bias=router_bias,
                route_scale=cfg.moe_route_scale, router_state=router_state, skip=cfg.moe_skip, rms_eps=cfg.rms_eps)


def _feed_forward(cfg: TransformerConfig, mesh, rules: ShardingRules, x, w, kind: LayerKind, router_bias,
                  router_state, mixer_stats, routed=None):
    """The second half of a decoder block, from the stream x after the mixer:
    the kind's feed-forward and what `_layer` hands back.  `mixer_stats`: the
    statistics the mixer counted (a dict), or None.  `routed`: the choice an
    early router made on the layer's input (`moe.routing`'s result), or None:
    the experts' input is routed."""
    with jax.named_scope("norm"):
        h = rms_norm(x, w["mlp_norm"], cfg.rms_eps)
    if kind.sparse:
        from torchft_tpu.models.moe import moe_layer

        y, aux = moe_layer(
            h,
            w["router"],
            w.get("w_gate"),  # None: un-gated experts
            w["w_up"],
            w["w_down"],
            capacity_factor=cfg.moe_capacity_factor,
            held_first=cfg.moe_held[0] if cfg.moe_held is not None else 0,
            shared=(w.get("shared_gate"), w["shared_up"], w["shared_down"]) if cfg.moe_shared_experts else None,
            activation=cfg.moe_activation,
            routed=routed,
            dtype=cfg.dtype,
            mesh=mesh,
            rules=rules,
            **_router_form(cfg, router_bias, router_state),
        )
        if cfg.moe_router_state:
            router_state = aux.pop("router_state")
        with jax.named_scope("experts"):
            x = _merge(x, y, w.get("mlp_merge"))
    else:
        with jax.named_scope("ffn"):
            from torchft_tpu.models.moe import hidden_units

            hidden = hidden_units(cfg.moe_activation, w.get("w_gate"), w["w_up"], lambda m: h @ m.astype(cfg.dtype))
            x = _merge(x, hidden @ w["w_down"].astype(cfg.dtype), w.get("mlp_merge"))
        aux = {} if mixer_stats is not None else jnp.zeros((), jnp.float32)
    if mixer_stats is not None:
        aux = dict(aux, **mixer_stats)
    x = constrain(x, ("batch", "seq", "embed"), mesh, rules)
    return ((x, router_state) if cfg.moe_router_state else x), aux


def _decoder(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
    router_bias: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Embedding + decoder stack (everything before the lm head).
    tokens: [B, S] int32 -> (hidden [B, S, E], aux).  For a dense model aux
    is a zero scalar; for an MoE model it is the sparse layers' router
    statistics (models/moe.py ``moe_layer``): ``balance``, ``z``,
    ``dropped``, ``rows_held`` and ``assignments`` summed over the layers,
    ``tokens_per_expert`` [n_sparse_layers, n_experts] and ``chosen``
    [n_sparse_layers, B, S, k], the sparse layers in their order in the
    model.  The layers run as ``cfg.layers`` lists them, each kind's out of
    its own stack (leading dense layers, ``cfg.moe_dense_layers`` under
    params["dense_layers"], are the oldest such pattern).
    ``router_bias`` [n_sparse_layers, n_experts]: the sigmoid router's
    choice bias, a constant of the loss."""
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
    with jax.named_scope("attn_proj"):  # RoPE's operand
        positions = jnp.broadcast_to(pos, (B, S))

    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        x = constrain(x, ("batch", "seq", "embed"), mesh, rules)

    if cfg.moe_router_state:
        # the second stream: the router's state, zeros before the first layer
        x = (x, jnp.zeros((B, S, cfg.moe_router_state), jnp.float32))
    from torchft_tpu.ops.cross_entropy import head_row_block, padded_vocab

    # A head that runs in pieces says the program is at the edge of the chip's memory.  There a layer's weight
    # gradients are finished inside the layer's backward pass: left alone the compiler puts them all at the end of
    # the program, where they are stacked, and holds every layer's inputs to them until then (ZAYA's step
    # compiled to 15.84e9 bytes so and to 15.04e9 with the barriers, PR 46).
    grads_inside = head_row_block(B * S, padded_vocab(cfg.vocab_size)) is not None
    stats = cfg.moe_experts > 0 or cfg.dsa_index_heads > 0  # a layer's aux is a dict of statistics
    aux_total = jnp.zeros((), jnp.float32)
    pieces, pending = [], []  # the layers' statistics: stacked runs, and layers still to be stacked

    def flush():
        if pending:
            pieces.append(jax.tree.map(lambda *a: jnp.stack(a), *pending))
            pending.clear()

    alpha_total, kda_layers = jnp.zeros((), jnp.float32), sum(kind.mixer == "kda" for kind in cfg.layers)
    ssm_layers = sum(kind.mixer == "mamba2" for kind in cfg.layers)

    def without_alpha(aux):
        """A KDA or Mamba-2 layer's (or run's) statistics without its mean decay, which is summed apart."""
        nonlocal alpha_total
        name = next((n for n in ("kda_alpha", "ssm_decay") if isinstance(aux, dict) and n in aux), None)
        if name is None:
            return aux
        aux = dict(aux)
        alpha_total = alpha_total + jnp.sum(aux.pop(name))
        return aux or jnp.zeros((), jnp.float32)

    # The walk of the pattern: runs of one kind, each through its own stack;
    # router_bias's rows by a layer's place among the SPARSE layers, whatever their stack.
    at = {stack: 0 for stack in cfg.stacks}  # the next layer of each stack
    sparse_at = 0
    for kind, run in itertools.groupby(cfg.layers):
        count, first = len(list(run)), at[kind.stack]
        at[kind.stack] += count
        with_stats = stats and (kind.sparse or cfg.dsa_index_heads > 0)
        stacked = params[kind.stack]
        bias, bias_first = (router_bias if kind.sparse else None), sparse_at
        sparse_at += count * kind.sparse

        def body(x, w, kind=kind):
            if grads_inside:
                x, w = _grads_inside(x, w)
            w = dict(w)
            return _layer(cfg, mesh, rules, x, w, positions, kind=kind, router_bias=w.pop("router_bias", None))

        if cfg.remat:
            body = _remat(cfg, body)
        # A run that `scan_unroll` covers whole is a STATIC Python loop rather
        # than lax.scan(unroll=count): scan's internal layer slicing survives
        # as dynamic-update-slice fusions in the backward (profiled: ~17
        # ms/step of DUS on the v5e flagship config); static integer indexing
        # lets XLA constant-fold the slices and fold the per-layer grad
        # writes, measured ~4 ms/step faster end-to-end.  Same math, different
        # op association — results agree with the scan path to fusion-order
        # rounding, not bitwise (pinned by test_scan_unroll_matches_scan).
        if count <= cfg.scan_unroll:
            for n in range(count):
                with jax.named_scope("stack"):  # the layer's parts are the innermost scopes and name their work
                    w = jax.tree.map(lambda a, i=first + n: a[i], stacked)
                    x, aux = body(x, w if bias is None else dict(w, router_bias=bias[bias_first + n]))
                aux = without_alpha(aux)
                if with_stats:
                    pending.append(aux)
                elif not stats:  # beside experts a dense layer has no statistics
                    aux_total = aux_total + aux
            continue
        # The scan's own slicing of the stacked weights is `stack`.
        with jax.named_scope("stack"):
            if (first, count) != (0, cfg.stacks[kind.stack][1]):
                stacked = jax.tree.map(lambda a: a[first:first + count], stacked)
            if bias is not None:
                whole = (bias_first, count) == (0, bias.shape[0])
                stacked = dict(stacked, router_bias=bias if whole else bias[bias_first:bias_first + count])
            x, aux_layers = jax.lax.scan(body, x, stacked, unroll=cfg.scan_unroll)
        aux_layers = without_alpha(aux_layers)
        if with_stats:
            flush()
            pieces.append(aux_layers)
        elif not stats:
            aux_total = aux_total + jnp.sum(aux_layers)
    if cfg.moe_router_state:
        x, _ = x  # the last layer's state goes nowhere
    if not stats:
        return x, aux_total
    with jax.named_scope("stack"):
        flush()
        whole = pieces[0] if len(pieces) == 1 else jax.tree.map(lambda *a: jnp.concatenate(a), *pieces)
        out = _over_layers(whole)
        if kda_layers:
            out["kda_alpha"] = alpha_total / kda_layers
        if ssm_layers:
            out["ssm_decay"] = alpha_total / ssm_layers
        return x, out


@jax.custom_vjp
def _grads_inside(x, w):
    """(x, w) as they are; backward, the two cotangents behind one barrier,
    so that what follows x's cotangent — the backward pass of the layer
    before — waits for every gradient of this layer's weights."""
    return x, w


_grads_inside.defvjp(lambda x, w: ((x, w), None), lambda _, ct: jax.lax.optimization_barrier(ct))


def _remat(cfg: TransformerConfig, body):
    if not cfg.remat_keeps_attention:
        return jax.checkpoint(body)
    from torchft_tpu.ops.attention import SAVED_NAMES
    from torchft_tpu.ops.sparse_attention import SAVED_NAMES as DSA_SAVED_NAMES

    names = SAVED_NAMES + (DSA_SAVED_NAMES if cfg.dsa_index_heads else ())
    if any(kind.mixer == "kda" for kind in cfg.layers):
        from torchft_tpu.ops.delta_attention import SAVED_NAMES as KDA_SAVED_NAMES

        names += KDA_SAVED_NAMES
    if any(kind.mixer == "mamba2" for kind in cfg.layers):
        from torchft_tpu.ops.ssd import SAVED_NAMES as SSD_SAVED_NAMES

        names += SSD_SAVED_NAMES
    return jax.checkpoint(body, policy=jax.checkpoint_policies.save_only_these_names(*names))


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
    router_bias: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """tokens: [B, S] int32 -> (logits [B, S, vocab] f32, aux scalar f32 —
    the summed MoE load-balance loss; zero for dense models)."""
    x, aux = _decoder(params, tokens, cfg, mesh, rules, router_bias)
    if isinstance(aux, dict):
        aux = aux.get("balance", jnp.zeros((), jnp.float32))
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
    with jax.named_scope("head_loss"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        # bf16 operands on the MXU, f32 accumulation/output: full systolic-array
        # rate with f32 logits (an f32xf32 matmul runs at a fraction of MXU peak).
        if cfg.tied_head:
            logits = jnp.einsum("bse,ve->bsv", x, params["embed"].astype(cfg.dtype), preferred_element_type=jnp.float32)
        else:
            logits = jnp.matmul(
                x, params["lm_head"].astype(cfg.dtype), preferred_element_type=jnp.float32
            )
        return constrain(logits, ("batch", "seq", "vocab"), mesh, rules)


def token_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token CE, computed as logsumexp - target_logit rather than
    materializing the full [B, S, vocab] log-softmax: the logits array is
    the single biggest activation, and one extra copy is pure HBM traffic."""
    with jax.named_scope("head_loss"):
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
        fused_linear_cross_entropy_padded,
        fused_linear_cross_entropy_rows,
        head_row_block,
        padded_vocab,
    )

    B, S, E = x.shape
    block = head_row_block(B * S, padded_vocab(cfg.vocab_size))
    if fused_ce_applicable(block or B * S, E, padded_vocab(cfg.vocab_size), mesh):
        with jax.named_scope("head_loss"):
            h = rms_norm(x, params["final_norm"], cfg.rms_eps)
            if cfg.tied_head or block:
                # The head's weight as the tree holds it, [V, E] where it is the embedding:
                # cast, padded and laid out for the kernels inside, its gradient float32.
                w = params["embed"] if cfg.tied_head else params["lm_head"]
                return fused_linear_cross_entropy_rows(
                    h.reshape(B * S, E), w, targets.reshape(B * S), block or B * S, cfg.tied_head)
            w = params["lm_head"].astype(cfg.dtype)
            # A vocabulary slice that no block divides runs the same kernels over
            # zero-padded columns whose logits count as -inf.
            fused = fused_linear_cross_entropy if cfg.vocab_size % 128 == 0 else fused_linear_cross_entropy_padded
            return fused(h.reshape(B * S, E), w, targets.reshape(B * S))
    return token_cross_entropy(head(params, x, cfg, mesh, rules), targets)


def loss_fn(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
    router_bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Next-token cross entropy; batch: {"tokens": [B,S], "targets": [B,S]}.

    MoE configs add moe_aux_coef * load-balance loss (Switch-style) and
    moe_z_coef * router z-loss.
    """
    return loss_and_counters(params, batch, cfg, mesh, rules, router_bias)[0]


def loss_and_counters(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
    router_bias: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``loss_fn`` and what the model counted on the way, for a
    ``TrainStep(loss_has_counters=True)``: for an MoE model
    ``moe_tokens_per_expert`` ([n_sparse_layers, n_experts] int32,
    assignments sent to each of the router's outputs) and ``moe_dropped``
    (int32, assignments to experts held here that reached none); where the
    device holds a share of the experts (``cfg.moe_held``) also
    ``moe_assignments`` (int32, all (token, expert) choices of the sparse
    layers) and ``moe_rows_held`` (int32, those that fell on held experts);
    where the experts are ReGLU (``cfg.moe_activation == "relu"``, the dropless
    path) also ``moe_active_units`` (int32, the (row, hidden unit) pairs of the
    held experts' rows that ReLU left above zero, over the sparse layers) and
    ``moe_units_held`` (int32, all such pairs: rows that hold an assignment
    times ``d_ff``); for a dense model nothing.  ``router_bias`` [n_sparse_layers,
    n_experts] is the sigmoid router's choice bias: a constant, no leaf of
    ``params``, so neither the gradient nor the optimizer sees it."""
    x, aux = _decoder(params, batch["tokens"], cfg, mesh, rules, router_bias)
    loss = lm_head_loss(params, x, cfg, batch["targets"], mesh, rules)
    counters = {}
    with jax.named_scope("head_loss"):  # the loss's other terms and the counters
        if cfg.dsa_index_heads:
            # The indexer's own loss: no weight outside the indexer has a gradient from it.
            loss = loss + cfg.dsa_loss_coef * aux["dsa_index_loss"]
            B, S = batch["tokens"].shape
            visible = cfg.n_layers * B * (S * (S + 1) // 2)
            assert visible < 2 ** 32, "the pair counters are uint32"
            counters.update(dsa_pairs_selected=aux["dsa_selected"], dsa_pairs_visible=jnp.uint32(visible),
                            dsa_index_loss=aux["dsa_index_loss"])
        if cfg.moe_experts == 0:
            return loss, counters
        loss = loss + cfg.moe_aux_coef * aux["balance"]
        if cfg.moe_z_coef:
            loss = loss + cfg.moe_z_coef * aux["z"]
        counters.update(moe_tokens_per_expert=aux["tokens_per_expert"], moe_dropped=aux["dropped"])
        if "kda_alpha" in aux:
            counters.update(kda_alpha_mean=aux["kda_alpha"])
        if "ssm_decay" in aux:
            counters.update(ssm_decay_mean=aux["ssm_decay"])
        if cfg.moe_skip:
            counters.update(moe_skipped=aux["skipped"])
        if cfg.moe_held is not None:
            counters.update(moe_assignments=aux["assignments"], moe_rows_held=aux["rows_held"])
        if "active_units" in aux:
            assert cfg.n_sparse_layers * batch["tokens"].size * cfg.moe_top_k * cfg.d_ff < 2 ** 31, "int32 counters"
            counters.update(moe_active_units=aux["active_units"],
                            moe_units_held=(aux["rows_held"] - aux["dropped"]) * cfg.d_ff)
        return loss, counters
