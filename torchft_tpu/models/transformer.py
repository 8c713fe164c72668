"""Flagship model: decoder-only transformer LM (Llama-3-class shape).

TPU-first design choices:
  - parameters are plain pytrees of jax.Arrays with per-layer weights
    *stacked* along a leading "layers" axis so the decoder runs as one
    ``lax.scan`` — one compiled layer body instead of L unrolled copies;
  - compute in bfloat16 (MXU-native), parameters and reductions in float32;
  - hot ops route through torchft_tpu.ops: fused pallas RMSNorm and flash
    attention; ring attention over the "sequence" mesh axis for long
    context;
  - a block's mixer is an entry of a table (models/mixers.MIXERS, a file a
    family beside this one): this file holds the configuration, the tree, the
    walk of the layers, the feed-forward, the head and the losses, and asks
    the entry for a mixer's leaves, axes, forward pass, kept names and
    statistic;
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
import itertools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.attention import _index_operands  # noqa: F401 — benchmark/tools/selection_ties.py takes it from here
from torchft_tpu.models.mixer import Mixer, _norm_init, _norm_start, _unit
from torchft_tpu.models.mixers import MIXERS
from torchft_tpu.ops import rms_norm
from torchft_tpu.ops.attention import bd_pairs_walked
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
    # An entry of `models/mixers.MIXERS` — "attention", "mla", "cca" (models/attention.py), "kda" (models/kda.py),
    # "gdn" (models/gdn.py), "mamba2" (models/mamba.py): each file's docstring says what it computes — or "none": the block has no mixer,
    # it is a feed-forward alone under its one norm (`mlp_norm`).
    mixer: str = "attention"
    # False: the block is a mixer alone under its one norm (`attn_norm`): no
    # second norm, no feed-forward, no such leaves in its stack.
    feed_forward: bool = True
    # A norm AFTER each sublayer too, on what the residual takes (`x + RMS(mixer(RMS(x)))`: four norms a block,
    # Ouro's; leaves `attn_post_norm`, `mlp_post_norm`).  A block of a mixer and a dense feed-forward.
    post_norms: bool = False


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
    # A looped model (Ouro, arXiv:2510.25741): the layers run `loop_steps` times over the SAME weights, the final
    # norm after every pass, its output the next pass's input; `_decoder` hands back every pass's normed state
    # [loop_steps, B, S, E].  `loop_scan`: the passes are a `lax.scan` of the program (the layers traced once, one
    # running gradient a weight in the scan's backward carry) and not `loop_steps` copies of the walk.
    loop_steps: int = 1
    loop_scan: bool = False
    # The exit-weighted loss of such a model: every pass's state goes through the head and — all but the last —
    # through a gate (`exit_gate`: a vector of d_model and a bias, float32; lambda = sigmoid(h w + b)); a token
    # leaves after pass t with p_t = lambda_t prod_{j<t} (1 - lambda_j), the last pass takes what is left, and the
    # loss is mean_i [sum_t p_t loss_t - exit_beta H(p)].  None: no gate, the (last) state's mean cross-entropy.
    exit_beta: Optional[float] = None
    # Block-diffusion training (BD3-LM, arXiv:2503.09573, as SDAR, arXiv:2510.06303, adapts it): the objective is
    # `_block_diffusion_loss` — a noised and a clean copy of every sequence in one stream of 2 x S positions under a
    # three-part block mask (ops/attention.py `_bd_visible`), a 1/t-weighted cross-entropy over the masked tokens —
    # with blocks of `bd_block_length` tokens; None: next-token cross-entropy over one causal stream.  The noise
    # is a function of the batch's ids and `bd_noise_seed` alone (`block_diffusion_noise`).
    bd_block_length: Optional[int] = None
    bd_noise_seed: int = 0
    # Gated DeltaNet (LayerKind.mixer "gdn"; the kind's heads are its VALUE heads): the key heads they share, a key
    # and a value head's widths, the kernel of the depthwise causal convolution on q, k and v.
    gdn_key_heads: int = 16
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    # Zero-centred norm weights (Qwen3-Next's): `rms_norm(x, 1 + w)` at a block's two norms, the final norm and the
    # per-head QK-norm's two — a weight starts at 0 — and NOT at a mixer's own head norm (models/mixer.py `_unit`).
    norm_unit_offset: bool = False
    # A sigmoid gate a COLUMN on attention's output, from the layer's normed input: `attn * sigmoid(h W_g)`, W_g:
    # embed -> heads * d_head ("attn_out_gate"; arXiv:2505.06708's elementwise form, where `attn_head_gate` is a
    # number a head).
    attn_out_gate: bool = False
    # The shared expert under a sigmoid gate a token: `sigmoid(h w_s) * Shared(h)`, w_s: embed -> 1 ("shared_scale",
    # float32; models/moe.py).
    moe_shared_gate: bool = False

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
        assert not (self.attn_head_gate and self.attn_out_gate), "a gate a head or a gate a column"
        assert not self.moe_shared_gate or self.moe_shared_experts, "the gate is a shared expert's"
        if self.norm_unit_offset:
            assert not (self.qk_norm or self.mla_kv_rank or self.loop_steps > 1 or self.bd_block_length is not None
                        or self.moe_router_state or any(kind.post_norms for kind in self.pattern)), (
                "the unit offset is written for a block's two norms, the final norm and the per-head QK-norm")
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
            assert all(kind.mixer == "none" or kind.mixer in MIXERS for kind in self.pattern), (
                f"a kind's mixer is one of {sorted(MIXERS)}, or 'none'")
            for kind in self.pattern:
                mixer = _mixer(kind)
                assert (kind.feed_forward or not kind.sparse) and (kind.feed_forward or mixer is not None), (
                    "a block is a mixer, a feed-forward, or both")
                assert not (self.moe_router_early and mixer is None), (
                    "an early router reads the input of a block that has a mixer")
                if mixer is not None and mixer.check is not None:
                    mixer.check(self, kind)
            assert all(a == b for a in self.pattern for b in self.pattern if a.stack == b.stack), "one kind a stack"
            assert all(self.moe_experts > 0 for kind in self.pattern if kind.sparse)
            assert all(kind.feed_forward and not kind.sparse and kind.mixer != "none"
                       for kind in self.pattern if kind.post_norms), "post-norms: a mixer and a dense feed-forward"
        assert self.loop_steps >= 1 and (self.exit_beta is None or self.loop_steps > 1), "the exit gate is a looped model's"
        if self.bd_block_length is not None:
            assert self.bd_block_length > 0 and self.attention == "flash" and not self.dsa_index_heads and self.loop_steps == 1, (
                "block diffusion runs the flash backend's mask over a plain stack, without an indexer")
            assert all(kind.window is None and kind.mixer in ("attention", "mla", "cca", "none") for kind in self.layers), (
                "a doubled stream's mixers are softmax attention over all of the past: no window, no recurrence")
            assert not self.moe_router_state, "a router's state is carried along one causal stream"
        if self.loop_steps > 1:
            assert not (self.moe_experts or self.dsa_index_heads or self.moe_router_state or self.tied_head), (
                "the layers that run several times are dense ones under an untied head: no statistics a layer")
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


def _mixer(kind: LayerKind) -> Optional[Mixer]:
    """The kind's entry of `MIXERS`; None: the block has no mixer ("none")."""
    return MIXERS.get(kind.mixer)


# Logical axis names for every parameter (see parallel/sharding.py).
def _layer_axes(cfg: TransformerConfig, kind: LayerKind) -> Dict[str, Any]:
    """A stack's leaves are its mixer's (`Mixer.axes`) under `attn_norm`, the
    learned merges', and the feed-forward's under `mlp_norm` — each only where
    the kind has the part."""
    layer: Dict[str, Any] = {}
    mixer = _mixer(kind)
    if mixer is not None:
        layer.update({"attn_norm": ("layers", "embed")}, **mixer.axes(cfg, kind))
    if cfg.scaled_merge:
        layer.update({"attn_merge": ("layers", None, "embed"), "mlp_merge": ("layers", None, "embed")})
    if kind.post_norms:
        layer.update({"attn_post_norm": ("layers", "embed"), "mlp_post_norm": ("layers", "embed")})
    if not kind.feed_forward:
        return layer
    layer["mlp_norm"] = ("layers", "embed")
    if kind.sparse:
        ffn = {
            "router": _state_router_axes() if cfg.moe_router_state else ("layers", "embed", "expert"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        }
        if cfg.moe_shared_experts:
            ffn.update({"shared_gate": ("layers", "embed", "mlp"), "shared_up": ("layers", "embed", "mlp"),
                        "shared_down": ("layers", "mlp", "embed")})
        if cfg.moe_shared_gate:
            ffn["shared_scale"] = ("layers", "embed", None)
    else:
        ffn = {"w_gate": ("layers", "embed", "mlp"), "w_up": ("layers", "embed", "mlp"), "w_down": ("layers", "mlp", "embed")}
    gated = cfg.moe_activation != "relu2"  # un-gated feed-forwards have no gate matrices
    layer.update({name: axes for name, axes in ffn.items() if gated or name not in ("w_gate", "shared_gate")})
    return layer


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
    if cfg.exit_beta is not None:
        axes["exit_gate"] = {"w": ("embed",), "b": (None,)}
    for stack, (kind, _) in cfg.stacks.items():
        axes[stack] = _layer_axes(cfg, kind)
    return axes


def _init_layers(key: jax.Array, cfg: TransformerConfig, L: int, kind: LayerKind) -> Dict[str, Any]:
    """A stack of L layers of one kind.  The key split in eight: the first
    four are the mixer's projections' (`Mixer.init` takes the stack's key and
    splits it so itself), the last four the feed-forward's."""
    pd = cfg.param_dtype
    E, sparse = cfg.d_model, kind.sparse

    def norm_init(k, shape, fan_in):
        return _norm_init(k, shape, fan_in, pd)

    ks = jax.random.split(key, 8)
    layers: Dict[str, Any] = {}
    mixer = _mixer(kind)
    one = _norm_start(cfg)
    if mixer is not None:
        layers.update({"attn_norm": one((L, E), pd)}, **mixer.init(key, cfg, L, kind))
    if cfg.scaled_merge:
        merge = jnp.broadcast_to(jnp.asarray([1.0, 0.0, 1.0, 0.0], pd)[None, :, None], (L, 4, E))
        layers.update({"attn_merge": merge, "mlp_merge": jnp.array(merge)})  # two buffers: a step donates each leaf
    if kind.post_norms:
        layers.update({"attn_post_norm": jnp.ones((L, E), pd), "mlp_post_norm": jnp.ones((L, E), pd)})
    if not kind.feed_forward:
        return layers
    layers["mlp_norm"] = one((L, E), pd)
    gated = cfg.moe_activation != "relu2"  # un-gated feed-forwards have no gate matrices
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
        layers.update({"router": router, "w_up": norm_init(ku, (L, held, E, F), E), "w_down": norm_init(kd, (L, held, F, E), F)})
        if gated:
            layers["w_gate"] = norm_init(kg, (L, held, E, F), E)
        if cfg.moe_shared_experts:
            Fs = cfg.moe_shared_experts * F
            kg, ku, kd = jax.random.split(jax.random.fold_in(ks[7], 1), 3)
            layers.update({"shared_up": norm_init(ku, (L, E, Fs), E), "shared_down": norm_init(kd, (L, Fs, E), Fs)})
            if gated:
                layers["shared_gate"] = norm_init(kg, (L, E, Fs), E)
            if cfg.moe_shared_gate:
                layers["shared_scale"] = norm_init(jax.random.fold_in(ks[7], 2), (L, E, 1), E)
    else:
        F = cfg.dense_d_ff or cfg.d_ff
        layers.update({"w_up": norm_init(ks[5], (L, E, F), E), "w_down": norm_init(ks[6], (L, F, E), F)})
        if gated:
            layers["w_gate"] = norm_init(ks[4], (L, E, F), E)
    return layers


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
        "final_norm": _norm_start(cfg)((E,), pd),
    }
    if not cfg.tied_head:
        params["lm_head"] = _norm_init(k_head, (E, cfg.vocab_size), E, pd)
    if cfg.exit_beta is not None:  # lambda starts near 1/2: p near (1/2, 1/4, 1/8, 1/8) over four passes
        params["exit_gate"] = {"w": _norm_init(jax.random.fold_in(k_head, 1), (E,), E, pd), "b": jnp.zeros((1,), pd)}
    # The last kind's stack draws from `k_layers` itself, each kind before it
    # from a key folded out of it (a model of one kind with leading dense
    # layers: "layers", then "dense_layers").
    for i, (stack, (kind, count)) in enumerate(reversed(cfg.stacks.items())):
        params[stack] = _init_layers(jax.random.fold_in(k_layers, i) if i else k_layers, cfg, count, kind)
    return params


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
    mixer = _mixer(kind)
    if mixer is None:  # a feed-forward alone: its one norm is `_feed_forward`'s
        return _feed_forward(cfg, mesh, rules, x, w, kind, router_bias, router_state, None)

    routed = None
    if cfg.moe_router_early and kind.sparse:
        from torchft_tpu.models.moe import routing

        routed = routing(x, w["router"], **_router_form(cfg, router_bias, router_state))
    # The scopes are the parts a profile's device time is booked to
    # (obs/spans.PARTS); they name the work and change no instruction.
    with jax.named_scope("norm"):
        h = rms_norm(x, _unit(cfg, w["attn_norm"]), cfg.rms_eps)
    y, mixer_stats = mixer.forward(cfg, kind, mesh, rules, h, w, positions)
    if kind.post_norms:
        with jax.named_scope("norm"):
            y = rms_norm(y, w["attn_post_norm"], cfg.rms_eps)
    with jax.named_scope("attn_proj"):
        x = constrain(_merge(x, y, w.get("attn_merge")), ("batch", "seq", "embed"), mesh, rules)
    return _after_the_mixer(cfg, mesh, rules, x, w, kind, router_bias, router_state, mixer_stats, routed)


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
        h = rms_norm(x, _unit(cfg, w["mlp_norm"]), cfg.rms_eps)
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
            shared_scale=w["shared_scale"] if cfg.moe_shared_gate else None,
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
            y = hidden @ w["w_down"].astype(cfg.dtype)
            if kind.post_norms:
                with jax.named_scope("norm"):
                    y = rms_norm(y, w["mlp_post_norm"], cfg.rms_eps)
            x = _merge(x, y, w.get("mlp_merge"))
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
    if cfg.bd_block_length is not None:  # the stream is [noised copy | clean copy]: a token's place in its sequence, twice
        pos = pos % (S // 2)
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
    # The same where the layers run several times: a weight's gradient is a sum over the passes, and each term is
    # added to the running sum where its layer's backward pass ends.
    grads_inside = head_row_block(B * S, padded_vocab(cfg.vocab_size)) is not None or cfg.loop_steps > 1
    stats = cfg.moe_experts > 0 or cfg.dsa_index_heads > 0  # a layer's aux is a dict of statistics
    aux_total = jnp.zeros((), jnp.float32)
    pieces, pending = [], []  # the layers' statistics: stacked runs, and layers still to be stacked

    def flush():
        if pending:
            pieces.append(jax.tree.map(lambda *a: jnp.stack(a), *pending))
            pending.clear()

    # A mixer's mean statistic (a decay's mean) is a scalar a layer of that mixer alone: summed by name, apart from
    # the statistics every layer of a run has and the runs stack.
    means = _mean_statistics(cfg)
    totals = {name: jnp.zeros((), jnp.float32) for name in means}

    def without_means(aux):
        """A layer's (or run's) statistics without its mixer's mean statistic, which goes to its total."""
        counted = means.keys() & aux.keys() if isinstance(aux, dict) else ()
        if not counted:
            return aux
        aux = dict(aux)
        for name in counted:
            totals[name] = totals[name] + jnp.sum(aux.pop(name))
        return aux or jnp.zeros((), jnp.float32)


    # A looped model's passes read each layer's slice of the stacked weights ONCE, and a pass hands the slice on to
    # the next THROUGH the layer's barrier (`_grads_inside` returns the weights it was given): backward, the
    # cotangent that arrives there is the later passes' sum plus this pass's term, and the barrier holds the layer
    # before's backward pass until that sum exists.  One running float32 gradient a weight — left to itself the
    # compiler keeps every pass's term until the end of the program (three more copies of the layers' gradient at
    # four passes: 4.9 GB at Ouro's 8 layers, compiled for a described v5e, PR 63) — stacked once at the end.
    sliced: Dict[Any, Any] = {}

    def layer_weights(stack: str, i: int):
        if (stack, i) in sliced:
            return sliced[stack, i]
        w = jax.tree.map(lambda a: a[i], params[stack])
        if cfg.loop_steps > 1:
            sliced[stack, i] = w
        return w

    if cfg.loop_steps > 1:  # outside the passes (a scan's body closes over the slices)
        with jax.named_scope("stack"):
            for stack, (kind, count) in cfg.stacks.items():
                if count <= cfg.scan_unroll:
                    for i in range(count):
                        layer_weights(stack, i)

    def walk(x, aux_total):
        """The walk of the pattern, once: runs of one kind, each through its own stack;
        router_bias's rows by a layer's place among the SPARSE layers, whatever their stack."""
        at = {stack: 0 for stack in cfg.stacks}  # the next layer of each stack
        sparse_at = 0
        for kind, run in itertools.groupby(cfg.layers):
            count, first = len(list(run)), at[kind.stack]
            at[kind.stack] += count
            with_stats = stats and (kind.sparse or cfg.dsa_index_heads > 0)
            stacked = params[kind.stack]
            bias, bias_first = (router_bias if kind.sparse else None), sparse_at
            sparse_at += count * kind.sparse

            # a static run of a looped model's static passes: the layer hands its weights on (above); a scan
            # over the passes sums a weight's terms in its backward carry
            chained = cfg.loop_steps > 1 and not cfg.loop_scan and count <= cfg.scan_unroll

            def body(x, w, kind=kind, chained=chained):
                if grads_inside:
                    x, w = _grads_inside(x, w)
                taken = dict(w)
                x, aux = _layer(cfg, mesh, rules, x, taken, positions, kind=kind, router_bias=taken.pop("router_bias", None))
                return x, ((aux, w) if chained else aux)

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
                        w = layer_weights(kind.stack, first + n)
                        x, aux = body(x, w if bias is None else dict(w, router_bias=bias[bias_first + n]))
                        if chained:
                            aux, sliced[kind.stack, first + n] = aux
                    aux = without_means(aux)
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
            aux_layers = without_means(aux_layers)
            if with_stats:
                flush()
                pieces.append(aux_layers)
            elif not stats:
                aux_total = aux_total + jnp.sum(aux_layers)
        return x, aux_total

    if cfg.loop_steps > 1:
        return _passes(cfg, lambda x: walk(x, jnp.zeros((), jnp.float32))[0], x, params["final_norm"]), aux_total
    x, aux_total = walk(x, aux_total)
    if cfg.moe_router_state:
        x, _ = x  # the last layer's state goes nowhere
    if not stats:
        return x, aux_total
    with jax.named_scope("stack"):
        flush()
        whole = pieces[0] if len(pieces) == 1 else jax.tree.map(lambda *a: jnp.concatenate(a), *pieces)
        out = _over_layers(whole)
        for name, (_, layers) in means.items():
            out[name] = totals[name] / layers
        return x, out


def _passes(cfg: TransformerConfig, walk, x, final_norm):
    """A looped model's passes: `walk` (the layers, first to last) `loop_steps`
    times over the same weights, the final norm after every pass and its output
    the next pass's input.  x [B, S, E] -> every pass's normed state
    [loop_steps, B, S, E].  The final norm is `head_loss`'s, as a plain model's."""
    def one(x, _=None):
        x = walk(x)
        with jax.named_scope("head_loss"):
            x = rms_norm(x, final_norm, cfg.rms_eps)
        return x, x

    if cfg.loop_scan:
        return jax.lax.scan(one, x, None, length=cfg.loop_steps)[1]
    states = []
    for _ in range(cfg.loop_steps):
        x = one(x)[0]
        states.append(x)
    return jnp.stack(states)


def _mean_statistics(cfg: TransformerConfig) -> Dict[str, Tuple[str, int]]:
    """{a statistic's name: (its counter's, the layers that count it)} over the
    mixers of the model's layers (`Mixer.mean_statistic`)."""
    found: Dict[str, Tuple[str, int]] = {}
    for kind, count in cfg.stacks.values():
        mixer = _mixer(kind)
        if mixer is not None and mixer.mean_statistic is not None:
            name, counter = mixer.mean_statistic
            found[name] = (counter, found.get(name, (counter, 0))[1] + count)
    return found


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
    # what each of the model's mixers keeps of a layer (`Mixer.saved_names`)
    mixers = [_mixer(kind) for kind, _ in cfg.stacks.values()]
    names = sorted({name for mixer in mixers if mixer is not None for name in mixer.saved_names})
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
    if cfg.loop_steps > 1:  # the last pass's state, which left the passes normed
        return head(params, x[-1], cfg, mesh, rules, normed=True), aux
    return head(params, x, cfg, mesh, rules), aux


def head(
    params: Dict[str, Any],
    x: jax.Array,
    cfg: TransformerConfig,
    mesh=None,
    rules: Optional[ShardingRules] = None,
    normed: bool = False,
) -> jax.Array:
    """Final norm + lm head: decoder output [B, S, E] -> logits [B, S, V].
    ``normed``: x is a looped model's state, which the passes left normed.

    Shared by the dense path (forward_with_aux) and the pipelined path
    (parallel/pipeline.pipeline_loss_fn) so the two can never diverge."""
    with jax.named_scope("head_loss"):
        if not normed:
            x = rms_norm(x, _unit(cfg, params["final_norm"]), cfg.rms_eps)
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
            h = rms_norm(x, _unit(cfg, params["final_norm"]), cfg.rms_eps)
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


def lm_head_losses(params: Dict[str, Any], h: jax.Array, cfg: TransformerConfig, targets: jax.Array, mesh=None,
                   rules: Optional[ShardingRules] = None) -> jax.Array:
    """The next-token loss of every row, [B * S] float32, from a looped
    model's NORMED state h [B, S, E] (`_passes`): `lm_head_loss` without its
    norm and its mean, for a loss that weighs the rows itself.  On one TPU
    device the `tpuft_ce_*` kernels (`fused_linear_cross_entropy_per_row`:
    the backward reads a cotangent a row); elsewhere the plain XLA form."""
    from torchft_tpu.ops.cross_entropy import (
        fused_ce_applicable, fused_linear_cross_entropy_per_row, fused_linear_cross_entropy_per_row_padded, head_row_block,
        padded_vocab)

    B, S, E = h.shape
    whole = head_row_block(B * S, padded_vocab(cfg.vocab_size)) is None and not cfg.tied_head
    with jax.named_scope("head_loss"):
        if whole and fused_ce_applicable(B * S, E, padded_vocab(cfg.vocab_size), mesh):
            # a vocabulary slice that no block divides: the same kernels over zero-padded columns (`lm_head_loss`)
            fused = fused_linear_cross_entropy_per_row if cfg.vocab_size % 128 == 0 else fused_linear_cross_entropy_per_row_padded
            return fused(h.reshape(B * S, E), params["lm_head"].astype(cfg.dtype), targets.reshape(B * S))
        logits = head(params, h, cfg, mesh, rules, normed=True)
        picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(logits, axis=-1) - picked).reshape(B * S)


def _looped_loss(params: Dict[str, Any], states: jax.Array, cfg: TransformerConfig, targets: jax.Array, mesh=None,
                 rules: Optional[ShardingRules] = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """A looped model's loss from every pass's normed state [T, B, S, E], and
    its counters.  Without a gate (`exit_beta` None): the last pass's mean
    next-token loss, and nothing counted.  With it, every pass's head (the one
    untied head, a pass after another) and the exit distribution of every
    token — p_t = lambda_t prod_{j<t} (1 - lambda_j) with lambda_t =
    sigmoid(h_t w + b) for t < T, and p_T what is left — in float32 and in
    logarithms (log p_t is a sum of log-sigmoids):
    ``mean_i [sum_t p_t loss_t - exit_beta H(p)]``.  Counters:
    `loop_exit_mass` [T] (sum_i p_t), `loop_pass_loss` [T] (mean_i loss_t),
    `loop_exit_entropy` (mean_i H(p))."""
    T, B, S, E = states.shape
    if cfg.exit_beta is None:
        return jnp.mean(lm_head_losses(params, states[-1], cfg, targets, mesh, rules)), {}
    losses = jnp.stack([lm_head_losses(params, states[t], cfg, targets, mesh, rules) for t in range(T)])  # [T, N]
    with jax.named_scope("exit_gate"):
        gate = params["exit_gate"]
        h = states[:-1].reshape(T - 1, B * S, E).astype(jnp.float32)
        logit = jnp.einsum("tne,e->tn", h, gate["w"].astype(jnp.float32)) + gate["b"].astype(jnp.float32)
        stay = jnp.cumsum(jax.nn.log_sigmoid(-logit), axis=0)  # log prod_{j<=t} (1 - lambda_j)
        before = jnp.concatenate([jnp.zeros((1, B * S), jnp.float32), stay[:-1]])
        log_p = jnp.concatenate([jax.nn.log_sigmoid(logit) + before, stay[-1:]])  # [T, N]
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
        loss = jnp.mean(jnp.sum(p * losses, axis=0) - cfg.exit_beta * entropy)
        counters = {"loop_exit_mass": jnp.sum(p, axis=1), "loop_pass_loss": jnp.mean(losses, axis=1),
                    "loop_exit_entropy": jnp.mean(entropy)}
    return loss, counters


_BD_GRID = 23  # levels and draws live on float32's uniform grid, the multiples of 2**-23
_BD_EPS = round(1e-3 * 2 ** _BD_GRID)  # the linear schedule's first thousandth (LLaDA's and BD3-LM's eps), on the grid


def block_diffusion_noise(tokens: jax.Array, block_length: int, noise_seed: int) -> Tuple[jax.Array, jax.Array]:
    """(masked [B, S] bool, level [B, S] float32) of a batch of token ids [B, S]: every block of ``block_length``
    consecutive tokens of a sequence draws a level ``t = eps + (1 - eps) u``, u ~ U[0, 1), eps = 1e-3, and each of
    its tokens is masked independently with probability t (``v < t``, v ~ U[0, 1)): a masked token's weight 1 / t
    is at most 1,000.  u and v are 23 random bits over 2**23 (what `jax.random.uniform` draws in float32) under a
    key that is a function of the SEQUENCE'S ids and ``noise_seed`` alone — the seed's key folded with a 32-bit sum
    of the ids weighted by position — so a step that runs again (a failed commit vote, a heal's replay) and the
    plain reference see the same masked batch, whatever else the batch holds.  The level is worked out in INTEGERS,
    rounded down to the grid: a product and a sum in float32 are one rounding or two as a backend fuses them, an
    ulp apart inside a jitted step and outside one."""
    B, S = tokens.shape
    assert S % block_length == 0, "a sequence is whole blocks"

    def one(ids):
        place = jnp.arange(S, dtype=jnp.uint32) * jnp.uint32(2654435761) + jnp.uint32(1)
        k_level, k_token = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(noise_seed), jnp.sum(ids.astype(jnp.uint32) * place)))
        u = jax.random.bits(k_level, (S // block_length,), jnp.uint32) >> (32 - _BD_GRID)
        # T = EPS + u - ceil(u EPS / 2**23), from EPS to 2**23 - 1.  u EPS passes 32 bits, so u goes in as its
        # high 11 and low 12 bits: ceil((u1 2**12 + u0) EPS / 2**23) = (u1 EPS + (u0 EPS + 2**23 - 1 >> 12)) >> 11.
        up = ((u >> 12) * _BD_EPS + (((u & jnp.uint32(0xFFF)) * _BD_EPS + (2 ** _BD_GRID - 1)) >> 12)) >> 11
        level = jnp.repeat(_BD_EPS + u - up, block_length)
        draw = jax.random.bits(k_token, (S,), jnp.uint32) >> (32 - _BD_GRID)
        return draw < level, level.astype(jnp.float32) * 2.0 ** -_BD_GRID  # both exact: a level is under 2**24

    return jax.vmap(one)(tokens)


def block_diffusion_stream(tokens: jax.Array, cfg: TransformerConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(stream [B, 2 S] int32, masked [B, S] bool, weight [B, S] float32) of a batch of token ids [B, S]: the
    noised copy — a masked token is the vocabulary's LAST id, which stands for [MASK]; the mask itself is carried as
    booleans, so a data token with that id is an ordinary token — then the clean copy; and the loss's weight a
    token, 1 / t where it is masked and 0 where it is not."""
    with jax.named_scope("bd_noise"):
        masked, level = block_diffusion_noise(tokens, cfg.bd_block_length, cfg.bd_noise_seed)
        weight = jnp.where(masked, 1.0 / level, 0.0)
        return jnp.concatenate([jnp.where(masked, cfg.vocab_size - 1, tokens), tokens], axis=1), masked, weight


def _block_diffusion_loss(params: Dict[str, Any], tokens: jax.Array, cfg: TransformerConfig, mesh=None,
                          rules: Optional[ShardingRules] = None, router_bias: Optional[jax.Array] = None):
    """The block-diffusion objective of a batch of token ids [B, S], and the decoder's statistics: the stream
    ``[noised copy | clean copy]`` of 2 S positions (`block_diffusion_stream`) through the decoder once, positions
    ``0 .. S - 1`` twice, then the head over the NOISED half alone, position i predicting token i (no shift), each row
    under its weight: ``sum_i m_i / t_i * CE_i / (B S)``.  Counters: `bd_masked_share` (masked tokens over data tokens:
    near 1/2), `bd_weight_mean` (the mean of m / t: near 1, so a schedule that drifts shows) and `bd_live_pairs_share`
    (the visible (query, key) pairs under the attention walk's steps, `ops/attention.py` `bd_pairs_walked`, over all
    (2 S)**2: (S**2 + S b) / (2 S)**2 where the walk has every live tile)."""
    B, S = tokens.shape
    stream, masked, weight = block_diffusion_stream(tokens, cfg)
    x, aux = _decoder(params, stream, cfg, mesh, rules, router_bias)
    with jax.named_scope("head_loss"):
        h = rms_norm(x[:, :S], params["final_norm"], cfg.rms_eps)
    losses = lm_head_losses(params, h, cfg, tokens, mesh, rules)
    with jax.named_scope("head_loss"):
        loss = jnp.sum(weight.reshape(B * S) * losses) / (B * S)
        counters = {"bd_masked_share": jnp.mean(masked.astype(jnp.float32)), "bd_weight_mean": jnp.mean(weight),
                    "bd_live_pairs_share": jnp.float32(bd_pairs_walked(2 * S, cfg.bd_block_length) / (2 * S) ** 2)}
    return loss, aux, counters


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
    times ``d_ff``); for a looped model under the exit-weighted loss what
    `_looped_loss` lists; for a dense model nothing.  ``router_bias`` [n_sparse_layers,
    n_experts] is the sigmoid router's choice bias: a constant, no leaf of
    ``params``, so neither the gradient nor the optimizer sees it."""
    if cfg.bd_block_length is not None:  # `batch["targets"]`, the tokens one place on, is not read
        loss, aux, counters = _block_diffusion_loss(params, batch["tokens"], cfg, mesh, rules, router_bias)
    else:
        x, aux = _decoder(params, batch["tokens"], cfg, mesh, rules, router_bias)
        if cfg.loop_steps > 1:
            return _looped_loss(params, x, cfg, batch["targets"], mesh, rules)
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
        counters.update({counter: aux[name] for name, (counter, _) in _mean_statistics(cfg).items()})
        if cfg.moe_shared_gate:  # the mean of the shared expert's gate over positions and sparse layers
            counters.update(moe_shared_gate_mean=aux["shared_gate"] / cfg.n_sparse_layers)
        if cfg.moe_skip:
            counters.update(moe_skipped=aux["skipped"])
        if cfg.moe_held is not None:
            counters.update(moe_assignments=aux["assignments"], moe_rows_held=aux["rows_held"])
        if "active_units" in aux:
            assert cfg.n_sparse_layers * batch["tokens"].size * cfg.moe_top_k * cfg.d_ff < 2 ** 31, "int32 counters"
            counters.update(moe_active_units=aux["active_units"],
                            moe_units_held=(aux["rows_held"] - aux["dropped"]) * cfg.d_ff)
        return loss, counters