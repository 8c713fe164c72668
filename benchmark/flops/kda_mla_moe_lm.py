"""Operations a sparse LM of Kimi Delta Attention and unrotated latent
attention (Kimi-Linear-48B-A3B) needs for one training token ON ONE CHIP'S
SHARE of its experts.

As `flops/mla_moe_lm.py`: matrix multiplications only, 6 operations per weight
of a matrix that multiplies the token's activation (2 forward, 4 backward),
plus each mixer's own work.  What differs:

- a KDA layer's projections are Wq, Wk, Wv (hidden x heads x 128 each), the
  two low-rank gates (hidden x 128 and 128 x heads x 128 each), beta (hidden x
  heads) and Wo; its mixer work is the chunked recurrence's REQUIRED
  operations, forward and backward, as `flops/tpuft_kda.py` counts them at its
  stated chunk size (0.43 MFLOP a head and position) — no softmax attention and
  nothing quadratic in the sequence;
- a latent layer's projections are Moonlight's at this model's sizes, and
  attention over the causal pairs (QK^T at 192, PV at 128, 32 heads) is counted
  in the LATENT layers only: the layers of `full_attn_layers` within the depth;
- the leading dense layer's feed-forward has its own width; a sparse layer's
  router and shared expert meet every token; of the routed experts a token
  meets `num_experts_per_token` of the router's outputs and of those the share
  held here in expectation: 8 x 8/256 of one expert a token.

The embedding is a gather and counts nothing; nor do the short convolutions,
norms and gates (elementwise), the row moves, padding, or any recomputation.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark import spec

_KDA = spec._module("flops", "tpuft_kda", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _router_outputs(config: Dict[str, Any]) -> int:
    return (config.get("expert_parallel") or {}).get("router_outputs", config["num_experts"])


def _mixers(config: Dict[str, Any]):
    """("kda" | "mla", sparse) of each layer within the depth."""
    linear = config["linear_attn_config"]
    return [("kda" if i in linear["kda_layers"] else "mla", i > config["first_k_dense_replace"])
            for i in range(1, config["num_hidden_layers"] + 1)]


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert's three projections."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def kda_matmul_params(config: Dict[str, Any]) -> int:
    hidden, linear = config["hidden_size"], config["linear_attn_config"]
    wide, dim = linear["num_heads"] * linear["head_dim"], linear["head_dim"]
    return 4 * hidden * wide + 2 * (hidden * dim + dim * wide) + hidden * linear["num_heads"]


def kda_other_params(config: Dict[str, Any]) -> int:
    """The convolutions' taps, A, the two gate biases and the head norm."""
    linear = config["linear_attn_config"]
    wide = linear["num_heads"] * linear["head_dim"]
    return 3 * linear["short_conv_kernel_size"] * wide + linear["num_heads"] + 2 * wide + linear["head_dim"]


def mla_params(config: Dict[str, Any]) -> int:
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v, rank = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    return hidden * heads * (nope + rope) + hidden * (rank + rope) + rank * heads * (nope + v) + heads * v * hidden


def held_experts_per_token(config: Dict[str, Any]) -> float:
    """Routed experts held HERE that a token meets, in expectation."""
    return config["num_experts_per_token"] * config["num_experts"] / _router_outputs(config)


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activation on this chip."""
    hidden = config["hidden_size"]
    total = float(hidden * config["vocab_size"])
    for mixer, sparse in _mixers(config):
        total += kda_matmul_params(config) if mixer == "kda" else mla_params(config)
        if sparse:
            total += (hidden * _router_outputs(config) + config["num_shared_experts"] * expert_params(config)
                      + held_experts_per_token(config) * expert_params(config))
        else:
            total += 3 * hidden * config["intermediate_size"]
    return total


def mixer_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of the mixers' own work for one token,
    averaged over a sequence of `seq_len`."""
    linear = config["linear_attn_config"]
    kda = 3.0 * _KDA.forward_flops_per_position(linear["head_dim"]) * linear["num_heads"]
    visible = (seq_len + 1) / 2.0
    widths = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    latent = 3 * 2 * config["num_attention_heads"] * widths * visible
    return sum(kda if mixer == "kda" else latent for mixer, _ in _mixers(config))


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + mixer_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: the mixers, the held experts, the shared
    expert, the router, two norm vectors a layer (and the latent layers' one
    over the rank), embedding, head and the final norm."""
    hidden = config["hidden_size"]
    total = 2 * hidden * config["vocab_size"] + hidden
    for mixer, sparse in _mixers(config):
        total += 2 * hidden
        total += (kda_matmul_params(config) + kda_other_params(config) if mixer == "kda"
                  else mla_params(config) + config["kv_lora_rank"])
        if sparse:
            total += (hidden * _router_outputs(config)
                      + (config["num_experts"] + config["num_shared_experts"]) * expert_params(config))
        else:
            total += 3 * hidden * config["intermediate_size"]
    return total
