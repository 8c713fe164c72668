"""Operations a sparse LM of Gated DeltaNet and output-gated attention
(Qwen3-Next-80B-A3B) needs for one training token ON ONE CHIP'S SHARE of its
experts.

As `flops/kda_mla_moe_lm.py`: matrix multiplications only, 6 operations per
weight of a matrix that multiplies the token's activation (2 forward, 4
backward), plus each mixer's own work.  What differs:

- a Gated DeltaNet layer's projections are Wq, Wk (hidden x 16 x 128), Wv, Wz
  (hidden x 32 x 128), Wb, Wa (hidden x 32) and Wo; its mixer work is the
  chunked recurrence's REQUIRED operations with a decay a head, forward and
  backward, as `flops/tpuft_gdn.py` counts them at its stated chunk size — no
  softmax attention and nothing quadratic in the sequence;
- an attention layer's projections are Wq and the column gate (hidden x 16 x
  256 each), Wk, Wv (hidden x 2 x 256) and Wo, and attention over the causal
  pairs (QK^T and PV at 256, 16 query heads) is counted in the ATTENTION layers
  only: every `full_attention_interval`-th within the depth;
- every layer's router, shared expert and the shared expert's gate meet every
  token; of the routed experts a token meets `num_experts_per_tok` of the
  router's outputs and of those the share held here in expectation: 10 x 32/512
  of one expert a token.

The embedding is a gather and counts nothing; nor do the short convolution,
norms and gates (elementwise), the row moves, padding, or any recomputation.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List

from benchmark import spec

_GDN = spec._module("flops", "tpuft_gdn", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _router_outputs(config: Dict[str, Any]) -> int:
    return (config.get("expert_parallel") or {}).get("router_outputs", config["num_experts"])


def mixers(config: Dict[str, Any], layers: int = 0) -> List[str]:
    """"gdn" | "attention" of each of the first `layers` layers (0: the depth)."""
    every = config["full_attention_interval"]
    return ["attention" if (i + 1) % every == 0 else "gdn" for i in range(layers or config["num_hidden_layers"])]


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert's three projections (the shared expert is as wide)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def gdn_matmul_params(config: Dict[str, Any]) -> int:
    hidden = config["hidden_size"]
    wide_k = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    wide_v = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return hidden * (2 * wide_k + 2 * wide_v) + 2 * hidden * config["linear_num_value_heads"] + wide_v * hidden


def gdn_other_params(config: Dict[str, Any]) -> int:
    """The convolution's taps, A_log, dt_bias and the head norm."""
    wide = 2 * config["linear_num_key_heads"] * config["linear_key_head_dim"] + config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return config["linear_conv_kernel_dim"] * wide + 2 * config["linear_num_value_heads"] + config["linear_value_head_dim"]


def attention_matmul_params(config: Dict[str, Any]) -> int:
    hidden, dim = config["hidden_size"], config["head_dim"]
    return hidden * dim * (3 * config["num_attention_heads"] + 2 * config["num_key_value_heads"])


def held_experts_per_token(config: Dict[str, Any]) -> float:
    """Routed experts held HERE that a token meets, in expectation."""
    return config["num_experts_per_tok"] * config["num_experts"] / _router_outputs(config)


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activation on this chip."""
    hidden = config["hidden_size"]
    total = float(hidden * config["vocab_size"])
    for mixer in mixers(config):
        total += gdn_matmul_params(config) if mixer == "gdn" else attention_matmul_params(config)
        total += hidden * _router_outputs(config) + hidden + (1 + held_experts_per_token(config)) * expert_params(config)
    return total


def mixer_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of the mixers' own work for one token,
    averaged over a sequence of `seq_len`."""
    gdn = 3.0 * _GDN.forward_flops_per_position(config["linear_key_head_dim"], config["linear_value_head_dim"]) * config["linear_num_value_heads"]
    visible = (seq_len + 1) / 2.0
    attention = 3 * 2 * config["num_attention_heads"] * 2 * config["head_dim"] * visible
    return sum(gdn if mixer == "gdn" else attention for mixer in mixers(config))


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + mixer_flops_per_token(config, seq_len)


def _layer_params(config: Dict[str, Any], mixer: str, experts: int) -> int:
    """A layer's parameters with `experts` routed experts: the mixer, two norm
    vectors (and the attention layer's q and k norms), the router, the shared
    expert with its gate, the experts."""
    hidden = config["hidden_size"]
    mix = (gdn_matmul_params(config) + gdn_other_params(config) if mixer == "gdn"
           else attention_matmul_params(config) + 2 * config["head_dim"])
    return mix + 2 * hidden + hidden * _router_outputs(config) + hidden + (1 + experts) * expert_params(config)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: the layers within the depth with the
    experts held here, embedding, head and the final norm."""
    hidden = config["hidden_size"]
    return (2 * hidden * config["vocab_size"] + hidden
            + sum(_layer_params(config, mixer, config["num_experts"]) for mixer in mixers(config)))


def published_params(config: Dict[str, Any]) -> int:
    """The PUBLISHED model's parameters, from the file's `published` group:
    every layer with all the router's experts, the whole vocabulary."""
    published, hidden = config["published"], config["hidden_size"]
    return (2 * hidden * published["vocab_size"] + hidden
            + sum(_layer_params(config, mixer, published["num_experts"]) for mixer in mixers(config, published["num_hidden_layers"])))
