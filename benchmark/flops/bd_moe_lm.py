"""Operations a sparse-expert LM trained by block diffusion (SDAR-30B-A3B-Chat)
needs for one training DATA token ON ONE CHIP'S SHARE of its experts.

As `flops/dsa_moe_lm.py`: matrix multiplications only, 6 operations per weight
of a matrix that multiplies a position's activation (2 forward, 4 backward).
What differs is what a data token costs:

- the decoder runs the DOUBLED stream, a noised and a clean copy of every
  sequence: two positions a data token through every layer's attention
  projections, router and held experts.  A count over the data tokens alone
  would read `mfu` at half;
- the head runs over the noised half only: one row a data token;
- attention's products run over the LIVE pairs of the three-part block mask —
  a sequence of L tokens in blocks of b has L**2 + L b of its (2 L)**2 pairs
  visible (noised-noised inside a block: L b; noised-clean before the block:
  L (L - b) / 2; clean-clean up to the block: L (L + b) / 2) — QK^T and PV at
  the head width, forward and the four products backward.  A kernel that visits
  every tile of the square, or of a triangle over 2 L, does more than this and
  is not credited for it;
- of the routed experts a position meets `num_experts_per_tok` of the router's
  outputs, and of those the share held here in expectation: 8 x 16/128 of one
  expert a position.

The last layer's clean-half rows after its keys and values are read by
nothing; the program computes them (as the reference does, and throws them
away), so they are counted.  The embedding is a gather and counts nothing; nor
do the noise, the row moves, padding, or any recomputation.
"""

from __future__ import annotations

from typing import Any, Dict


def _router_outputs(config: Dict[str, Any]) -> int:
    return (config.get("expert_parallel") or {}).get("router_outputs", config["num_experts"])


def expert_params(config: Dict[str, Any]) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def attention_params(config: Dict[str, Any]) -> int:
    hidden, d = config["hidden_size"], config["head_dim"]
    return hidden * d * (2 * config["num_attention_heads"] + 2 * config["num_key_value_heads"])


def held_experts_per_position(config: Dict[str, Any]) -> float:
    return config["num_experts_per_tok"] * config["num_experts"] / _router_outputs(config)


def live_pairs(seq_len: int, block_length: int) -> int:
    """(query, key) pairs of one sequence's doubled stream that the mask keeps, a layer."""
    return seq_len * seq_len + seq_len * block_length


def matmul_params_per_token(config: Dict[str, Any]) -> float:
    """Parameters that multiply an activation on this chip, a DATA token: a
    layer's twice (the token's noised and its clean position), the head's once."""
    hidden = config["hidden_size"]
    layer = (attention_params(config) + hidden * _router_outputs(config)
             + held_experts_per_position(config) * expert_params(config))
    return 2 * config["num_hidden_layers"] * layer + hidden * config["vocab_size"]


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward products of attention over the live pairs (2
    forward, 4 backward, each 2 * head width operations a pair and head), a
    data token."""
    pairs = live_pairs(seq_len, config["block_diffusion"]["block_length"])
    attend = 6 * 2 * config["head_dim"] * config["num_attention_heads"] * pairs
    return config["num_hidden_layers"] * attend / seq_len


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params_per_token(config) + attention_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: the held experts, the router, attention
    with its two QK-norm vectors, two norm vectors a layer, embedding, head
    and the final norm."""
    hidden = config["hidden_size"]
    layer = (attention_params(config) + 2 * config["head_dim"] + 2 * hidden + hidden * _router_outputs(config)
             + config["num_experts"] * expert_params(config))
    return config["num_hidden_layers"] * layer + 2 * hidden * config["vocab_size"] + hidden
