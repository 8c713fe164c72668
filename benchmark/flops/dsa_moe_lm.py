"""Operations a sparse-attention, sparse-expert LM (Keye-VL-2.0-30B-A3B's
language model) needs for one training token ON ONE CHIP'S SHARE of its
experts.

As `flops/mla_moe_lm.py`: matrix multiplications only, 6 operations per weight
of a matrix that multiplies the token's activation (2 forward, 4 backward).
What differs:

- attention's products run over the SELECTED pairs only — a query keeps
  min(position + 1, topk) keys — QK^T and PV at the head width, forward and
  the four products backward.  A kernel that visits every causal tile does
  more than this and is not credited for it;
- the indexer scores every VISIBLE pair: per pair and index head one product
  at the index width forward, and backward the two products of its own loss
  (towards the index query and the index key).  Its three projections count as
  matrices; the selection passes (thresholds, counts, the mask) are no
  required work and count nothing;
- of the routed experts a token meets `num_experts_per_tok` of the router's
  outputs, and of those the share held here in expectation: 8 x 16/128 of one
  expert a token.

The embedding is a gather and counts nothing; nor do the row moves, padding,
the heads' probabilities formed again for the index loss, or any
recomputation.
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


def indexer_params(config: Dict[str, Any]) -> int:
    """The indexer's three projections (its LayerNorm's 2 x 64 apart)."""
    sa = config["sa_config"]
    return config["hidden_size"] * (sa["indexer_num_heads"] * sa["indexer_head_dim"] + sa["indexer_head_dim"]
                                    + sa["indexer_num_heads"])


def held_experts_per_token(config: Dict[str, Any]) -> float:
    return config["num_experts_per_tok"] * config["num_experts"] / _router_outputs(config)


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activation on this chip."""
    hidden = config["hidden_size"]
    layer = (attention_params(config) + indexer_params(config) + hidden * _router_outputs(config)
             + held_experts_per_token(config) * expert_params(config))
    return config["num_hidden_layers"] * layer + hidden * config["vocab_size"]


def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs one sequence's attention keeps, a layer."""
    short = min(seq_len, topk)
    return short * (short + 1) // 2 + (seq_len - short) * topk


def visible_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward products of attention over the selected pairs
    (2 forward, 4 backward, each 2 * head width operations a pair and head)
    and of the indexer over the visible pairs (1 forward, 2 backward, each
    2 * index width operations a pair and index head), a token."""
    sa = config["sa_config"]
    attend = 6 * 2 * config["head_dim"] * config["num_attention_heads"] * selected_pairs(seq_len, sa["topk"])
    index = 3 * 2 * sa["indexer_head_dim"] * sa["indexer_num_heads"] * visible_pairs(seq_len)
    return config["num_hidden_layers"] * (attend + index) / seq_len


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: the held experts, the router,
    attention with its two QK-norm vectors, the indexer with its LayerNorm,
    two norm vectors a layer, embedding, head and the final norm."""
    hidden, sa = config["hidden_size"], config["sa_config"]
    layer = (attention_params(config) + 2 * config["head_dim"] + indexer_params(config)
             + 2 * sa["indexer_head_dim"] + 2 * hidden + hidden * _router_outputs(config)
             + config["num_experts"] * expert_params(config))
    return config["num_hidden_layers"] * layer + 2 * hidden * config["vocab_size"] + hidden
