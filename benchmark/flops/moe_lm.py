"""Operations a sparse mixture-of-experts decoder-only LM needs for one
training token.

As `flops/dense_lm.py`: matrix multiplications only, 6 operations per weight
of a matrix that multiplies the token's activation (2 forward, 4 backward),
and causal attention's QK^T and PV over the visible positions.  Of a layer's
experts a token's activation meets `num_experts_per_tok`, so those are what
counts: 8 of OLMoE's 64, not all of them (`6 * n_params` would count 403M
expert parameters a layer where a token uses 50M).  The router is a matmul
and counts.  The embedding is a gather and counts nothing; nor do the moves
of rows to their experts and back, the zero rows that pad an expert's rows to
a tile, or any recomputation: work the algorithm does not require.
"""

from __future__ import annotations

from typing import Any, Dict


def _head_dim(config: Dict[str, Any]) -> int:
    return config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]


def expert_params(config: Dict[str, Any]) -> int:
    """One expert's three projections."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that multiply one token's activation: per layer the
    attention projections, the router and the token's `num_experts_per_tok`
    experts; and the output head."""
    hidden, heads, kv = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    attn = hidden * heads * _head_dim(config) * 2 + hidden * kv * _head_dim(config) * 2  # wq, wo; wk, wv
    router = hidden * config["num_experts"]
    experts = config["num_experts_per_tok"] * expert_params(config)
    return config["num_hidden_layers"] * (attn + router + experts) + hidden * config["vocab_size"]


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of causal attention for one token,
    averaged over a sequence of `seq_len` (as `flops/dense_lm.py`)."""
    visible = (seq_len + 1) / 2.0
    forward = 2 * (2 * config["num_attention_heads"] * _head_dim(config) * visible)
    return config["num_hidden_layers"] * 3 * forward


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: all experts, the embedding, the norms
    (two a layer over the hidden size, the query's and the key's over their
    projected widths, and the final one)."""
    hidden, heads, kv = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    layer = (
        hidden * heads * _head_dim(config) * 2 + hidden * kv * _head_dim(config) * 2
        + hidden * config["num_experts"] + config["num_experts"] * expert_params(config)
        + 2 * hidden + (heads + kv) * _head_dim(config)
    )
    return config["num_hidden_layers"] * layer + 2 * hidden * config["vocab_size"] + hidden
