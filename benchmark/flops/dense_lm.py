"""Operations a dense decoder-only LM needs for one training token.

The count MFU is taken against: matrix multiplications only.  Each weight of a
matrix that multiplies the token's activation costs 2 operations forward and 4
backward (gradient of the input and of the weight): 6 per matmul parameter.
The embedding table is a gather, not a matmul, and counts nothing (`6 * n_params`
with the table included over-counts by its share: 30% of the parameters of
InternLM2-1.8B cut to 4 layers).  Causal attention adds, per layer, the QK^T
and PV products over the positions at or before the token: on average
(seq + 1) / 2 of them.  Recomputation (rematerialised layers, the flash
backward's second pass over the scores) is not counted: it is work the
algorithm does not require.
"""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that take part in a matmul per token: the layers'
    projections and the output head.  Norm weights and the embedding do not."""
    hidden, heads, kv = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    head_dim = config.get("head_dim", hidden // heads)
    attn = hidden * heads * head_dim * 2 + hidden * kv * head_dim * 2  # wq, wo; wk, wv
    mlp = 3 * hidden * config["intermediate_size"]
    return config["num_hidden_layers"] * (attn + mlp) + hidden * config["vocab_size"]


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of causal attention for one token,
    averaged over a sequence of `seq_len`: QK^T and PV are 2 * head_dim
    operations per head and visible position each, forward; twice that
    backward."""
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim", config["hidden_size"] // heads)
    visible = (seq_len + 1) / 2.0
    forward = 2 * (2 * heads * head_dim * visible)
    return config["num_hidden_layers"] * 3 * forward


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    hidden = config["hidden_size"]
    return matmul_params(config) + config["vocab_size"] * hidden + (2 * config["num_hidden_layers"] + 1) * hidden
