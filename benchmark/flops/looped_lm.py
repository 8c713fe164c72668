"""Operations a looped decoder-only LM (Ouro: `total_ut_steps` passes over the
same dense layers, a head and an exit gate after every pass) needs for one
training token.

The count MFU is taken against, as `flops/dense_lm.py` counts a dense model:
matrix multiplications only, 6 operations a matmul parameter and token (2
forward, 4 backward), causal attention over the positions at or before the
token, no embedding gather, nothing for recomputation.  A parameter of a layer
takes part in T products a token, one a pass, and so does the head (every pass's
state goes through it); the gate's vector meets the states of all passes but
the last.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters of ONE layer: wq, wo, wk, wv and the gated feed-forward's three."""
    hidden, heads, kv, dim = (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
                              config["head_dim"])
    return 2 * hidden * heads * dim + 2 * hidden * kv * dim + 3 * hidden * config["intermediate_size"]


def matmul_params_applied(config: Dict[str, Any]) -> int:
    """Matmul parameters a token meets in one step, each counted as often as
    it is applied: the layers' and the head's T times, the gate's T - 1."""
    passes, hidden = config["total_ut_steps"], config["hidden_size"]
    return (passes * (config["num_hidden_layers"] * layer_matmul_params(config) + hidden * config["vocab_size"])
            + (passes - 1) * hidden)


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of causal attention for one token,
    averaged over a sequence of `seq_len`, over all layers and passes."""
    visible = (seq_len + 1) / 2.0
    forward = 2 * (2 * config["num_attention_heads"] * config["head_dim"] * visible)  # QK^T and PV
    return config["total_ut_steps"] * config["num_hidden_layers"] * 3 * forward


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params_applied(config) + attention_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """The weights the tree holds, each once: the layers with their four norms,
    embedding, head, final norm, the gate's vector and bias."""
    hidden = config["hidden_size"]
    return (config["num_hidden_layers"] * (layer_matmul_params(config) + 4 * hidden)
            + 2 * config["vocab_size"] * hidden + hidden + hidden + 1)
