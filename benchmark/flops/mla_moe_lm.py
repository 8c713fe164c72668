"""Operations a latent-attention, shared-expert sparse LM (the DeepSeek-V3
family) needs for one training token ON ONE CHIP'S SHARE of its experts.

As `flops/moe_lm.py`: matrix multiplications only, 6 operations per weight of a
matrix that multiplies the token's activation (2 forward, 4 backward), and
causal attention's products over the visible positions.  What differs:

- latent attention: the projections are Wq (hidden x heads x 192), Wkva
  (hidden x 576), Wkvb (512 x heads x 256) and Wo (heads x 128 x hidden); QK^T
  runs at 192 (128 + the 64 rotary columns) and PV at 128;
- the leading dense layers have a feed-forward of their own width;
- a sparse layer's router and shared expert meet every token; of the routed
  experts a token meets `num_experts_per_tok` of the router's outputs, and of
  those the share held here in expectation: 6 x 8/64 of one expert a token for
  Moonlight on an eighth of its experts.  What the other seven chips compute is
  their work, not this chip's.

The embedding is a gather and counts nothing; nor do the row moves, the zero
rows that pad an expert's rows to a tile, the zero columns that pad the query
and key to a lane, or any recomputation (`program.remat` recomputes a layer's
forward in the backward pass): work the algorithm does not require.
"""

from __future__ import annotations

from typing import Any, Dict


def _router_outputs(config: Dict[str, Any]) -> int:
    return (config.get("expert_parallel") or {}).get("router_outputs", config["n_routed_experts"])


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert's three projections."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def attention_params(config: Dict[str, Any]) -> int:
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v, rank = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    return hidden * heads * (nope + rope) + hidden * (rank + rope) + rank * heads * (nope + v) + heads * v * hidden


def held_experts_per_token(config: Dict[str, Any]) -> float:
    """Routed experts held HERE that a token meets, in expectation."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] / _router_outputs(config)


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activation on this chip."""
    hidden = config["hidden_size"]
    dense, sparse = config["first_k_dense_replace"], config["num_hidden_layers"] - config["first_k_dense_replace"]
    dense_layer = attention_params(config) + 3 * hidden * config["intermediate_size"]
    sparse_layer = (
        attention_params(config) + hidden * _router_outputs(config)
        + config["n_shared_experts"] * expert_params(config)
        + held_experts_per_token(config) * expert_params(config)
    )
    return dense * dense_layer + sparse * sparse_layer + hidden * config["vocab_size"]


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of causal attention for one token,
    averaged over a sequence of `seq_len`: QK^T at nope + rope, PV at v."""
    visible = (seq_len + 1) / 2.0
    widths = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    forward = 2 * config["num_attention_heads"] * widths * visible
    return config["num_hidden_layers"] * 3 * forward


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: the held experts, the shared expert,
    the router, attention with its three norm vectors a layer (two over the
    hidden size, one over the rank), embedding, head and the final norm."""
    hidden = config["hidden_size"]
    dense, sparse = config["first_k_dense_replace"], config["num_hidden_layers"] - config["first_k_dense_replace"]
    common = attention_params(config) + 2 * hidden + config["kv_lora_rank"]
    dense_layer = common + 3 * hidden * config["intermediate_size"]
    sparse_layer = (
        common + hidden * _router_outputs(config)
        + (config["n_routed_experts"] + config["n_shared_experts"]) * expert_params(config)
    )
    return dense * dense_layer + sparse * sparse_layer + 2 * hidden * config["vocab_size"] + hidden
