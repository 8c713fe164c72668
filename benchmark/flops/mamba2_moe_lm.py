"""Operations a sparse LM of Mamba-2 blocks, attention blocks and un-gated
expert blocks (the causal tower of Nemotron-Labs-TwoTower-30B-A3B-Base) needs
for one training token ON ONE CHIP'S SHARE of its experts.

As `flops/kda_mla_moe_lm.py`: matrix multiplications only, 6 operations per
weight of a matrix that multiplies the token's activation (2 forward, 4
backward), plus each mixer's own work.  A block is ONE part:

- `M`: W_in (hidden x (2 H P + 2 G N + H)) and W_out (H P x hidden); its mixer
  work is the chunked recurrence's REQUIRED operations, forward and backward,
  as `flops/tpuft_ssd.py` counts them at the published chunk size — nothing
  quadratic in the sequence;
- `*`: Wq, Wk, Wv, Wo at 32 query and 2 KV heads of 128, and attention over the
  causal pairs (QK^T and PV at 128, 32 heads);
- `E`: the router (all its outputs) and the shared expert meet every token; of
  the routed experts a token meets `num_experts_per_tok` of the router's
  outputs and of those the share held here in expectation: 6 x 8/128 of one
  expert a token; an expert is TWO matrices (un-gated).

The embedding is a gather and counts nothing; nor do the convolution, norms and
gates (elementwise), the row moves, padding (the experts' 64 zero columns up to
1,920 among it), or any recomputation.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark import spec

_SSD = spec._module("flops", "tpuft_ssd", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _router_outputs(config: Dict[str, Any]) -> int:
    return (config.get("expert_parallel") or {}).get("router_outputs", config["n_routed_experts"])


def _plan(config: Dict[str, Any]) -> str:
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert's two projections."""
    return 2 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config: Dict[str, Any]) -> int:
    return 2 * config["hidden_size"] * config["moe_shared_expert_intermediate_size"]


def _ssm_widths(config: Dict[str, Any]):
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    return inner, inner + 2 * config["n_groups"] * config["ssm_state_size"]


def mamba_matmul_params(config: Dict[str, Any]) -> int:
    inner, channels = _ssm_widths(config)
    return config["hidden_size"] * (inner + channels + config["mamba_num_heads"]) + inner * config["hidden_size"]


def mamba_other_params(config: Dict[str, Any]) -> int:
    """The convolution's taps and bias, dt_bias, A_log, D and the group norm."""
    inner, channels = _ssm_widths(config)
    return channels * config["conv_kernel"] + channels + 3 * config["mamba_num_heads"] + inner


def attention_params(config: Dict[str, Any]) -> int:
    hidden, dim = config["hidden_size"], config["head_dim"]
    return 2 * hidden * config["num_attention_heads"] * dim + 2 * hidden * config["num_key_value_heads"] * dim


def held_experts_per_token(config: Dict[str, Any]) -> float:
    """Routed experts held HERE that a token meets, in expectation."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] / _router_outputs(config)


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activation on this chip."""
    hidden = config["hidden_size"]
    total = float(hidden * config["vocab_size"])
    for letter in _plan(config):
        if letter == "M":
            total += mamba_matmul_params(config)
        elif letter == "*":
            total += attention_params(config)
        else:
            total += (hidden * _router_outputs(config) + shared_params(config)
                      + held_experts_per_token(config) * expert_params(config))
    return total


def mixer_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of the mixers' own work for one token,
    averaged over a sequence of `seq_len`."""
    plan = _plan(config)
    scan = 3.0 * _SSD.forward_flops_per_position(config) * config["mamba_num_heads"]
    visible = (seq_len + 1) / 2.0
    attention = 3 * 2 * config["num_attention_heads"] * 2 * config["head_dim"] * visible
    return plan.count("M") * scan + plan.count("*") * attention


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + mixer_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: the mixers, the held experts, the shared
    expert, the router, ONE norm vector a block, embedding, head and the final
    norm."""
    hidden = config["hidden_size"]
    total = 2 * hidden * config["vocab_size"] + hidden
    for letter in _plan(config):
        total += hidden
        if letter == "M":
            total += mamba_matmul_params(config) + mamba_other_params(config)
        elif letter == "*":
            total += attention_params(config)
        else:
            total += (hidden * _router_outputs(config) + shared_params(config)
                      + config["n_routed_experts"] * expert_params(config))
    return total
