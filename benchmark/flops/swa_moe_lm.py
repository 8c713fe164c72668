"""Operations a sparse LM whose layers mix window and full attention
(Laguna-XS.2) needs for one training token ON ONE CHIP'S SHARE of its experts.

As `flops/mla_moe_lm.py`: matrix multiplications only, 6 operations per weight
of a matrix that multiplies the token's activation (2 forward, 4 backward), and
attention's products over the pairs a layer sees.  What differs:

- a layer's kind sets its query heads (48 full, 64 window) and the pairs it
  attends to: a full layer the causal half, (S + 1) / 2 keys a query on
  average; a window layer min(position + 1, window) keys, 504 of 8,192.5 at
  16,384 positions and a window of 512;
- the projections are Wq and Wo at the kind's heads x 128, Wk and Wv at the 8
  KV heads x 128, and the head gate (hidden x heads);
- layer 0's feed-forward is dense at `intermediate_size`; every other layer has
  the router (all of its outputs), the shared expert, and of the routed experts
  `num_experts_per_tok` x held / routed in expectation: 8 x 32/256 = one expert
  a token on an eighth of them.  What the other seven chips compute is their
  work, not this chip's.

The embedding is a gather and counts nothing; nor do the row moves, the zero
rows that pad an expert's rows to a tile, the tiles' masked halves, or any
recomputation (`program.remat` recomputes a layer's forward in the backward
pass): work the algorithm does not require.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def layers_of(config: Dict[str, Any]) -> List[Tuple[str, str, int]]:
    """(attention kind, feed-forward kind, query heads) of each layer run."""
    n = config["num_hidden_layers"]
    return list(zip(config["layer_types"][:n], config["mlp_layer_types"][:n],
                    config["num_attention_heads_per_layer"][:n]))


def _router_outputs(config: Dict[str, Any]) -> int:
    return (config.get("expert_parallel") or {}).get("router_outputs", config["num_experts"])


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert's three projections."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def attention_params(config: Dict[str, Any], heads: int) -> int:
    hidden, dim = config["hidden_size"], config["head_dim"]
    gate = hidden * heads if config["gating"] else 0
    return 2 * hidden * heads * dim + 2 * hidden * config["num_key_value_heads"] * dim + gate


def held_experts_per_token(config: Dict[str, Any]) -> float:
    """Routed experts held HERE that a token meets, in expectation."""
    return config["num_experts_per_tok"] * config["num_experts"] / _router_outputs(config)


def pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs of one sequence: the causal half, or the band."""
    causal = seq_len * (seq_len + 1) // 2
    if window is None or window >= seq_len:
        return causal
    return causal - (seq_len - window) * (seq_len - window + 1) // 2


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activation on this chip."""
    hidden = config["hidden_size"]
    total = float(hidden * config["vocab_size"])
    for _, ffn, heads in layers_of(config):
        total += attention_params(config, heads)
        if ffn == "dense":
            total += 3 * hidden * config["intermediate_size"]
        else:
            total += (hidden * _router_outputs(config) + 3 * hidden * config["shared_expert_intermediate_size"]
                      + held_experts_per_token(config) * expert_params(config))
    return total


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of attention for one token, averaged
    over a sequence of `seq_len`: QK^T and PV at head_dim over the keys a query
    of the layer's kind sees, at the kind's heads."""
    total = 0.0
    for attention, _, heads in layers_of(config):
        window = config["sliding_window"] if attention == "sliding_attention" else None
        visible = pairs(seq_len, window) / seq_len
        total += 3 * 2 * heads * 2 * config["head_dim"] * visible
    return total


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: attention with its gate and two norm
    vectors a layer, the dense feed-forward or the held experts, the shared
    expert and the router, embedding, head and the final norm."""
    hidden = config["hidden_size"]
    total = 2 * hidden * config["vocab_size"] + hidden
    for _, ffn, heads in layers_of(config):
        total += attention_params(config, heads) + 2 * hidden
        if ffn == "dense":
            total += 3 * hidden * config["intermediate_size"]
        else:
            total += (hidden * _router_outputs(config) + 3 * hidden * config["shared_expert_intermediate_size"]
                      + config["num_experts"] * expert_params(config))
    return total
