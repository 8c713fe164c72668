"""Operations a sparse LM whose router reads the layer's input and whose layers
mix window-4,096 and un-rotated full attention (SmallThinker-21BA3B-Instruct)
needs for one training token ON ONE CHIP'S SHARE of its experts.

As `flops/swa_moe_lm.py`: matrix multiplications only, 6 operations per weight
of a matrix that multiplies the token's activation (2 forward, 4 backward), and
attention's products over the pairs a layer sees.  What differs:

- every layer has 28 query heads over 4 KV heads of 128 and is sparse; a full
  layer attends to the causal half, (S + 1) / 2 keys a query on average, a
  window layer to min(position + 1, 4,096) keys: 58,722,304 of the 134,225,920
  causal pairs at 16,384 positions, 3,584.1 keys a query;
- the projections are Wq and Wo at 28 x 128, Wk and Wv at 4 x 128; there is no
  head gate, no shared expert and no dense layer;
- every layer has the router (all of its outputs: 64, on the layer's input) and
  of the routed experts `moe_num_active_primary_experts` x held / routed in
  expectation: 6 x 8/64 = three quarters of an expert a token on an eighth of
  them, each three matrices of [2,560, 768] (ReGLU costs what SwiGLU costs:
  the activation is no product).  What the other seven chips compute is their
  work, not this chip's.

The embedding is a gather and counts nothing; nor do the row moves, the zero
rows that pad an expert's rows to a tile, the tiles' masked parts, or any
recomputation (`program.remat` recomputes a layer's forward in the backward
pass): work the algorithm does not require.
"""

from __future__ import annotations

from typing import Any, Dict, List


def layers_of(config: Dict[str, Any]) -> List[str]:
    """"window" or "full" for each layer run."""
    n = config["num_hidden_layers"]
    return ["window" if flag else "full" for flag in config["sliding_window_layout"][:n]]


def _router_outputs(config: Dict[str, Any]) -> int:
    return (config.get("expert_parallel") or {}).get("router_outputs", config["moe_num_primary_experts"])


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert's three projections."""
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def attention_params(config: Dict[str, Any]) -> int:
    hidden, dim = config["hidden_size"], config["head_dim"]
    return 2 * hidden * config["num_attention_heads"] * dim + 2 * hidden * config["num_key_value_heads"] * dim


def held_experts_per_token(config: Dict[str, Any]) -> float:
    """Routed experts held HERE that a token meets, in expectation."""
    return config["moe_num_active_primary_experts"] * config["moe_num_primary_experts"] / _router_outputs(config)


def pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs of one sequence: the causal half, or the band."""
    causal = seq_len * (seq_len + 1) // 2
    if window is None or window >= seq_len:
        return causal
    return causal - (seq_len - window) * (seq_len - window + 1) // 2


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activation on this chip."""
    hidden = config["hidden_size"]
    a_layer = (attention_params(config) + hidden * _router_outputs(config)
               + held_experts_per_token(config) * expert_params(config))
    return float(hidden * config["vocab_size"]) + config["num_hidden_layers"] * a_layer


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of attention for one token, averaged
    over a sequence of `seq_len`: QK^T and PV at head_dim over the keys a query
    of the layer's kind sees."""
    total = 0.0
    for kind in layers_of(config):
        visible = pairs(seq_len, config["sliding_window_size"] if kind == "window" else None) / seq_len
        total += 3 * 2 * config["num_attention_heads"] * 2 * config["head_dim"] * visible
    return total


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: attention and two norm vectors a layer,
    the held experts and the router, embedding, head and the final norm."""
    hidden = config["hidden_size"]
    a_layer = (attention_params(config) + 2 * hidden + hidden * _router_outputs(config)
               + config["moe_num_primary_experts"] * expert_params(config))
    return 2 * hidden * config["vocab_size"] + hidden + config["num_hidden_layers"] * a_layer
