"""Operations a sparse LM with compressed convolutional attention and a tied
head (ZAYA1-8B) needs for one training token ON ONE CHIP'S SHARE of its experts.

As `flops/mla_moe_lm.py`: matrix multiplications only, 6 operations per weight
of a matrix that multiplies the token's activation (2 forward, 4 backward), and
attention's products over the causal pairs.  What this model multiplies a token
by, a layer:

- the projections Wq (hidden x 8 heads x 128), Wk and Wv (hidden x 2 x 128
  each) and Wo (8 x 128 x hidden);
- the convolution over sequence and channels: a [128, 128] matrix a head and
  tap, 10 heads x 2 taps (the convolution a channel is elementwise and counts
  nothing, as the norms, the mean, the shifts and the merges);
- the router: the down-projection (hidden x 256), two matrices of 256 x 256
  and the scores' 256 x 17;
- of the routed experts `num_experts_per_tok` x held / router outputs in
  expectation: 1 x 8/17 of an expert a token — held, and not skipped; what the
  other chip computes is its work, and the choice that takes no expert costs
  nothing;

and once the tied head, hidden x the vocabulary slice: the embedding as a gather
counts nothing, the same matrix as the head's product counts as any head does.
No row move, no zero row that pads an expert's rows to a tile, no masked half
of a tile and no recomputation (`program.remat` recomputes a layer's forward
in the backward pass): work the algorithm does not require.
"""

from __future__ import annotations

from typing import Any, Dict


def _router_outputs(config: Dict[str, Any]) -> int:
    return (config.get("expert_parallel") or {}).get("router_outputs", config["num_experts"] + 1)


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert's three projections."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def attention_params(config: Dict[str, Any]) -> int:
    """The four projections and the convolution over sequence and channels."""
    hidden, dim = config["hidden_size"], config["head_dim"]
    heads, groups = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * hidden * heads * dim + 2 * hidden * groups * dim + (heads + groups) * config["cca_time1"] * dim * dim


def router_params(config: Dict[str, Any]) -> int:
    """The router's four matrices."""
    hidden, width = config["hidden_size"], config["router_hidden_size"]
    return hidden * width + 2 * width * width + width * _router_outputs(config)


def held_experts_per_token(config: Dict[str, Any]) -> float:
    """Routed experts held HERE that a token meets, in expectation."""
    return config["num_experts_per_tok"] * config["num_experts"] / _router_outputs(config)


def matmul_params(config: Dict[str, Any]) -> float:
    """Parameters that multiply one token's activation on this chip."""
    layer = attention_params(config) + router_params(config) + held_experts_per_token(config) * expert_params(config)
    return config["num_hidden_layers"] * layer + config["hidden_size"] * config["vocab_size"]


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward operations of causal attention for one token,
    averaged over a sequence of `seq_len`: QK^T and PV at head_dim over the
    (S + 1) / 2 keys a query sees, at the query heads."""
    visible = (seq_len + 1) / 2.0
    forward = 2 * config["num_attention_heads"] * 2 * config["head_dim"] * visible
    return config["num_hidden_layers"] * 3 * forward


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config, seq_len)


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds, a layer: attention's matrices, the
    convolution a channel (two taps and a bias), the other convolution's bias,
    the temperature a KV head; the router's matrices, its three biases, the
    carried state's weight and its norm; the held experts; two norm vectors
    and two merges of four; and once the embedding, which is the head, and the
    final norm."""
    hidden, dim, width = config["hidden_size"], config["head_dim"], config["router_hidden_size"]
    heads, groups = config["num_attention_heads"], config["num_key_value_heads"]
    chans = (heads + groups) * dim
    attention = attention_params(config) + (config["cca_time0"] + 1) * chans + chans + groups
    router = router_params(config) + 5 * width
    layer = attention + router + config["num_experts"] * expert_params(config) + 2 * hidden + 8 * hidden
    return config["num_hidden_layers"] * layer + hidden * config["vocab_size"] + hidden
