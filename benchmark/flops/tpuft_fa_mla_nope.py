"""Operations and bytes the program's flash-attention kernels need for
UNROTATED latent attention (MLA with `mla_use_nope`), counted over the latent
layers that lie within the depth, from shapes.

As `flops/tpuft_fa_mla.py` — `tpuft_fa_fwd` and the backward kernel run once a
LATENT layer on [batch * heads, seq, width] bf16 tensors, query and key 192 wide
(128 + the 64 shared columns, content here and not rotary), value, output and
their cotangents 128; six products over the causal pairs, the scores'
recomputation in the backward (the flash trade) and the zero columns that pad
192 to 256 not counted — with ONE difference: the layers are those of
`linear_attn_config.full_attn_layers` among the first `num_hidden_layers`
(one of five in the benchmark's cut), where that file multiplies by
`num_hidden_layers` and would read five times the work here, an impossible
share.  The other layers' mixer is the delta rule (`flops/tpuft_kda.py`).

Bytes are the least the kernels must move through HBM: each operand read once
and each result written once, in bf16, the row statistics in f32; the shared
64 columns of the key once a head, as each head's kernel instance reads them.
"""

from __future__ import annotations

from typing import Any, Dict


def layers_within_depth(config: Dict[str, Any]) -> int:
    """Latent-attention layers among the first `num_hidden_layers`."""
    return sum(1 for i in config["linear_attn_config"]["full_attn_layers"] if i <= config["num_hidden_layers"])


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of the forward and backward kernels over one step of one group."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    seq, batch, layers = traffic["seq_len"], traffic["sequences_per_step"], layers_within_depth(config)
    bh = batch * config["num_attention_heads"]
    pairs = seq * (seq + 1) / 2.0  # causal: visible pairs only
    flops = layers * bh * 2.0 * pairs * (3 * qk + 3 * v)  # QK^T, dQ, dK at qk; PV, dV, dP at v
    wide, narrow, stats = seq * qk * 2, seq * v * 2, seq * 4
    forward = 2 * wide + 2 * narrow + stats  # read Q K V, write O, lse
    backward = 4 * wide + 4 * narrow + 2 * stats  # read Q K V O dO, write dQ dK dV; lse, delta
    return {"flops": flops, "bytes": float(layers * bh * (forward + backward))}
