"""Bytes and operations the program's `gdn_mix` kernels need, from shapes.

`tpuft_kdamix_fwd` / `tpuft_kdamix_bwd` and `tpuft_kdamix_out_fwd` /
`tpuft_kdamix_out_bwd` (ops/kda_mix.py) under Gated DeltaNet's shapes are what
`models/gdn.py` puts around its scan: a pass a direction over q~, k~ (the KEY
heads' columns, `linear_num_key_heads * linear_key_head_dim` each) and v~ (the
VALUE heads', `linear_num_value_heads * linear_value_head_dim`), and after the
scan over o, the gate's projection z and the output (the value heads').  What
is counted is the REQUIRED traffic through HBM of each half, each operand read
once and each result written once in the compute type (bf16: two bytes an
element), a position and layer, with K = 2 x the key heads' columns + the
value heads' and V = the value heads' columns:

- before the scan, forward: read q~, k~, v~ and write q, k, v: 2 K;
  backward: read the three inputs and the three cotangents, write three
  gradients: 3 K;
- after the scan, forward: read o and z, write the output: 3 V;  backward:
  read o, z and the output's cotangent, write two gradients: 5 V.

At the published widths (16 key heads and 32 value heads of 128: K = 8,192, V
= 4,096) that is 2 x (16,384 + 24,576 + 12,288 + 20,480) = 147,456 bytes a
position and layer — 18 arrays of [16,384, 4,096] bf16 a layer where Kimi Delta
Attention's halves move 30 (`flops/tpuft_kdamix.py`: no decay a channel to
read or write here) — 7.25 GB over three layers of 16,384 positions, 8.85 ms at
a v5e's 819 GB/s.  Not counted, so that they read as a lower share and not as
work: the forward pass a rematerialised layer runs again, the sixteen rows of
the tile before that each grid step fetches for the convolution, the small
leaves (taps, the norm's weight) and their partial sums, and the decay a value
head with `beta` ([positions, 32]: two small XLA fusions, not these kernels').

Operations are the elementwise arithmetic a position and column, forward (7 a
convolution and 4 a SiLU on K columns, 3 a norm on the key heads' and q's
scale, 11 the head norm under SiLU(z) on V) and twice that backward.  They run
on the vector unit, whose peak `peaks.json` does not hold; even against the
matrix unit's they are a hundredth of the traffic's time, so the part is bound
by HBM by these counts.
"""

from __future__ import annotations

from typing import Any, Dict

# arrays read and written, as (multiples of K, multiples of V)
UNITS = {"before_forward": (2, 0), "before_backward": (3, 0), "after_forward": (0, 3), "after_backward": (0, 5)}


def layers_within_depth(config: Dict[str, Any]) -> int:
    """Gated DeltaNet layers among the first `num_hidden_layers`."""
    every = config["full_attention_interval"]
    return sum(1 for i in range(config["num_hidden_layers"]) if (i + 1) % every)


def columns(config: Dict[str, Any]) -> Dict[str, int]:
    """{"key": one of q~'s or k~'s columns, "value": v~'s, o's, z's}."""
    return {"key": config["linear_num_key_heads"] * config["linear_key_head_dim"],
            "value": config["linear_num_value_heads"] * config["linear_value_head_dim"]}


def bytes_per_position(config: Dict[str, Any]) -> Dict[str, int]:
    """{half and direction: bytes a position and layer}."""
    wide = columns(config)
    k, v = 2 * wide["key"] + wide["value"], wide["value"]
    return {name: 2 * (of_k * k + of_v * v) for name, (of_k, of_v) in UNITS.items()}


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of the four kernels over one step of one group."""
    wide = columns(config)
    positions = traffic["seq_len"] * traffic["sequences_per_step"] * layers_within_depth(config)
    forward_ops = 11 * (2 * wide["key"] + wide["value"]) + 7 * wide["key"] + 11 * wide["value"]
    return {"flops": 3.0 * forward_ops * positions, "bytes": float(positions * sum(bytes_per_position(config).values()))}
