"""Bytes and operations the program's `kda_mix` kernels need, from shapes.

`tpuft_kdamix_fwd` / `tpuft_kdamix_bwd` and `tpuft_kdamix_out_fwd` /
`tpuft_kdamix_out_bwd` (ops/kda_mix.py) are what Kimi Delta Attention puts
around its scan, a pass a direction over [positions, heads * width] arrays.
What is counted is the REQUIRED traffic through HBM of each half, each operand
read once and each result written once, in units of one such array in the
compute type (bf16: two bytes an element; g and its cotangent are float32,
two units each):

- before the scan, forward: read q~, k~, v~, a (4), write q, k, v (3) and g
  (2): 9;  backward: read the four inputs (4) and the cotangents of q, k, v
  (3) and g (2), write four gradients (4): 13;
- after the scan, forward: read o and the gate's projection (2), write the
  output (1): 3;  backward: read o, the gate's projection and the output's
  cotangent (3), write two gradients (2): 5.

30 units a layer: 4.03 GB at 16,384 positions x 4,096 columns, 4.92 ms at a
v5e's 819 GB/s.  Not counted, so that they read as a lower share and not as
work: the forward pass a rematerialised layer runs again, the sixteen rows
of the tile before that each grid step fetches for the convolution, the small
leaves (taps, biases, the rate, the norm's weight) and their partial sums,
and `beta`'s sigmoid and the decay's mean, which stay in XLA.

Operations are the elementwise arithmetic a position and column, forward (7 a
convolution, 4 a SiLU, 3 a norm and q's scale, 6 the decay, 9 the gated norm:
55) and twice that backward.  They run on the vector unit, whose peak
`peaks.json` does not hold; even against the matrix unit's they are a
hundredth of the traffic's time, so the part is bound by HBM by these counts.
"""

from __future__ import annotations

from typing import Any, Dict

UNITS = {"before_forward": 9, "before_backward": 13, "after_forward": 3, "after_backward": 5}
FORWARD_OPS = 3 * 7 + 3 * 4 + (2 * 3 + 1) + 6 + 9


def layers_within_depth(config: Dict[str, Any]) -> int:
    """KDA layers among the first `num_hidden_layers`."""
    return sum(1 for i in config["linear_attn_config"]["kda_layers"] if i <= config["num_hidden_layers"])


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of the four kernels over one step of one group."""
    linear = config["linear_attn_config"]
    elements = (traffic["seq_len"] * traffic["sequences_per_step"] * linear["num_heads"] * linear["head_dim"]
                * layers_within_depth(config))
    return {"flops": 3.0 * FORWARD_OPS * elements, "bytes": 2.0 * sum(UNITS.values()) * elements}
