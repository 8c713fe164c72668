"""Bytes and operations the program's `ssm_mix` kernels need, from shapes.

`tpuft_ssmmix_fwd` / `tpuft_ssmmix_bwd` and `tpuft_ssmmix_out_fwd` /
`tpuft_ssmmix_out_bwd` (ops/ssm_mix.py) are what a Mamba-2 block puts around
its scan, a pass a direction over [positions, columns] arrays.  What is counted
is the REQUIRED traffic through HBM of each half — each operand read once and
each result written once, whatever the implementation happens to move — in
units of one array of [positions, heads * head width] in the compute type
(bf16: two bytes an element; B and C are `n_groups * ssm_state_size` columns
each, a quarter of a unit at the published widths):

- before the scan, forward: read u = [x | B | C] (1.5), write x, dt * x (2) and
  B, C (0.5): 4;  backward: read u (1.5) and the cotangents of x, dt * x (2)
  and B, C (0.5), write u's gradient (1.5): 5.5;
- after the scan, forward: read y, x and the gate's projection (3), write the
  output (1): 4;  backward: read those and the output's cotangent (4), write
  three gradients (3): 7;
- the step's projection and the log decay a head, [positions, heads]: dt read
  in bf16 and the decay written in float32 forward (6 bytes a head), dt and
  the decay's cotangent read and dt's gradient written backward (8).

A block is a checkpoint whose mixer keeps the scan's output alone: both
forward halves run TWICE a step, and both runs are counted, because the time
they are set against (every `tpuft_ssmmix_` instruction of the gradient
program) holds both — 2 x (4 + 4) + 5.5 + 7 = 28.5 units a block, 3.83 GB at
16,384 positions x 4,096 columns, 4.67 ms at a v5e's 819 GB/s.  (PR 49's count
of `kda_mix` leaves the second forward out; its share reads lower for it.)  Not
counted: the sixteen rows of the tile before that each grid step fetches for
the convolution, the small leaves (taps, biases, D, the norm's weight) and
their partial sums, dt's padding to whole lane tiles of heads.

Operations are the elementwise arithmetic a position and column, forward (12 a
convolved channel: 7 the taps, 1 the bias, 4 SiLU; 1 for dt * x; 12 the skip,
the gate and the group norm) and twice that backward.  They run on the vector
unit, whose peak `peaks.json` does not hold; even against the matrix unit's
they are a hundredth of the traffic's time, so the part is bound by HBM by
these counts.
"""

from __future__ import annotations

from typing import Any, Dict

# columns read and written, as (multiples of the heads' joined width, multiples of B's width)
UNITS = {"before_forward": 4.0, "before_backward": 5.5, "after_forward": 4.0, "after_backward": 7.0}
_COLUMNS = {"before_forward": (3, 4), "before_backward": (4, 6), "after_forward": (4, 0), "after_backward": (7, 0)}
_RUNS = {"before_forward": 2, "before_backward": 1, "after_forward": 2, "after_backward": 1}
_HEAD_BYTES = {"before_forward": 6, "before_backward": 8}


def blocks_within_depth(config: Dict[str, Any]) -> int:
    """Mamba-2 blocks among the first `num_hidden_layers`."""
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]].count("M")


def bytes_per_position(config: Dict[str, Any]) -> Dict[str, float]:
    """{half and direction: bytes a position of ONE run of it}."""
    heads = config["mamba_num_heads"]
    inner, state = heads * config["mamba_head_dim"], config["n_groups"] * config["ssm_state_size"]
    return {name: 2.0 * (wide * inner + narrow * state) + heads * _HEAD_BYTES.get(name, 0)
            for name, (wide, narrow) in _COLUMNS.items()}


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of the four kernels over one step of one group."""
    heads = config["mamba_num_heads"]
    inner, state = heads * config["mamba_head_dim"], config["n_groups"] * config["ssm_state_size"]
    positions = traffic["seq_len"] * traffic["sequences_per_step"] * blocks_within_depth(config)
    a_position = bytes_per_position(config)
    forward_ops = 12 * (inner + 2 * state) + inner + 12 * inner
    return {"flops": 4.0 * forward_ops * positions,                # two forward runs, the backward at twice a forward
            "bytes": float(positions * sum(_RUNS[name] * a_position[name] for name in _RUNS))}
