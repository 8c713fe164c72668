"""Operations and bytes the program's state-space kernels need, from shapes and
the chunk size the configuration states.

`tpuft_ssd_fwd` and `tpuft_ssd_bwd` (ops/ssd.py) run Mamba-2's recurrence — a
scalar decay a head over a state [N, P] — chunk by chunk over [batch, seq,
heads * P] tensors, a group's heads a grid step.  What is counted is the
REQUIRED work of the chunked recurrence at `chunk_size` positions, a head and
chunk, with H / G heads sharing a group's B and C:

- forward: `C B^T` over the visible pairs, C^2 N operations a GROUP (half of a
  full C x C x N product), so C^2 N G / H a head; `((C B^T) * L) XDT`, lower
  triangular, C^2 P; the two products with the state (`C S` and `B^T (w *
  XDT)`), 2 C N P each; the state's decay, N P;
- backward: twice the forward's (each product has two transposed gradients).

Not counted, so that they read as a lower share and not as work: the forward
pass that makes the chunks' states again; the recomputation inside the backward
kernel; the upper triangle of the two in-chunk products, which the kernels
compute and mask; the other head's half of a 128-lane block, which a product
over one head of 64 columns carries along.

Bytes are the least the kernels must move through HBM: each operand read once
and each result written once — dt * x, y and their cotangents in bf16; B, C and
their gradients in bf16, once a GROUP; the running sum of the log decay and its
gradient in f32 (the kernels read it twice, down the rows and across the lanes:
counted once).  The chunks' states that the backward's forward pass writes and
its kernel reads again are the program's trade of memory for recomputation and
are not counted.  By these counts the scan is bound by HBM: 844 bytes against
0.13 MFLOP a head and position, 1.08 ms against 0.69 ms a block of 64 heads x
16,384 positions at a v5e's peaks.
"""

from __future__ import annotations

from typing import Any, Dict


def blocks_within_depth(config: Dict[str, Any]) -> int:
    """Mamba-2 blocks among the first `num_hidden_layers`."""
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]].count("M")


def forward_flops_per_position(config: Dict[str, Any]) -> float:
    """One head, one position, forward."""
    chunk, p, n = config["chunk_size"], config["mamba_head_dim"], config["ssm_state_size"]
    per_group = config["mamba_num_heads"] // config["n_groups"]
    a_chunk = chunk * chunk * n / per_group + chunk * chunk * p + 4 * chunk * n * p + n * p
    return a_chunk / chunk


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    heads, p, n = config["mamba_num_heads"], config["mamba_head_dim"], config["ssm_state_size"]
    per_group = heads // config["n_groups"]
    positions = traffic["seq_len"] * traffic["sequences_per_step"] * heads * blocks_within_depth(config)
    flops = 3.0 * forward_flops_per_position(config) * positions
    row, shared = p * 2, 2 * n * 2 / per_group                   # dt * x or y; B and C, a head's share
    forward = 2 * row + shared + 4                               # read xdt B C c, write y
    backward = 3 * row + 2 * shared + 2 * 4                      # read xdt B C c dy, write dxdt dB dC dc
    return {"flops": flops, "bytes": float(positions * (forward + backward))}
