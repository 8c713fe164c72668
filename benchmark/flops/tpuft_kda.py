"""Operations and bytes the program's delta-rule kernels need, from shapes and
the chunk size this file states.

`tpuft_kda_fwd` and `tpuft_kda_bwd` (ops/delta_attention.py) run the gated
delta rule with a decay a channel over [batch * heads, seq, 128] tensors, chunk
by chunk.  What is counted is the REQUIRED work of the chunked recurrence at
``CHUNK`` = 64 positions (the program's `ops.delta_attention.CHUNK`; a test
pins the two equal), a head and chunk, with K = V = the head's width:

- forward: the two decayed pair matrices R (strictly lower) and Rq (lower),
  C^2 K each (half of a full C x C x K product); the triangular solve, 2/3 C^3;
  W = T Kg and U = T V with T lower triangular, C^2 K and C^2 V; the three
  products with the state (W S, Qg S, Kend^T D), 2 C K V each; Rq D, C^2 V;
  the state's decay, K V;
- backward: twice the forward's (each product has two transposed
  gradients).

Not counted, so that they read as a lower share and not as work: the
recomputation of the forward inside the backward kernel and the forward pass
that makes the chunks' states again; the program's way to bounded exponents
(log2(C) masked products over the whole C x C square where one triangular
product is required); the three bfloat16 passes of its float32 sums and of
its solve by squarings; a larger chunk's extra arithmetic.

Bytes are the least the kernels must move through HBM: each operand read once
and each result written once — q, k, v, o and their cotangents in bf16, g and
its gradient in f32, beta and its gradient in f32.  The chunks' states that
the backward's forward pass writes and its kernel reads again are the
program's trade of memory for recomputation and are not counted.  By these
counts the scan is bound by HBM, not by the MXU: 4,364 bytes against 0.43
MFLOP a head and position, 2.79 ms against 1.14 ms a layer of 32 heads x
16,384 positions at a v5e's peaks.
"""

from __future__ import annotations

from typing import Any, Dict

CHUNK = 64


def layers_within_depth(config: Dict[str, Any]) -> int:
    """KDA layers among the first `num_hidden_layers`."""
    return sum(1 for i in config["linear_attn_config"]["kda_layers"] if i <= config["num_hidden_layers"])


def forward_flops_per_position(width: int, chunk: int = CHUNK) -> float:
    """One head, one position, forward."""
    k = v = width
    a_chunk = (2 * chunk * chunk * k + 2.0 / 3.0 * chunk ** 3 + chunk * chunk * k + chunk * chunk * v
               + 6 * chunk * k * v + chunk * chunk * v + k * v)
    return a_chunk / chunk


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of both kernels over one step of one group."""
    linear = config["linear_attn_config"]
    heads, width = linear["num_heads"], linear["head_dim"]
    positions = traffic["seq_len"] * traffic["sequences_per_step"] * heads * layers_within_depth(config)
    flops = 3.0 * forward_flops_per_position(width) * positions
    row16, row32 = width * 2, width * 4
    forward = 3 * row16 + row32 + 4 + row16                      # read q k v g beta, write o
    backward = 3 * row16 + row32 + 4 + row16 + 3 * row16 + row32 + 4  # read q k v g beta do, write dq dk dv dg dbeta
    return {"flops": flops, "bytes": float(positions * (forward + backward))}
