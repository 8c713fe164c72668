"""Operations and bytes the gated delta rule with a decay a HEAD needs (Gated
DeltaNet's scan: 32 value heads of 128 over 16 key heads of 128), from shapes
and the chunk size this file states — whatever kernels run it.

What is counted is the REQUIRED work of the chunked recurrence at ``CHUNK`` =
64 positions, a VALUE head and chunk, with K the key's width and V the
value's.  With a scalar decay `exp(G_r - G_i)` is one [C, C] matrix a head, so
R = (K K^T) o decay and Rq = (Q K^T) o decay are ONE triangular product each:

- forward: R (strictly lower) and Rq (lower), C^2 K each (half of a full C x C
  x K product); the triangular solve, 2/3 C^3; W = T Kg and U = T V with T lower
  triangular, C^2 K and C^2 V; the three products with the state (W S, Qg S,
  Kend^T D), 2 C K V each; Rq D, C^2 V; the state's decay, K V;
- backward: twice the forward's (each product has two transposed gradients).

That is `flops/tpuft_kda.py`'s count, and on purpose: the channel decay's
REQUIRED work has one triangular product for R and one for Rq as well, and what
its kernels pay beyond it (log2(C) masked products over the whole square) was
never counted there either.  So a program that runs this scan on the channel
form's kernels under a broadcast decay (PR 68), one that runs a scalar-decay
chunk function and one that reads the key heads in place are all read against
the same operations; what moves between them is the time, and the bytes below.

Not counted, so that they read as a lower share and not as work: the forward
pass that makes the chunks' states again inside the backward, the
recomputation inside the backward kernel, the broadcast of the decay over the
key's channels and the repeat of a key head for its two value heads, the
bfloat16 passes of float32 sums, a larger chunk's extra arithmetic.

Bytes are the least the scan must move through HBM: q and k read once a KEY
head (each serves two value heads), v, o and their cotangents a value head in
bf16, dq and dk written once a key head, g, beta and their gradients ONE
float32 a value head and position: 2,328 bytes a value head and position where
the channel form moves 4,364 (its g alone is 512 of them, and as much of dg).
By these counts the scan stays bound by HBM, if less so: 3.66 GB (4.5 ms at a
v5e's HBM peak) against 0.67 TFLOP (3.4 ms at its bf16 peak) for three layers
of 32 value heads x 16,384 positions.
"""

from __future__ import annotations

from typing import Any, Dict

CHUNK = 64


def layers_within_depth(config: Dict[str, Any]) -> int:
    """Gated DeltaNet layers among the first `num_hidden_layers`."""
    every = config["full_attention_interval"]
    return sum(1 for i in range(config["num_hidden_layers"]) if (i + 1) % every)


def forward_flops_per_position(k: int, v: int, chunk: int = CHUNK) -> float:
    """One value head, one position, forward."""
    a_chunk = (2 * chunk * chunk * k + 2.0 / 3.0 * chunk ** 3 + chunk * chunk * k + chunk * chunk * v
               + 6 * chunk * k * v + chunk * chunk * v + k * v)
    return a_chunk / chunk


def per_step(config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, float]:
    """{"flops", "bytes"} of the scan, forward and backward, over one step of one group."""
    heads, key_heads = config["linear_num_value_heads"], config["linear_num_key_heads"]
    k, v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    positions = traffic["seq_len"] * traffic["sequences_per_step"] * layers_within_depth(config)
    flops = 3.0 * forward_flops_per_position(k, v) * positions * heads
    key_row, value_row = k * 2, v * 2
    forward = key_heads * 2 * key_row + heads * (2 * value_row + 2 * 4)          # read q k | v g beta, write o
    backward = (key_heads * 4 * key_row                                          # read q k, write dq dk
                + heads * (4 * value_row + 4 * 4))                               # read v o-cotangent, write dv | g beta dg dbeta
    return {"flops": flops, "bytes": float(positions * (forward + backward))}
