"""Operations and least bytes of the work a cell does, from sizes and counts.

Counts are fixed by the work, not by how the program does it, so no later
implementation can read above 100% of a peak:

* a projection pass over M real tokens (no padding, no idle lanes) is
  ``2 * M * K * N`` operations per projection;
* its least bytes are each weight's elements at the packed preset's mean
  stored width (``avg_w_bits``, sign included) plus one byte per 64-element
  group exponent; activations are not counted, since a fused step may keep
  them on chip;
* the least time of a pass is the larger of operations over the int8 peak
  (DSBP's products are integers and may move to the int8 MXU) and bytes
  over HBM bandwidth.

Model FLOPs (``mfu``) add attention, ``4 * (p + 1) * heads * d_head`` per
layer for a token at position ``p`` (0-based), and the head, ``2 * d_model * vocab``
per token whose logits are used.
"""
from __future__ import annotations

__all__ = ["projection_elements", "least_pass_bytes", "gemm_least_seconds",
           "model_flops", "GROUP"]

GROUP = 64


def projection_elements(dims) -> int:
    """Weight elements of every DSBP projection of the stack."""
    d, hd, ff = dims.d_model, dims.d_head, dims.d_ff
    per_layer = (2 * d * dims.n_heads * hd + 2 * d * dims.n_kv_heads * hd
                 + 3 * d * ff)
    return dims.n_layers * per_layer


def least_pass_bytes(elements: int, avg_w_bits: float) -> float:
    """Least HBM bytes one pass over the packed stack reads."""
    return elements * avg_w_bits / 8 + elements / GROUP


def gemm_least_seconds(pass_tokens, dims, avg_w_bits: float, peaks) -> float:
    """Least time of the GEMM work of passes over ``pass_tokens`` real
    tokens each (one entry per pass through the whole stack)."""
    e = projection_elements(dims)
    t_bytes = least_pass_bytes(e, avg_w_bits) / peaks["hbm_bytes_per_s"]
    return sum(max(2.0 * m * e / peaks["int8_ops"], t_bytes)
               for m in pass_tokens if m > 0)


def model_flops(dims, segments, logit_tokens: int) -> float:
    """Model FLOPs of processing ``segments`` — ``(start, n)`` pairs, n
    tokens of one sequence at positions ``start .. start + n - 1`` — with
    ``logit_tokens`` of them needing the head."""
    tokens = attn_pos = 0
    for start, n in segments:
        tokens += n
        attn_pos += n * start + n * (n + 1) // 2
    attn = 4.0 * attn_pos * dims.n_heads * dims.d_head * dims.n_layers
    return (2.0 * projection_elements(dims) * tokens + attn
            + 2.0 * dims.d_model * dims.vocab * logit_tokens)
