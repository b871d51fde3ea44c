"""Operations and least bytes of a mixture-of-experts cell's work, from
widths and counts.

Counts are fixed by the work, not by how the program does it:

* the routed expert work of a token is ``top_k`` (token, expert) pairs
  per layer, each ``6 * d * f`` operations (gate, up and down: three
  ``d x f`` products of two operations per element);
* the least bytes of a pass's expert GEMMs are the experts it touches,
  each ``3 * d * f`` weight elements at the packed experts' mean stored
  width (sign included) plus one byte per 64-element group exponent;
  activations are not counted;
* the least time of a pass is the larger of operations over the int8
  peak and bytes over HBM bandwidth, as in ``bench/work.py``.

Model FLOPs count the active parameters of a token (attention
projections, router, ``top_k`` experts; ``2`` operations per weight
element), attention over ``p + 1`` positions on full layers and
``min(p + 1, window)`` on sliding ones (``4 * heads * d_head`` each), and
the head for each token whose logits are used.
"""
from __future__ import annotations

__all__ = ["expert_pair_ops", "expert_bytes", "expert_least_seconds",
           "active_elements", "model_flops", "GROUP"]

GROUP = 64


def expert_pair_ops(dims) -> float:
    """Operations of one (token, expert) pair in one layer."""
    return 6.0 * dims.d_model * dims.d_ff


def expert_bytes(touched: int, dims, avg_w_bits: float) -> float:
    """Least HBM bytes of ``touched`` (layer, expert) weight sets."""
    e = 3 * dims.d_model * dims.d_ff
    return touched * (e * avg_w_bits / 8 + e / GROUP)


def expert_least_seconds(passes, dims, avg_w_bits: float, peaks) -> float:
    """Least time of the expert GEMMs of ``passes``: ``(tokens, touched)``
    per pass, its real tokens and the (layer, expert) pairs it touched.
    No pass touches more experts than its tokens route to."""
    total = 0.0
    for tokens, touched in passes:
        if tokens <= 0:
            continue
        touched = min(touched, dims.n_layers
                      * min(dims.n_experts, tokens * dims.top_k))
        ops = tokens * dims.top_k * dims.n_layers * expert_pair_ops(dims)
        total += max(ops / peaks["int8_ops"],
                     expert_bytes(touched, dims, avg_w_bits)
                     / peaks["hbm_bytes_per_s"])
    return total


def active_elements(dims) -> int:
    """Weight elements one token multiplies in one layer: q/k/v/o, the
    router and its ``top_k`` experts."""
    d, hd = dims.d_model, dims.d_head
    attn = 2 * d * dims.n_heads * hd + 2 * d * dims.n_kv_heads * hd
    return attn + d * dims.n_experts + dims.top_k * 3 * d * dims.d_ff


def model_flops(dims, segments, logit_tokens: int) -> float:
    """Model FLOPs of processing ``segments`` — ``(start, n)`` pairs, n
    tokens of one sequence at positions ``start .. start + n - 1`` — with
    ``logit_tokens`` of them needing the head."""
    kinds = [dims.pattern[i % len(dims.pattern)] for i in range(dims.n_layers)]
    n_full = sum(k == "attn_full" for k in kinds)
    n_local = len(kinds) - n_full
    w = dims.window
    tokens = full_pos = local_pos = 0
    for start, n in segments:
        tokens += n
        full_pos += n * start + n * (n + 1) // 2
        # sum over p in [start, start + n) of min(p + 1, w)
        lo, hi = start + 1, start + n
        below = max(0, min(hi, w) - lo + 1)
        if below:
            a = lo
            local_pos += (a + a + below - 1) * below // 2
        local_pos += max(0, hi - max(lo, w + 1) + 1) * w
    attn = 4.0 * dims.n_heads * dims.d_head * (n_full * full_pos
                                               + n_local * local_pos)
    return (2.0 * active_elements(dims) * dims.n_layers * tokens + attn
            + 2.0 * dims.d_model * dims.vocab * logit_tokens)
