"""Seeded float32 weights of a mixture-of-experts decoder, made on the device.

The roles of ``bench/weights.py`` (norm scales, q/k/v/o projections, the
embedding, final norm and head, drawn the same way) with the dense FFN
replaced by a router and a stack of SwiGLU experts:

* ``router`` (d, E) ``N(0, 1/d)``;
* ``w_gate`` and ``w_up`` (E, d, f) ``N(0, 1/d)``, ``w_down`` (E, f, d)
  ``N(0, 1/f)``.

Each expert draws from a key of its own, so any slice of the experts is
made alone (``experts``) and equals that slice of the whole stack: the
program packs the experts a slice at a time and the plain reference
(``bench/reference_moe.py``) draws one expert at a time.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench import weights as W

__all__ = ["Dims", "layer", "experts", "seed_key", "embed", "head",
           "final_norm"]

seed_key, embed, head, final_norm = (W.seed_key, W.embed, W.head,
                                     W.final_norm)
_normal = W._normal


class Dims(NamedTuple):
    """Hashable widths of a mixture-of-experts decoder."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int            # one expert's width
    n_experts: int
    top_k: int
    vocab: int
    rope_theta: float
    norm_eps: float
    pattern: tuple       # layer kinds of one period, 'attn_local'/'attn_full'
    window: int          # sliding window of the 'attn_local' layers
    yarn: tuple | None   # (factor, original positions, beta_fast,
                         #  beta_slow, attention factor) on 'attn_full'


def _layer_key(key, i):
    return jax.random.fold_in(key, 1000 + i)


def layer(key, i, dims: Dims) -> dict:
    """Layer ``i``'s weights outside its experts (``i`` may be traced)."""
    d, hd = dims.d_model, dims.d_head
    k = jax.random.split(_layer_key(key, i), 7)
    return {
        "attn_norm": 1.0 + _normal(k[0], (d,), 0.1),
        "wq": _normal(k[1], (d, dims.n_heads * hd), d ** -0.5),
        "wk": _normal(k[2], (d, dims.n_kv_heads * hd), d ** -0.5),
        "wv": _normal(k[3], (d, dims.n_kv_heads * hd), d ** -0.5),
        "wo": _normal(k[4], (dims.n_heads * hd, d),
                      (dims.n_heads * hd) ** -0.5),
        "mlp_norm": 1.0 + _normal(k[5], (d,), 0.1),
        "router": _normal(k[6], (d, dims.n_experts), d ** -0.5),
    }


def experts(key, i, dims: Dims, first, count: int) -> dict:
    """Experts ``first .. first + count - 1`` of layer ``i`` (``i`` and
    ``first`` may be traced): ``w_gate``/``w_up`` (count, d, f),
    ``w_down`` (count, f, d)."""
    d, f = dims.d_model, dims.d_ff
    base = _layer_key(key, i)

    def one(e):
        k = jax.random.split(jax.random.fold_in(base, 10_000 + e), 3)
        return {"w_gate": _normal(k[0], (d, f), d ** -0.5),
                "w_up": _normal(k[1], (d, f), d ** -0.5),
                "w_down": _normal(k[2], (f, d), f ** -0.5)}

    return jax.vmap(one)(first + jnp.arange(count))
