"""Seeded float32 weights of a llama-style decoder, made on the device.

The benchmark owns its weights: the program under test is handed them to
pack (``bench/program.py``) and the plain reference (``bench/reference.py``)
draws the very same arrays again from the same key, layer by layer, so it
takes nothing the program made.

Scales follow the program's own initialisation (projections
``N(0, 1/d_in)``, embedding and head ``N(0, 1/d_model)``); norm scales are
``1 + 0.1 N(0, 1)`` rather than ones, so a norm whose scale is dropped
shows in the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from typing import NamedTuple

__all__ = ["Dims", "seed_key", "layer", "embed", "head", "final_norm"]


class Dims(NamedTuple):
    """Hashable model widths (a static argument of the jitted weight programs)."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (64-bit seeds keep their high
    bits: ``jax.random.PRNGKey`` drops them without x64)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)


def layer(key, i, dims: Dims) -> dict:
    """Layer ``i``'s weights (``i`` may be traced), by role."""
    d, hd, ff = dims.d_model, dims.d_head, dims.d_ff
    k = jax.random.split(jax.random.fold_in(key, 1000 + i), 9)
    return {
        "attn_norm": 1.0 + _normal(k[0], (d,), 0.1),
        "wq": _normal(k[1], (d, dims.n_heads * hd), d ** -0.5),
        "wk": _normal(k[2], (d, dims.n_kv_heads * hd), d ** -0.5),
        "wv": _normal(k[3], (d, dims.n_kv_heads * hd), d ** -0.5),
        "wo": _normal(k[4], (dims.n_heads * hd, d), (dims.n_heads * hd) ** -0.5),
        "mlp_norm": 1.0 + _normal(k[5], (d,), 0.1),
        "w_gate": _normal(k[6], (d, ff), d ** -0.5),
        "w_up": _normal(k[7], (d, ff), d ** -0.5),
        "w_down": _normal(k[8], (ff, d), ff ** -0.5),
    }


def embed(key, dims: Dims) -> jax.Array:
    """(vocab, d_model) token embedding."""
    return _normal(jax.random.fold_in(key, 0), (dims.vocab, dims.d_model),
                   dims.d_model ** -0.5)


def head(key, dims: Dims) -> jax.Array:
    """(d_model, vocab) output head (untied)."""
    return _normal(jax.random.fold_in(key, 1), (dims.d_model, dims.vocab),
                   dims.d_model ** -0.5)


def final_norm(key, dims: Dims) -> jax.Array:
    return 1.0 + _normal(jax.random.fold_in(key, 2), (dims.d_model,), 0.1)
