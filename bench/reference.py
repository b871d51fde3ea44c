"""Plain float32 reference of a llama-style decoder (Llama / Yi / DeepSeek-Coder).

Straight ``jax.numpy`` at matmul precision "highest": token embedding,
then per layer RMSNorm -> q/k/v projections -> rotary embedding (halves
rotated, as HF's ``rotate_half``) -> causal grouped-query attention (query
head h reads key/value head ``h // (heads / kv_heads)``) -> output
projection -> residual; RMSNorm -> SwiGLU (``silu(x W_gate) * (x W_up)``)
-> down projection -> residual; final RMSNorm and an untied head.  No
cache, no kernels, no batching tricks: each row is a full causal pass.

It imports nothing of the program and takes no weights from it: every
layer's weights are drawn again from the seed (``bench/weights.py``)
inside the loop, so one layer's weights live on the device at a time.
Departure from the published models: none of them is run with rotary
scaling here (DeepSeek-Coder's linear factor 4 is listed under the
configuration's ``reduced``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import weights as W

__all__ = ["forward_stats", "logits"]

HI = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x (B, S, H, D), positions 0..S-1."""
    d = x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(w, x, dims: W.Dims):
    b, s, d = x.shape
    h, kvh, hd = dims.n_heads, dims.n_kv_heads, dims.d_head
    y = _rms(x, w["attn_norm"], dims.norm_eps)
    q = jnp.einsum("bsd,dn->bsn", y, w["wq"], precision=HI).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,dn->bsn", y, w["wk"], precision=HI).reshape(b, s, kvh, hd)
    v = jnp.einsum("bsd,dn->bsn", y, w["wv"], precision=HI).reshape(b, s, kvh, hd)
    q, k = _rope(q, dims.rope_theta), _rope(k, dims.rope_theta)
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI).reshape(b, s, h * hd)
    x = x + jnp.einsum("bsn,nd->bsd", o, w["wo"], precision=HI)
    y = _rms(x, w["mlp_norm"], dims.norm_eps)
    g = jnp.einsum("bsd,df->bsf", y, w["w_gate"], precision=HI)
    u = jnp.einsum("bsd,df->bsf", y, w["w_up"], precision=HI)
    return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, w["w_down"],
                          precision=HI)


def _trunk(key, tokens, dims: W.Dims):
    x = jnp.take(W.embed(key, dims), tokens, axis=0)
    x = jax.lax.fori_loop(
        0, dims.n_layers, lambda i, x: _layer(W.layer(key, i, dims), x, dims),
        x)
    return _rms(x, W.final_norm(key, dims), dims.norm_eps)


@partial(jax.jit, static_argnames=("dims",))
def logits(key, tokens, dims: W.Dims):
    """(B, S, vocab) float32 logits of ``tokens`` (B, S)."""
    x = _trunk(key, tokens, dims)
    return jnp.einsum("bsd,dv->bsv", x, W.head(key, dims), precision=HI)


@partial(jax.jit, static_argnames=("dims",))
def forward_stats(key, tokens, targets, dims: W.Dims):
    """Per position of ``tokens`` (B, S): the best logit, the logit of
    ``targets`` (B, S) and its log-probability, all under the reference."""
    lg = logits(key, tokens, dims)
    best = jnp.max(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    lse = jax.nn.logsumexp(lg, axis=-1)
    return {"best": best, "target": tgt, "target_logp": tgt - lse}
