"""Plain float32 reference of a mixture-of-experts decoder (Mellum2).

Straight ``jax.numpy`` at matmul precision "highest": token embedding,
then per layer RMSNorm -> q/k/v projections -> rotary embedding (halves
rotated, as HF's ``rotate_half``; YaRN on full-attention layers) ->
causal grouped-query attention, on sliding layers only over keys with
``q - k < window`` -> output projection -> residual; RMSNorm -> router
softmax over every expert -> the top-k probabilities renormalized to sum
to one (zero for every other expert) -> every expert's SwiGLU
(``silu(x W_gate) * (x W_up)``, then ``W_down``) evaluated on every token
and summed under those weights -> residual; final RMSNorm and an untied
head.  No cache, no kernels, no routing tricks: each row is a full causal
pass and every expert runs densely.

YaRN follows HF's ``_compute_yarn_parameters``: correction dimensions
``d ln(orig / (beta 2 pi)) / (2 ln theta)`` for ``beta_fast`` (floored) and
``beta_slow`` (ceiled), a linear ramp between them blending the
interpolated (``/ factor``) and the original inverse frequencies, and cos
and sin scaled by ``attention_factor``; it is evaluated here in float64
with numpy.

It imports nothing of the program and takes no weights from it: every
layer's weights are drawn again from the seed (``bench/weights_moe.py``),
the experts one at a time, so one layer's attention weights and one
expert live on the device at a time.  Departures from the published
model: none of its layer equations; the multi-token-prediction head is
not part of the serving path and is left out.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights_moe as WM

__all__ = ["inv_freq", "forward_stats", "logits"]

HI = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def inv_freq(d_head: int, theta: float, yarn=None) -> np.ndarray:
    """(d_head/2,) float64 rotary inverse frequencies, with the YaRN blend
    when ``yarn`` = (factor, original positions, beta_fast, beta_slow,
    attention factor) is given."""
    half = d_head // 2
    base = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2 / d_head)
    if yarn is None:
        return base
    factor, orig, beta_fast, beta_slow, _ = yarn

    def dim(beta):
        return d_head * math.log(orig / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), d_head - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def _rope(x, theta, yarn):
    """x (B, S, H, D), positions 0..S-1."""
    d = x.shape[-1]
    half = d // 2
    inv = jnp.asarray(inv_freq(d, theta, yarn), jnp.float32)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    mag = 1.0 if yarn is None else yarn[4]
    cos = (jnp.cos(ang) * mag)[None, :, None]
    sin = (jnp.sin(ang) * mag)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(w, x, dims: WM.Dims, kind: str):
    b, s, _ = x.shape
    h, kvh, hd = dims.n_heads, dims.n_kv_heads, dims.d_head
    yarn = dims.yarn if kind == "attn_full" else None
    y = _rms(x, w["attn_norm"], dims.norm_eps)
    q = jnp.einsum("bsd,dn->bsn", y, w["wq"], precision=HI).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,dn->bsn", y, w["wk"], precision=HI).reshape(b, s, kvh, hd)
    v = jnp.einsum("bsd,dn->bsn", y, w["wv"], precision=HI).reshape(b, s, kvh, hd)
    q, k = _rope(q, dims.rope_theta, yarn), _rope(k, dims.rope_theta, yarn)
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    qpos, kpos = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = kpos <= qpos
    if kind == "attn_local":
        mask &= qpos - kpos < dims.window
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI).reshape(b, s, h * hd)
    return x + jnp.einsum("bsn,nd->bsd", o, w["wo"], precision=HI)


def _experts(key, i, w, x, dims: WM.Dims):
    y = _rms(x, w["mlp_norm"], dims.norm_eps)
    probs = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", y, w["router"], precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, dims.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, dims.n_experts) * top[..., None],
                     axis=-2)  # (B, S, E): renormalized gates, 0 elsewhere

    def one(e, acc):
        we = jax.tree.map(lambda a: a[0], WM.experts(key, i, dims, e, 1))
        g = jnp.einsum("bsd,df->bsf", y, we["w_gate"], precision=HI)
        u = jnp.einsum("bsd,df->bsf", y, we["w_up"], precision=HI)
        out = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, we["w_down"],
                         precision=HI)
        gate = jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=-1)
        return acc + gate * out

    return x + jax.lax.fori_loop(0, dims.n_experts, one, jnp.zeros_like(x))


def _trunk(key, tokens, dims: WM.Dims):
    x = jnp.take(WM.embed(key, dims), tokens, axis=0)
    period = len(dims.pattern)

    def unit(u, x):
        for li, kind in enumerate(dims.pattern):
            i = u * period + li
            w = WM.layer(key, i, dims)
            x = _experts(key, i, w, _attention(w, x, dims, kind), dims)
        return x

    x = jax.lax.fori_loop(0, dims.n_layers // period, unit, x)
    return _rms(x, WM.final_norm(key, dims), dims.norm_eps)


@partial(jax.jit, static_argnames=("dims",))
def logits(key, tokens, dims: WM.Dims):
    """(B, S, vocab) float32 logits of ``tokens`` (B, S)."""
    x = _trunk(key, tokens, dims)
    return jnp.einsum("bsd,dv->bsv", x, WM.head(key, dims), precision=HI)


@partial(jax.jit, static_argnames=("dims",))
def forward_stats(key, tokens, targets, dims: WM.Dims):
    """Per position of ``tokens`` (B, S): the best logit, the logit of
    ``targets`` (B, S) and its log-probability, all under the reference."""
    lg = logits(key, tokens, dims)
    best = jnp.max(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    lse = jax.nn.logsumexp(lg, axis=-1)
    return {"best": best, "target": tgt, "target_logp": tgt - lse}
