"""Shared model layers: RMSNorm, RoPE, dense projections (DSBP-quantizable).

Parameters are plain pytrees (nested dicts of jnp arrays); sharding rules
bind to the dict key names (repro/parallel/sharding.py), so names here are
part of the distribution contract:

  embed, lm_head, head_*           vocab-sharded
  wq, wk, wv, wo                   head-sharded (model axis)
  w1, w2, w3, router               ffn-sharded
  scale (norms), a_param, ...      replicated
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.packed import PackedDSBPWeight, get_quant_method
from repro.core.quantized import PRESETS

__all__ = ["rms_norm", "dense", "init_dense", "rope", "rope_scaling",
           "yarn_inv_freq", "Yarn", "init_norm", "Quant"]


def init_norm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def init_dense(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    if scale is None:
        scale = d_in**-0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


class Quant:
    """Threaded quantization context: a PRESETS key / config (or None) plus
    the quantized-linear method that executes it (DESIGN.md §2).

    ``preset`` may also be the string ``"policy"`` — **per-layer mode**
    (DESIGN.md §9): no single global config is active; every packed weight
    executes under the :class:`QuantizedMatmulConfig` embedded in its own
    container (``pw.cfg``), so one model serves mixed presets chosen by a
    :class:`~repro.policy.policy.DSBPPolicy`.  Raw (unpacked) weights fall
    back to the float einsum in that mode — a policy quantizes exactly the
    projections it packed.

    ``method`` is a name from the ``repro.core.packed`` registry
    ('dense_bf16', 'dsbp_ref', 'dsbp_kernel', 'dsbp_fused'); None
    auto-selects 'dsbp_ref' when a config (or policy mode) is set,
    'dense_bf16' otherwise.
    """

    def __init__(self, preset: str | None, method: str | None = None):
        self.per_layer = preset == "policy"
        if self.per_layer:
            self.cfg = None
        elif isinstance(preset, str):
            if preset not in PRESETS:
                raise ValueError(
                    f"unknown quant preset {preset!r}; valid: "
                    f"{sorted(PRESETS)} or 'policy' (per-layer packed configs)")
            self.cfg = PRESETS[preset]
        else:
            self.cfg = preset
        if method is None:
            method = "dsbp_ref" if bool(self) else "dense_bf16"
        self.method = get_quant_method(method)

    def __bool__(self):
        return self.cfg is not None or self.per_layer

    def cfg_for(self, w):
        """The config one projection executes under: the global preset, or
        (policy mode) the config its packed container was built with."""
        if self.per_layer:
            return w.cfg if isinstance(w, PackedDSBPWeight) else None
        return self.cfg


def dense(w, x: jax.Array, quant: Quant | None = None,
          name: str | None = None) -> jax.Array:
    """x (..., d_in) @ w (d_in, d_out) through the active quant method.

    ``w`` is a raw array or a :class:`PackedDSBPWeight` (offline-quantized
    int8 aligned mantissas, ~1.06 B/elem stored/sharded/gathered instead of
    2 bf16 / 4 f32 — the serving memory+collective lever).  Dispatch:

    * quant context active -> its registry method runs the GEMM under
      ``quant.cfg_for(w)`` (the global preset, or each container's own
      config in policy mode); packed weights take the true DSBP integer
      path (on-the-fly input quantization against the stored mantissas, no
      re-quantization), raw weights the QAT STE path.
    * no quant context -> packed weights dequantize (weight-only
      quantization); raw weights are the plain einsum baseline.

    ``name`` is the projection's parameter name ('wq', 'wo', ...) — the
    same key the sharding rules bind to.  Call sites pass it so the
    'dsbp_fused_sharded' method can pick the projection's tensor-parallel
    split (column vs row parallel, ``parallel.context.tp_axes_for``);
    every other method ignores it.
    """
    if quant is not None and quant:
        return quant.method.apply(w, x, quant.cfg_for(w), name=name)
    if isinstance(w, PackedDSBPWeight):
        return get_quant_method("dsbp_ref").apply(w, x, None)
    return jnp.einsum("...k,kn->...n", x, w)


class Yarn(NamedTuple):
    """YaRN rotary scaling (HF ``rope_type`` "yarn")."""

    factor: float
    original_max_len: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


def rope_scaling(cfg, kind: str) -> Yarn | None:
    """The rotary scaling a layer of ``kind`` runs under: YaRN on
    'attn_full' layers of a config that sets ``yarn_factor``, else none."""
    if kind != "attn_full" or not cfg.yarn_factor:
        return None
    return Yarn(cfg.yarn_factor, cfg.yarn_original_max_len,
                cfg.yarn_beta_fast, cfg.yarn_beta_slow,
                cfg.yarn_attention_factor)


def yarn_inv_freq(d_head: int, theta: float, yarn: Yarn) -> jax.Array:
    """Inverse frequencies (d_head/2,) as HF's ``_compute_yarn_parameters``
    makes them: dimensions that turn fewer than ``beta_slow`` times over
    the original context are interpolated (divided by ``factor``), those
    that turn more than ``beta_fast`` times keep their frequency, and a
    linear ramp blends the band between."""
    half = d_head // 2
    inv_extrap = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)

    def turns_dim(beta):
        return (d_head * math.log(yarn.original_max_len / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_dim(yarn.beta_slow)), d_head - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    return inv_extrap / yarn.factor * ramp + inv_extrap * (1.0 - ramp)


def _rope_angles(positions: jax.Array, d_head: int, theta: float,
                 yarn: Yarn | None = None):
    if yarn is None:
        half = d_head // 2
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = yarn_inv_freq(d_head, theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    if yarn is None:
        return jnp.cos(ang), jnp.sin(ang)
    scale = jnp.float32(yarn.attention_factor)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0,
         yarn: Yarn | None = None) -> jax.Array:
    """Rotary embedding. x: (B, H, S, D), positions: (B, S) or (S,);
    ``yarn`` scales the frequencies and the magnitudes (:class:`Yarn`)."""
    d = x.shape[-1]
    cos, sin = _rope_angles(positions, d, theta, yarn)  # (B, S, D/2) or (S, D/2)
    if cos.ndim == 2:  # (S, half) -> broadcast over batch
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, None], sin[:, None]  # add head axis
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
