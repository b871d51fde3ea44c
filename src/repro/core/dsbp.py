"""Dynamic Shift-aware Bitwidth Prediction (DSBP) — Algorithm 1 of the paper.

Given the (sign, exponent, mantissa) fields of an FP8-quantized tensor,
partition the reduction axis into groups of ``G`` (= 64, the SRAM column
depth of the macro), and per group:

    E_max     = max_i E_i                       (zeros excluded)
    shift_i   = E_max - E_i
    w_i       = 2**(-shift_i)
    B_dyn     = ceil( sum_i shift_i*w_i / sum_i w_i )        [Algorithm 1]
      (or)      k * (sum_i shift_i*w_i / sum_i w_i) + B_fix  [MPU, Eq. (1)]
    B_g       = round_to_valid(k*B_dyn + B_fix)
                  weights: nearest of {1,3,5,7};  inputs: ceil, clamped [1,11]

and align every element to a (B_g+1)-bit signed integer sharing the group
scale 2**(E_max-(B_g-1)):

    A_i = clip(round(s_i * 2**(B_g-1-shift_i)), -(2**B_g - 1), 2**B_g - 1)

with s_i the real significand in [1,2) (normals) / [0,1) (subnormals).  The
aligned-mantissa bitwidth convention (B magnitude bits + 1 sign bit) makes
E5M7 alignment exactly int8 and 11-bit input alignment exactly int12,
matching the macro's 2-12b input / 2/4/6/8b weight INT MAC array.

Two predictor variants are provided (see DESIGN.md §3):
  * ``algorithm1`` — ceil() applied to the ratio *before* scaling by k;
    used for the offline weight path (paper: "For weights, B_g can be
    calculated offline and rounded to the nearest valid bitwidth").
  * ``mpu`` — k * raw_ratio + B_fix as computed by the MPU circuit (Eq. 1),
    then the input path's hardware round-up.  The bit-exact fixed-point MPU
    (8b reciprocal LUT etc.) lives in ``repro.core.mpu``; this module's
    float version is its oracle.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

from .formats import FPFormat, decompose, exp2i, get_format, per_tensor_scale

__all__ = [
    "DSBPConfig",
    "WEIGHT_VALID_WIDTHS",
    "INPUT_WIDTH_RANGE",
    "MAX_SHIFT",
    "group_reshape",
    "group_shifts",
    "predict_bdyn",
    "round_to_valid_weight",
    "round_to_valid_input",
    "align_group",
    "dsbp_quantize",
    "dequantize",
    "avg_total_bits",
]

WEIGHT_VALID_WIDTHS = (1, 3, 5, 7)
INPUT_WIDTH_RANGE = (1, 11)
# E5M2 spans 32 binades; shifts beyond this are saturated (the macro's
# fixed-point MPU registers saturate here too, see core/mpu.py).
MAX_SHIFT = 31


@dataclasses.dataclass(frozen=True)
class DSBPConfig:
    """Hyperparameters of one DSBP operand path (inputs or weights)."""

    fmt: str = "e4m3"  # FP8 storage format
    k: float = 1.0  # scaling factor (Table I: 0, 1, 2)
    b_fix: int = 6  # fixed bitwidth component
    group_size: int = 64  # G; the SRAM array has 64 rows
    side: Literal["input", "weight"] = "input"
    # 'dsbp' = dynamic prediction; 'fixed' = clock-gated MPU, B_g = b_fix.
    mode: Literal["dsbp", "fixed"] = "dsbp"
    predictor: Literal["algorithm1", "mpu"] = "mpu"
    # FIAU reads mantissas serially and truncates at save_len -> 'trunc';
    # Algorithm 1 line 14 says round() -> 'rne'.  Both supported; accuracy
    # delta is an ablation in benchmarks/bench_fig7.py.
    mantissa_rounding: Literal["rne", "trunc"] = "rne"
    # FP8 scaling granularity before field extraction.  The paper quantizes
    # per LLM-FP4 [10]: per-channel ('row' of the transposed weight) scales
    # for weights, per-tensor for activations.  'row' keeps E2M5 weights in
    # the normal range so group exponents reflect the true dynamic range.
    scale_granularity: Literal["tensor", "row"] = "tensor"

    def __post_init__(self):
        if self.side == "weight" and self.predictor == "mpu":
            object.__setattr__(self, "predictor", "algorithm1")

    @property
    def format(self) -> FPFormat:
        return get_format(self.fmt)


def group_reshape(x: jax.Array, group_size: int) -> jax.Array:
    """(..., K) -> (..., K//G, G), zero-padding K up to a multiple of G."""
    k = x.shape[-1]
    g = group_size
    pad = (-k) % g
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], (k + pad) // g, g)


def group_shifts(e_unb: jax.Array, m_int: jax.Array):
    """Per-group shifts.  ``e_unb``/``m_int`` already grouped (..., n_g, G).

    Zeros (m_int == 0) are excluded from the max and flagged via a mask.
    Returns (shift, e_max, nonzero_mask).
    """
    nz = m_int != 0
    neg_inf = jnp.int32(-(2**30))
    e_eff = jnp.where(nz, e_unb, neg_inf)
    e_max = jnp.max(e_eff, axis=-1)
    any_nz = jnp.any(nz, axis=-1)
    e_max = jnp.where(any_nz, e_max, 0)
    shift = jnp.clip(e_max[..., None] - e_unb, 0, MAX_SHIFT)
    shift = jnp.where(nz, shift, MAX_SHIFT)
    return shift.astype(jnp.int32), e_max.astype(jnp.int32), nz


def predict_bdyn(shift: jax.Array, nz: jax.Array) -> jax.Array:
    """Raw weighted-average ratio  sum(shift*2^-shift)/sum(2^-shift).

    Returns float; callers apply ceil / k / B_fix per the predictor variant.
    All-zero groups give 0.0 (no dynamic range -> B_fix alone suffices).
    """
    w = exp2i(-shift) * nz.astype(jnp.float32)
    num = jnp.sum(shift.astype(jnp.float32) * w, axis=-1)
    den = jnp.sum(w, axis=-1)
    return jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)


def round_to_valid_weight(b_raw: jax.Array) -> jax.Array:
    """Nearest of {1,3,5,7} (ties up): the macro's weight widths."""
    b = jnp.clip(b_raw, WEIGHT_VALID_WIDTHS[0], WEIGHT_VALID_WIDTHS[-1])
    # valid widths are the odd integers 1..7 -> round (b-1)/2 to nearest int
    idx = jnp.floor((b - 1.0) / 2.0 + 0.5)
    return (2 * idx + 1).astype(jnp.int32)


def round_to_valid_input(b_raw: jax.Array) -> jax.Array:
    """Hardware-friendly round-up to the continuous 1..11 input widths."""
    lo, hi = INPUT_WIDTH_RANGE
    return jnp.clip(jnp.ceil(b_raw), lo, hi).astype(jnp.int32)


def _predict_b(shift: jax.Array, nz: jax.Array, cfg: DSBPConfig) -> jax.Array:
    if cfg.mode == "fixed":
        b_fix = jnp.full(shift.shape[:-1], cfg.b_fix, jnp.float32)
        raw = b_fix
    elif cfg.predictor == "algorithm1":
        b_dyn = jnp.ceil(predict_bdyn(shift, nz))
        raw = cfg.k * b_dyn + cfg.b_fix
    else:  # 'mpu', Eq. (1)
        raw = cfg.k * predict_bdyn(shift, nz) + cfg.b_fix
    if cfg.side == "weight":
        return round_to_valid_weight(raw)
    return round_to_valid_input(raw)


def align_group(
    sign: jax.Array,
    e_unb: jax.Array,
    m_int: jax.Array,
    mbits: int,
    shift: jax.Array,
    e_max: jax.Array,
    b: jax.Array,
    rounding: str = "rne",
):
    """Align grouped fields to (B+1)-bit signed integers + group scale.

    Returns (a_int int32 (..., n_g, G), scale f32 (..., n_g)) such that
    dequant = a_int * scale[..., None] approximates the FP8 values with
    per-element error <= 2**(e_max - B)  (half ulp of the aligned grid).
    """
    b_e = b[..., None]
    # s_i * 2**(B-1-shift) == m_int * 2**(B-1-shift-mbits), sign applied
    mag = (
        sign.astype(jnp.float32)
        * m_int.astype(jnp.float32)
        * exp2i(b_e - 1 - shift - mbits)
    )
    lim = exp2i(b_e)  # 2**B
    if rounding == "rne":
        a = jnp.clip(jnp.round(mag), -(lim - 1.0), lim - 1.0)
    else:
        # FIAU serial read of the 2's-complement register: arithmetic
        # right-shift == floor division (toward -inf); 2c range [-2^B, 2^B-1]
        a = jnp.clip(jnp.floor(mag), -lim, lim - 1.0)
    scale = exp2i(e_max - (b - 1))
    return a.astype(jnp.int32), scale


def per_row_scale(x: jax.Array, fmt, margin: float = 1.0) -> jax.Array:
    """Power-of-two scale per row (all-but-last axes): LLM-FP4-style
    per-channel weight scaling."""
    f = get_format(fmt)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    amax = jnp.where(amax > 0, amax, 1.0)
    _, e = jnp.frexp(f.max_value * margin / amax)
    return exp2i(e - 1)


@partial(jax.jit, static_argnames=("cfg",))
def dsbp_quantize(x: jax.Array, cfg: DSBPConfig, tscale=None):
    """Full DSBP pipeline: f32 tensor -> aligned ints + scales + stats.

    The last axis of ``x`` is the reduction (MAC) axis and is grouped by
    ``cfg.group_size``.  ``tscale`` (optional) is a power-of-two scale
    taken over a larger tensor that ``x`` is part of, used in place of
    ``x``'s own.  Returns a dict:
      a        int32 (..., n_g, G)  aligned mantissas (sign applied)
      scale    f32   (..., n_g)     group scales (power of two)
      bits     int32 (..., n_g)     predicted aligned-mantissa widths B_g
      tscale   f32 () or (...,1)   power-of-two scale(s) (x ≈ deq/tscale)
      value    f32                  the FP8-quantized (pre-alignment) values
    """
    f = cfg.format
    if tscale is not None:
        tscale = jnp.asarray(tscale, jnp.float32)
    elif cfg.scale_granularity == "row":
        tscale = per_row_scale(x, f)
    else:
        tscale = per_tensor_scale(x, f)
    fields = decompose(x * tscale, f)
    sign = group_reshape(fields["sign"], cfg.group_size)
    e_unb = group_reshape(fields["e_unb"], cfg.group_size)
    m_int = group_reshape(fields["m_int"], cfg.group_size)
    shift, e_max, nz = group_shifts(e_unb, m_int)
    b = _predict_b(shift, nz, cfg)
    a, scale = align_group(
        sign, e_unb, m_int, f.mbits, shift, e_max, b, cfg.mantissa_rounding
    )
    return {
        "a": a,
        "scale": scale,
        "bits": b,
        "tscale": tscale,
        "value": fields["value"],
    }


def dequantize(q: dict) -> jax.Array:
    """Aligned ints back to (tensor-scaled) f32: inverse modulo truncation."""
    deq = q["a"].astype(jnp.float32) * q["scale"][..., None]
    flat = deq.reshape(*deq.shape[:-2], -1)
    return flat / q["tscale"]


def avg_total_bits(bits: jax.Array) -> jax.Array:
    """Average *computational* bitwidth incl. the sign bit (paper's I/W)."""
    return jnp.mean(bits.astype(jnp.float32)) + 1.0
