"""Pallas TPU kernel: fused FP8 quantize + DSBP predict + mantissa align.

This is the macro's *input path* (max-exponent logic + MPU + FIAU) as one
VPU kernel: a f32/bf16 tile comes in from HBM, and aligned integer
mantissas + per-64-group scales + predicted bitwidths go out.  Fusing the
three stages means the activations are read exactly once (the memory-term
optimization for the serving path — see DESIGN.md §8).

The tile-level math lives in :func:`quant_align_tile` so the standalone
kernel here and the one-pass GEMM in ``kernels/dsbp_fused.py`` (which runs
the same stages and feeds the MXU dot without ever writing the aligned
ints to HBM) share ONE implementation.

Implementation notes (TPU-friendly, no transcendentals):
  * FP8 round-to-nearest-even is done with the same step-quantization as
    repro.core.formats.quantize, but the exponent comes from the f32 bit
    pattern (bitcast) instead of frexp, and 2**n from bit assembly — both
    lower to pure VPU integer ops.
  * the predictor is Eq. (1) vectorized in f32 (the bit-exact 8b-LUT MPU is
    the DCIM circuit model; its ≤1-level deviation is characterized in
    tests/test_mpu.py).
  * groups (64) never straddle tiles, so there is no cross-tile reduction;
    within a tile the per-group reductions are lane butterflies, so every
    intermediate keeps the tile's (rows, lanes) shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dsbp import DSBPConfig, MAX_SHIFT
from repro.core.formats import get_format

from . import backend

GROUP = 64

__all__ = ["fp8_quant_align_kernel_call", "quant_align_tile", "pick_bk",
           "GROUP"]


def _exp2i(n):
    """Exact 2**n via f32 bit assembly (n in [-126, 127])."""
    return jax.lax.bitcast_convert_type(
        (n.astype(jnp.int32) + 127) << 23, jnp.float32
    )


def _floor_log2(ax):
    """Exponent field of |x| (normal f32 range; subnormal f32 -> emin clamp)."""
    bits = jax.lax.bitcast_convert_type(ax, jnp.int32)
    return ((bits >> 23) & 0xFF) - 127


def _group_allreduce(v, op, partner_is_up):
    """Reduce ``v (bm, bk)`` over each aligned 64-lane group with ``op``;
    every lane receives its group's result.

    An XOR butterfly: at distance ``d`` lane ``i`` combines with lane
    ``i ^ d``, which lies in the same group for every ``d < 64``, so lane
    rotations of the whole tile never mix groups.  Both partners of a pair
    compute ``op`` of the same two values, so all 64 lanes end bitwise
    equal.  The tile stays (bm, bk) throughout: Mosaic refuses the
    ``(bm, bk) -> (bm, bk/64, 64)`` lane split a grouped reshape needs.
    """
    bk = v.shape[-1]
    for d, up in partner_is_up:
        v = op(v, jnp.where(up, pltpu.roll(v, bk - d, 1), pltpu.roll(v, d, 1)))
    return v


def _butterfly_partners(shape):
    """Per butterfly distance ``d``: a mask of the lanes whose partner
    ``i ^ d`` sits above them, i.e. where ``roll(v, bk - d)`` holds the
    partner.  Derived by rolling the lane index itself, so the result does
    not depend on the rotation direction convention of ``pltpu.roll``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    bk = shape[-1]
    return [
        (d, pltpu.roll(lane, bk - d, 1) == (lane ^ d))
        for d in (1, 2, 4, 8, 16, 32)
    ]


def quant_align_tile(x: jax.Array, cfg: DSBPConfig):
    """Tile-level input path: quantize + predict + align one VMEM tile.

    ``x (bm, bk)`` f32, already multiplied by the per-tensor scale, with
    ``bk`` a multiple of the group (groups never straddle tiles).  Returns
    ``(a, scale, bits)``, all ``(bm, bk)`` with the per-group values
    repeated on each of the group's 64 lanes: aligned mantissas ``a`` as
    *integer-valued f32* (callers cast — the standalone kernel stores
    int32, the fused GEMM feeds the MXU dot directly), group scales
    ``scale`` f32 and predicted widths ``bits`` int32.  This is the one
    shared implementation behind both the standalone kernel below and
    ``kernels/dsbp_fused`` (DESIGN.md §8).

    The group reductions run as lane butterflies (:func:`_group_allreduce`),
    so their summation order differs from ``jnp.sum``; the results still
    match the reference bit for bit whenever the sums are exact in f32,
    which holds for every E4M3 input (shifts <= 14: each term is a multiple
    of 2**-14 and a group sums to <= 64).
    """
    f = get_format(cfg.fmt)
    x = x.astype(jnp.float32)
    partners = _butterfly_partners(x.shape)

    # ---- FP8 quantize (RNE, saturating) + field extraction ----
    ax = jnp.abs(x)
    e = jnp.maximum(_floor_log2(jnp.where(ax > 0, ax, 1.0)), f.emin)
    step = _exp2i(e - f.mbits)
    q = jnp.clip(jnp.round(x / step) * step, -f.max_value, f.max_value)
    q = jnp.where(ax > 0, q, 0.0)
    aq = jnp.abs(q)
    e_unb = jnp.clip(_floor_log2(jnp.where(aq > 0, aq, 1.0)), f.emin, f.emax)
    m_int = jnp.round(aq * _exp2i(f.mbits - e_unb))
    nz = aq > 0
    e_unb = jnp.where(nz, e_unb, f.emin)

    # ---- group max-exponent + shifts (the max-exponent logic) ----
    empty = -(2**30)
    e_max = _group_allreduce(jnp.where(nz, e_unb, empty), jnp.maximum,
                             partners)
    e_max = jnp.where(e_max > empty, e_max, 0)  # all-zero group -> 0
    shift = jnp.clip(e_max - e_unb, 0, MAX_SHIFT)
    shift = jnp.where(nz, shift, MAX_SHIFT)

    # ---- MPU: Eq. (1) on the VPU ----
    if cfg.mode == "fixed":
        b = jnp.full(x.shape, cfg.b_fix, jnp.int32)
    else:
        w = _exp2i(-shift) * nz.astype(jnp.float32)
        num = _group_allreduce(shift.astype(jnp.float32) * w, jnp.add,
                               partners)
        den = _group_allreduce(w, jnp.add, partners)
        ratio = jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)
        b = jnp.clip(jnp.ceil(cfg.k * ratio + cfg.b_fix), 1, 11).astype(jnp.int32)

    # ---- FIAU: align to (B+1)-bit signed ints sharing 2**(e_max-(B-1)) ----
    sign = jnp.where(q < 0, -1.0, 1.0)
    mag = sign * m_int * _exp2i(b - 1 - shift - f.mbits)
    lim = _exp2i(b)
    if cfg.mantissa_rounding == "rne":
        a = jnp.clip(jnp.round(mag), -(lim - 1.0), lim - 1.0)
    else:
        a = jnp.clip(jnp.floor(mag), -lim, lim - 1.0)

    return a, _exp2i(e_max - (b - 1)), b


# VMEM budget per grid step, in elements: the f32 input tile and the f32
# copy of the weight tile each stay near 0.25-2 MiB, so the input path's
# temporaries and the double-buffered operands fit the default scoped VMEM
_X_TILE_ELEMS = 128 * 512
_W_TILE_ELEMS = 2048 * 256


def pick_bk(k: int, bm: int, bn: int = 0) -> int:
    """Reduction block shared by the kernels: the largest divisor of ``k``
    that is a multiple of 128 lanes and keeps the f32 input tile (bm, bk)
    and weight tile (bk, bn) in budget; ``k`` itself when it fits or when
    no 128-aligned divisor exists (K' = 64·odd)."""
    if bm * k <= _X_TILE_ELEMS and k * bn <= _W_TILE_ELEMS:
        return k
    best = 0
    for bk in range(128, k, 128):
        if (k % bk == 0 and bm * bk <= _X_TILE_ELEMS
                and bk * bn <= _W_TILE_ELEMS):
            best = bk
    return best or k


def _kernel(x_ref, a_ref, s_ref, b_ref, *, cfg: DSBPConfig):
    a, s, b = quant_align_tile(x_ref[...], cfg)
    a_ref[...] = a.astype(a_ref.dtype)
    s_ref[...] = s
    b_ref[...] = b


@functools.partial(jax.jit, static_argnames=("cfg", "bm", "bk", "interpret"))
def fp8_quant_align_kernel_call(
    x: jax.Array,
    cfg: DSBPConfig,
    *,
    bm: int = 256,
    bk: int | None = None,
    interpret: bool | None = None,
):
    """x (M, K) f32 (pre-scaled by the per-tensor scale) ->
    (a (M,K) int32, scale (M,K//64) f32, bits (M,K//64) int32).

    The kernel writes scales and widths once per lane — (bm, bk) blocks
    obey the TPU block rule where (bm, bk/64) ones would not — and the
    per-group values are read off every 64th lane outside it.

    M is ragged-friendly: any batch/token count is zero-padded up to a
    multiple of the row block internally and the outputs are sliced back —
    decode batches like B=3 need no caller-side padding."""
    if interpret is None:
        interpret = backend.interpret_default()
    m, k = x.shape
    assert k % GROUP == 0
    bm = min(bm, m)
    bk = pick_bk(k, bm) if bk is None else min(bk, k)
    assert k % bk == 0 and bk % GROUP == 0
    pad_m = (-m) % bm
    if pad_m:  # ragged M: zero rows quantize to a=0 and are sliced away
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    mp = m + pad_m
    spec = pl.BlockSpec((bm, bk), lambda i, j: (i, j))
    a, s, b = pl.pallas_call(
        functools.partial(_kernel, cfg=cfg),
        grid=(mp // bm, k // bk),
        in_specs=[spec],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((mp, k), jnp.int32),
            jax.ShapeDtypeStruct((mp, k), jnp.float32),
            jax.ShapeDtypeStruct((mp, k), jnp.int32),
        ],
        interpret=interpret,
    )(x)
    return a[:m], s[:m, ::GROUP], b[:m, ::GROUP]
