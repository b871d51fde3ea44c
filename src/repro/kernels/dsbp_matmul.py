"""Pallas TPU kernel: group-aligned integer GEMM with per-64-group scales.

This is the INT MAC array of the macro, re-tiled for the TPU MXU
(DESIGN.md §3/§4).  The DCIM 64-row column becomes a K-axis sub-block of 64
sharing one scale; a (bm × bk)×(bk × bn) VMEM tile runs bk/64 rank-64 MXU
dots, each folded into the f32 accumulator with its per-(row,group) ×
per-(group,col) scale outer product:

    acc[m, n] += dot64_g(ax, aw)[m, n] * sx[m, g] * sw[g, n]

Integer mantissas (|ax| < 2**11, |aw| < 2**7) are exact in f32, and a
64-deep dot of 18-bit products stays < 2**24 — so the kernel is bit-exact
vs. the integer reference (no rounding anywhere before the scale multiply).

VMEM budget at the default bm=bn=128 with bk from ``pick_bk`` (512 at
yi-9b's K = 4096, f32 staging):
  ax 128×512×4 + aw 512×128×4 + acc 128×128×4 + scales ≈ 0.6 MiB « 16 MiB.
bk covers 8 groups there; the MXU sees K=64 per dot — on real hardware one would
fuse 2 groups into a K=128 dot by pre-multiplying one operand's scale; that
variant is `folded=True` (both validated against the same oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import backend
from .fp8_quant_align import pick_bk

GROUP = 64

__all__ = ["dsbp_matmul_kernel_call", "GROUP"]


def _kernel(ax_ref, sx_ref, aw_ref, sw_ref, o_ref, *, groups_per_blk: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = o_ref[...]
    for g in range(groups_per_blk):  # static unroll: bk//64 MXU dots
        a = ax_ref[:, g * GROUP : (g + 1) * GROUP].astype(jnp.float32)
        b = aw_ref[g * GROUP : (g + 1) * GROUP, :].astype(jnp.float32)
        part = jnp.dot(a, b, preferred_element_type=jnp.float32)
        acc = acc + part * (sx_ref[:, g * GROUP : g * GROUP + 1] * sw_ref[g])
    o_ref[...] = acc


def _kernel_folded(ax_ref, sx_ref, aw_ref, sw_ref, o_ref, *, groups_per_blk: int):
    """Scale-folded variant: one full-width (bk-deep) MXU dot per tile.

    Both group scales are powers of two, so folding them into their own
    operand is *exact* in f32 (sx·ax: ≤11-bit int × pow2; sw·aw: ≤7-bit int
    × pow2), and

        Σ_g sx[m,g]·sw[g,n]·dot64_g  ==  dot_bk( ax⊙sx̃ , aw⊙sw̃ )

    with s̃ the group scales broadcast along their 64 lanes.  This replaces
    bk/64 rank-64 dots + bk/64 scaled adds with ONE rank-bk MXU dot — the
    compute-term optimization (DESIGN.md §8; the fused one-pass kernel in
    ``kernels/dsbp_fused.py`` builds on exactly this dot).  ``sx`` arrives
    already repeated per lane, so the input side needs no group reshape.
    """
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    gpb, _, bn = sw_ref.shape
    a = ax_ref[...].astype(jnp.float32) * sx_ref[...]
    b = aw_ref[...].astype(jnp.float32).reshape(gpb, GROUP, bn)
    b = (b * sw_ref[...]).reshape(gpb * GROUP, bn)
    o_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "folded")
)
def dsbp_matmul_kernel_call(
    ax: jax.Array,
    sx: jax.Array,
    aw: jax.Array,
    sw: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int | None = None,
    interpret: bool | None = None,
    folded: bool = False,
):
    """Tiled pallas_call; N/K must divide by their block sizes.

    ax (M,K) int, sx (M,K//64) f32, aw (K,N) int, sw (K//64,N) f32 -> (M,N) f32.

    M is ragged-friendly: decode batches like B=3 (or any M not dividing
    the row block) are zero-padded up to a multiple of ``bm`` internally
    and the output rows sliced back — no caller-side padding.

    Operands may be any integer dtype: the input path produces int32 (up to
    11 magnitude bits + sign) while pack-once weights arrive as **int8**
    aligned mantissas (<= 7 magnitude bits + sign) straight from
    ``PackedDSBPWeight`` — both stage to f32 losslessly inside the kernel.

    The kernel reads ``sx`` repeated per lane and ``sw`` as ``(ng, 1, N)``:
    TPU blocks of those shapes are legal for every ``bk``, where
    ``(bm, bk/64)`` and ``(bk/64, bn)`` ones are not.
    """
    if interpret is None:
        interpret = backend.interpret_default()
    m, k = ax.shape
    n = aw.shape[1]
    ng = k // GROUP
    assert jnp.issubdtype(ax.dtype, jnp.integer), ax.dtype
    assert jnp.issubdtype(aw.dtype, jnp.integer), aw.dtype
    assert k % GROUP == 0 and sx.shape == (m, ng) and sw.shape == (ng, n)
    bm, bn = min(bm, m), min(bn, n)
    bk = pick_bk(k, bm, bn) if bk is None else min(bk, k)
    assert n % bn == 0 and k % bk == 0 and bk % GROUP == 0
    pad_m = (-m) % bm
    if pad_m:  # zero mantissa rows contribute 0 and are sliced away
        ax = jnp.pad(ax, ((0, pad_m), (0, 0)))
        sx = jnp.pad(sx, ((0, pad_m), (0, 0)))
    mp = m + pad_m
    gpb = bk // GROUP
    sx = jnp.repeat(sx, GROUP, axis=1)
    body = _kernel_folded if folded else _kernel
    y = pl.pallas_call(
        functools.partial(body, groups_per_blk=gpb),
        grid=(mp // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((gpb, 1, bn), lambda i, j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        interpret=interpret,
    )(ax, sx, aw, sw.reshape(ng, 1, n))
    return y[:m] if pad_m else y
