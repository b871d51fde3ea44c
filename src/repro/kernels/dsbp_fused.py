"""Pallas TPU kernel: the one-pass quantize-align-MAC DSBP GEMM.

This is the paper's macro datapath as ONE kernel (DESIGN.md §8): the FP8
quantize + DSBP predict + mantissa align stages
(``fp8_quant_align.quant_align_tile`` — the same tile math as the
standalone input-path kernel) run on the activation tile in VMEM, and the
aligned integers feed the scale-folded MXU dot of
``dsbp_matmul._kernel_folded`` directly.  Exactly like the FIAU feeds the
INT MAC array with no intermediate buffer, the int32 ``(M, K)``
aligned-mantissa intermediate, its ``(M, K/64)`` group scales and the bits
map never leave VMEM.  The two-kernel path round-trips all three through
HBM and adds two full-tensor elementwise passes (``x * ts`` before,
``y / (ts_x · ts_w)`` after); both disappear here because the tensor
scales are folded into the group scales *inside* the kernel.

Scale folding is exact: the group scales and the per-tensor / per-row FP8
scales are all powers of two, so ``sx/ts_x`` and ``sw/ts_w`` are exact f32
values, and multiplying the aligned integer mantissas (|a_x| < 2**11,
|a_w| < 2**7, exact in f32) by them only adjusts exponents — no mantissa
bit is ever rounded before the MXU dot.  The kernel is bit-exact vs
``core.quantized.dsbp_matmul_ref`` under the default RNE path whenever the
reduction fits one block (tests/test_fused.py); on the MXU the dot runs at
``Precision.HIGHEST``, so the integer products stay exact there too.

The input path runs once per (row block, K tile), not once per output
tile: the grid visits a row block's output tiles in order, the first one
aligns each K tile of the block and keeps the scale-folded result in a
``(K/bk, bm, bk)`` f32 VMEM scratch, and the others read it back, so ``x``
is read from HBM once per row block.  The stored values are the ones the
dot would have received, so every output tile's result is unchanged.

The weight operands are consumed in the container's stored kernel layout
(``PackedDSBPWeight.ka (K', N)`` int8 / ``.kscale (ng, N)``), so the
serving path performs zero per-call relayout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dsbp import DSBPConfig

from . import backend
from .fp8_quant_align import pick_bk, quant_align_tile

GROUP = 64
# default scoped VMEM of a v5e TensorCore; a longer reduction's aligned-input
# scratch (bm * K f32: 16 MiB at K = 32768) raises the limit by its own size
_SCOPED_VMEM = 16 * 2**20

__all__ = ["dsbp_fused_kernel_call", "dsbp_fused_sharded_call", "GROUP"]


def _kernel(x_ref, ts_ref, aw_ref, sw_ref, tw_ref, o_ref, ae_ref, *,
            cfg: DSBPConfig):
    j, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # ---- on-the-fly input path, entirely in VMEM, once per (row block,
    # K tile): the first output tile aligns the input and keeps it in
    # ``ae_ref``; the row block's other output tiles read it back ----
    @pl.when(j == 0)
    def _input_path():
        ts = ts_ref[0, 0]  # per-tensor input scale (power of two)
        a, s, _bits = quant_align_tile(
            x_ref[...].astype(jnp.float32) * ts, cfg)
        # fold the pow2 tensor scale into the pow2 group scales (exact)
        ae_ref[kk] = a * (s / ts)

    # ---- the folded MXU dot (dsbp_matmul._kernel_folded) ----
    gpb, _, bn = sw_ref.shape  # (groups, 1, bn): one scale row per group
    we = (
        aw_ref[...].astype(jnp.float32).reshape(gpb, GROUP, bn)
        * (sw_ref[...] / tw_ref[...])
    ).reshape(gpb * GROUP, bn)
    o_ref[...] += jnp.dot(ae_ref[kk], we, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)


@functools.partial(
    jax.jit, static_argnames=("cfg", "bm", "bn", "bk", "interpret")
)
def dsbp_fused_kernel_call(
    x: jax.Array,
    ts: jax.Array,
    aw: jax.Array,
    sw: jax.Array,
    tw: jax.Array,
    cfg: DSBPConfig,
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int | None = None,
    interpret: bool | None = None,
):
    """One-pass DSBP GEMM over a (M, N, K) grid; the input path runs at
    the first output tile of each row block and the rest reuse it.

    x  (M, K')  f32 raw activations (K' group-padded, NOT pre-scaled)
    ts ()/(1,1) f32 power-of-two per-tensor input scale
    aw (K', N)  int8 kernel-layout weight mantissas (``PackedDSBPWeight.ka``)
    sw (ng, N)  f32 per-(group, col) weight scales (``.kscale``)
    tw (1, N)   f32 power-of-two per-channel (or broadcast per-tensor)
                weight scale
    -> (M, N) f32, final output: the tensor scales are already divided out
    via in-kernel folding — no post-GEMM elementwise pass.

    M is ragged-friendly (auto-padded to the row block and sliced back).
    ``bk=None`` (default) picks the reduction block with :func:`pick_bk`:
    the whole reduction in one grid step while the tiles fit VMEM — the
    bit-exact configuration, cross-group accumulation then happens in the
    very same reduction shape as ``dsbp_matmul_ref`` — and 128-aligned K
    tiles beyond that (yi-9b's K = 4096 and 11008 at serving row blocks).
    K tiles keep every per-tile dot an exact integer dot; only the f32
    accumulation across tiles is ordered differently from the reference.
    """
    if interpret is None:
        interpret = backend.interpret_default()
    m, k = x.shape
    n = aw.shape[1]
    ng = k // GROUP
    assert k % GROUP == 0 and aw.shape[0] == k, (x.shape, aw.shape)
    assert sw.shape == (ng, n) and tw.shape == (1, n), (sw.shape, tw.shape)
    bm, bn = min(bm, m), min(bn, n)
    bk = pick_bk(k, bm, bn) if bk is None else min(bk, k)
    assert n % bn == 0 and k % bk == 0 and bk % GROUP == 0
    pad_m = (-m) % bm
    if pad_m:  # zero rows quantize to a=0 -> zero output rows, sliced away
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    mp = m + pad_m
    ts = jnp.asarray(ts, jnp.float32).reshape(1, 1)
    gpb, nk = bk // GROUP, k // bk
    ae_bytes = 4 * bm * k  # the aligned-input scratch below
    y = pl.pallas_call(
        functools.partial(_kernel, cfg=cfg),
        grid=(mp // bm, n // bn, nk),
        in_specs=[
            # x is read only while j == 0; after that its block index stays
            # on the last K tile, so no output tile past the first fetches it
            pl.BlockSpec((bm, bk),
                         lambda i, j, kk: (i, jnp.where(j == 0, kk, nk - 1))),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            # (ng, 1, N): a (gpb, 1, bn) block is legal for any gpb, where
            # a (gpb, bn) one needs gpb % 8 == 0 (K = 11008 has 172 groups)
            pl.BlockSpec((gpb, 1, bn), lambda i, j, kk: (kk, 0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        # the row block's scale-folded aligned input, one slot per K tile
        scratch_shapes=[pltpu.VMEM((nk, bm, bk), jnp.float32)],
        # output tiles past the first read what the first one stored
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=(_SCOPED_VMEM + ae_bytes
                              if ae_bytes > _SCOPED_VMEM // 2 else None)),
        interpret=interpret,
    )(x, ts, aw, sw.reshape(ng, 1, n), tw)
    return y[:m] if pad_m else y


def dsbp_fused_sharded_call(
    x: jax.Array,
    ts: jax.Array,
    aw: jax.Array,
    sw: jax.Array,
    tw: jax.Array,
    cfg: DSBPConfig,
    mesh,
    *,
    batch_axis=None,
    k_axis: str | None = None,
    n_axis: str | None = None,
    bm: int = 128,
    bn: int = 256,
    bk: int | None = None,
    interpret: bool | None = None,
):
    """The one-pass DSBP GEMM under ``shard_map``, collective folded in
    (DESIGN.md §11).

    Operand layout mirrors :func:`dsbp_fused_kernel_call`; the extra axis
    arguments name mesh axes:

      batch_axis  shards the M (token) rows of ``x`` / ``y`` — pure data
                  parallelism, no collective;
      n_axis      shards the output dim: ``aw (K', N/s)`` / ``kscale`` /
                  ``tw`` column shards, each device runs the full-K fused
                  GEMM for its columns (column-parallel TP, no collective);
      k_axis      shards the contraction: ``x (M, K'/s)`` against
                  ``aw (K'/s, N)`` row shards — each device quantizes and
                  aligns only its own K-slice (group boundaries are
                  shard-local because shards are group-aligned) and ONE
                  ``jax.lax.psum`` folds the partial products AFTER the
                  in-kernel scale division (row-parallel TP).

    The psum is bit-exact vs the single-device reduction under the §8
    exactness argument: every local partial is an exact multiple of the
    common pow2 granularity (integer mantissa products x pow2 folded
    scales), so summing shards reassociates an exact sum.  ``ts`` is the
    GLOBAL power-of-two input scale — computed over the full activation
    before sharding and replicated, so per-device quantization is
    bit-identical to the unsharded input path.

    Callers guarantee divisibility: M by batch_axis, N by n_axis, and K' by
    ``GROUP * size(k_axis)`` (shards must be group-aligned).  ``ops.
    dsbp_matmul_fused_sharded`` checks and falls back to replication per
    axis, mirroring the sharding-rule behavior (parallel/sharding.py).
    """
    from jax.sharding import PartitionSpec as P

    m, k = x.shape
    n = aw.shape[1]

    def _sz(ax):  # axis (or axis tuple, for batch) -> total mesh extent
        if not ax:
            return 1
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        return math.prod(mesh.shape[a] for a in axes)

    m_l, n_l, k_l = m // _sz(batch_axis), n // _sz(n_axis), k // _sz(k_axis)
    assert m_l * _sz(batch_axis) == m, (m, batch_axis)
    assert n_l * _sz(n_axis) == n, (n, n_axis)
    assert k_l * _sz(k_axis) == k and k_l % GROUP == 0, (k, k_axis, k_l)
    # block sizes must tile the LOCAL shard
    bn_l = min(bn, n_l)
    if n_l % bn_l:
        bn_l = n_l
    bk_l = None if bk is None else min(bk, k_l)
    if bk_l is not None and (k_l % bk_l or bk_l % GROUP):
        bk_l = k_l
    ts = jnp.asarray(ts, jnp.float32).reshape(1, 1)

    def local(xl, tsl, awl, swl, twl):
        y = dsbp_fused_kernel_call(
            xl, tsl, awl, swl, twl, cfg,
            bm=bm, bn=bn_l, bk=bk_l, interpret=interpret,
        )
        if k_axis is not None:
            y = jax.lax.psum(y, k_axis)  # fold the contraction partials
        return y

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(batch_axis, k_axis),   # x
            P(None, None),           # ts: replicated global input scale
            P(k_axis, n_axis),       # ka
            P(k_axis, n_axis),       # kscale (ng rows follow the K shards)
            P(None, n_axis),         # tscale
        ),
        out_specs=P(batch_axis, n_axis),
        check_vma=False,  # jit-wrapped pallas_call defeats vma inference
    )(x, ts, aw, sw, tw)
