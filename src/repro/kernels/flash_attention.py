"""Pallas TPU kernel: blockwise online-softmax (flash) attention.

Used by the serving path; supports causal + sliding-window masking and GQA
(kv-head grouping handled by the wrapper via vmap over kv heads).  Oracle:
kernels.ref.flash_attention_ref.

Grid: (Sq/bq) outer × (Skv/bkv) inner; the running (max, sum, acc) state
lives in VMEM scratch across the kv iterations of one q block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

__all__ = ["flash_attention_kernel_call", "paged_flash_attention_kernel_call",
           "packed_flash_attention_kernel_call",
           "paged_packed_flash_attention_kernel_call"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int | None,
            skv: int, bq: int, bkv: int, sq: int):
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bkv)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + (skv - sq)
    kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "bq", "bkv", "interpret")
)
def flash_attention_kernel_call(
    q: jax.Array,  # (Sq, D)
    k: jax.Array,  # (Skv, D)
    v: jax.Array,  # (Skv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool | None = None,
):
    sq, d = q.shape
    skv = k.shape[0]
    bq, bkv = min(bq, sq), min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    scale = float(1.0 / (d**0.5))
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            skv=skv, bq=bq, bkv=bkv, sq=sq,
        ),
        grid=(sq // bq, skv // bkv),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bkv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bkv, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),   # accumulator
        ],
        interpret=(backend.interpret_default() if interpret is None
                   else interpret),
    )(q, k, v)


def _packed_kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, scale: float, causal: bool,
                   window: int | None, skv: int, bq: int, bkv: int, sq: int):
    """Packed-KV body (DESIGN.md §14): K/V arrive as int8 aligned mantissas
    + per-token pow2 group scales and are consumed IN VMEM — the int8->f32
    widening happens on the kernel's own block, never as an HBM-level
    dequantized copy (``kernels.ops.count_kv_dequants`` asserts the jaxpr
    has zero such converts outside the pallas_call).

    Scale folding is exact (the §8 argument): the K scale is constant along
    the reduced D axis, so multiplying the f32 QK^T block by the pow2 row
    vector AFTER the dot equals dequantize-then-dot bit for bit; the V
    scale varies along the key reduction, so it folds INTO the probability
    row (per-term pow2 products, summation order unchanged).
    """
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale
    k = kq_ref[...].astype(jnp.float32)             # int8 -> f32, in VMEM
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bkv)
    s = s * ks_ref[...].reshape(1, bkv)             # pow2 fold: exact

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + (skv - sq)
    kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = jnp.ones((bq, bkv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = p * vs_ref[...].reshape(1, bkv)            # pow2 fold into probs
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pv, vq_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "bq", "bkv", "interpret")
)
def packed_flash_attention_kernel_call(
    q: jax.Array,        # (Sq, D)
    k_qm: jax.Array,     # (Skv, D) int8 aligned mantissas
    k_scale: jax.Array,  # (Skv, 1) f32 pow2 group scales
    v_qm: jax.Array,     # (Skv, D) int8
    v_scale: jax.Array,  # (Skv, 1) f32
    *,
    causal: bool = True,
    window: int | None = None,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool | None = None,
):
    """Flash attention consuming a packed KV cache without a dequantize
    pass: the mantissa blocks stream int8 (4x less KV HBM traffic than f32)
    and the group scales ride (bkv, 1) blocks folded in-kernel.
    Bit-identical to :func:`flash_attention_kernel_call` over the
    dequantized arrays (tests/test_kvq.py) — the §8 exactness argument
    extended to both attention GEMMs."""
    sq, d = q.shape
    skv = k_qm.shape[0]
    bq, bkv = min(bq, sq), min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    scale = float(1.0 / (d**0.5))
    return pl.pallas_call(
        functools.partial(
            _packed_kernel, scale=scale, causal=causal, window=window,
            skv=skv, bq=bq, bkv=bkv, sq=sq,
        ),
        grid=(sq // bq, skv // bkv),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bkv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bkv, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bkv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bkv, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),   # accumulator
        ],
        interpret=(backend.interpret_default() if interpret is None
                   else interpret),
    )(q, k_qm, k_scale, v_qm, v_scale)


def _paged_kernel(table_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, causal: bool, window: int | None,
                  kv_len: int, q_start: int, bq: int, bs: int):
    # table_ref is the scalar-prefetch operand: the BlockSpec index maps
    # already consumed it to stream pool block table_ref[ki] into k_ref/
    # v_ref — the kernel body only needs positions for masking.
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)          # (bs, D): drop the block axis
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bs)

    qpos = q_start + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 0)
    kpos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
    mask = kpos < kv_len                      # tail of the last block
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kv_len", "causal", "window", "q_start", "bq",
                     "interpret"),
)
def paged_flash_attention_kernel_call(
    q: jax.Array,       # (Sq, D)
    k_pool: jax.Array,  # (NB, bs, D) physical block pool, single head
    v_pool: jax.Array,  # (NB, bs, D)
    table: jax.Array,   # (nb,) int32: this lane's logical->physical blocks
    *,
    kv_len: int,        # valid kv positions (<= nb * bs)
    causal: bool = True,
    window: int | None = None,
    q_start: int = 0,   # absolute position of q row 0 (decode/verify tail)
    bq: int = 128,
    interpret: bool | None = None,
):
    """Flash attention reading K/V straight out of a paged block pool.

    The block table rides the TPU scalar-prefetch path
    (``pltpu.PrefetchScalarGridSpec``): it lands in SMEM before the kernel
    body runs, so the k/v BlockSpec index maps dereference ``t[ki]`` to DMA
    exactly the pool blocks this lane owns — HBM traffic is the lane's own
    kv_len, never the pool size, and no gathered (Sq_kv, D) copy is ever
    materialized.  Grid = (Sq/bq, nb): one kv iteration per table entry,
    same online-softmax state as the dense kernel.  Positions are ring
    SLOTS — callers cover the pre-wrap regime (slot == absolute position;
    post-wrap serving keeps the jnp gather path).  Oracle:
    ``flash_attention_kernel_call`` over the gathered view
    (models.attention.gather_kv_view), asserted in tests/test_paged.py.
    """
    sq, d = q.shape
    _, bs, _ = k_pool.shape
    nb = table.shape[0]
    assert 0 < kv_len <= nb * bs
    bq = min(bq, sq)
    assert sq % bq == 0
    scale = float(1.0 / (d**0.5))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(sq // bq, nb),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j, t: (i, 0)),
            pl.BlockSpec((1, bs, d), lambda i, j, t: (t[j], 0, 0)),
            pl.BlockSpec((1, bs, d), lambda i, j, t: (t[j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, d), lambda i, j, t: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),   # accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, causal=causal, window=window,
            kv_len=int(kv_len), q_start=int(q_start), bq=bq, bs=bs,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        interpret=(backend.interpret_default() if interpret is None
                   else interpret),
    )(table, q, k_pool, v_pool)


def _paged_packed_kernel(table_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                         causal: bool, window: int | None, kv_len: int,
                         q_start: int, bq: int, bs: int):
    """Paged twin of :func:`_packed_kernel`: the scalar-prefetched block
    table streams this lane's int8 mantissa blocks + their (1, bs, 1)
    scale columns straight out of the packed pool — per kv iteration the
    DMA moves bs*(D+4) bytes per tensor instead of 4*bs*D, and the
    widening/scale fold stays in VMEM."""
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale
    k = kq_ref[0].astype(jnp.float32)         # (bs, D): drop the block axis
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bs)
    s = s * ks_ref[0].reshape(1, bs)          # pow2 fold: exact

    qpos = q_start + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 0)
    kpos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
    mask = kpos < kv_len                      # tail of the last block
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = p * vs_ref[0].reshape(1, bs)         # pow2 fold into probs
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pv, vq_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kv_len", "causal", "window", "q_start", "bq",
                     "interpret"),
)
def paged_packed_flash_attention_kernel_call(
    q: jax.Array,          # (Sq, D)
    k_qm_pool: jax.Array,  # (NB, bs, D) int8 mantissa pool, single head
    k_scale_pool: jax.Array,  # (NB, bs, 1) f32 pow2 scales
    v_qm_pool: jax.Array,  # (NB, bs, D) int8
    v_scale_pool: jax.Array,  # (NB, bs, 1) f32
    table: jax.Array,      # (nb,) int32: this lane's logical->physical ids
    *,
    kv_len: int,
    causal: bool = True,
    window: int | None = None,
    q_start: int = 0,
    bq: int = 128,
    interpret: bool | None = None,
):
    """Flash attention over a PACKED paged block pool: the block table
    rides the scalar-prefetch path exactly as in
    :func:`paged_flash_attention_kernel_call`, but the four KV operands
    are the pool's qm/scale children — no dequantized pool copy and no
    gathered dense view ever exist in HBM.  Bit-identical to the dense
    packed kernel over the gathered view (tests/test_kvq.py)."""
    sq, d = q.shape
    _, bs, _ = k_qm_pool.shape
    nb = table.shape[0]
    assert 0 < kv_len <= nb * bs
    bq = min(bq, sq)
    assert sq % bq == 0
    scale = float(1.0 / (d**0.5))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(sq // bq, nb),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j, t: (i, 0)),
            pl.BlockSpec((1, bs, d), lambda i, j, t: (t[j], 0, 0)),
            pl.BlockSpec((1, bs, 1), lambda i, j, t: (t[j], 0, 0)),
            pl.BlockSpec((1, bs, d), lambda i, j, t: (t[j], 0, 0)),
            pl.BlockSpec((1, bs, 1), lambda i, j, t: (t[j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, d), lambda i, j, t: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),   # accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_packed_kernel, scale=scale, causal=causal, window=window,
            kv_len=int(kv_len), q_start=int(q_start), bq=bq, bs=bs,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        interpret=(backend.interpret_default() if interpret is None
                   else interpret),
    )(table, q, k_qm_pool, k_scale_pool, v_qm_pool, v_scale_pool)
