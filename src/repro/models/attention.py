"""Attention: blockwise online-softmax (train/prefill) + single-step decode.

The blockwise path is the pure-JAX mirror of kernels/flash_attention.py —
never materializes the (Sq, Skv) score matrix: lax.map over query blocks,
lax.scan over KV blocks with running (max, sum, acc).  It supports causal,
sliding-window and GQA, so one implementation serves every assigned arch
(full, SWA, 5:1 local:global).

The decode path is a plain masked single-query attention: with the KV cache
possibly sequence-sharded (long_500k), its softmax reductions become
all-reduces under GSPMD — see DESIGN.md §6 (SP).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kvq import PackedKVBlock

__all__ = ["blockwise_attention", "decode_attention", "verify_attention",
           "gather_kv_view", "qk_logits", "pv_out"]

NEG_INF = -1e30
# Float dots ask for full precision: at the default precision a TPU rounds
# f32 operands to bfloat16, which moved yi-9b's prefill logits by 12% of
# their range against the reference and made the dense and paged
# schedulers pick different tokens.  bfloat16 operands are unaffected.
HIGHEST = jax.lax.Precision.HIGHEST


def _scale_row(kv: PackedKVBlock, ndim: int) -> jax.Array:
    """The per-(token, head) pow2 group scale as a (B, Hkv, 1..., S) factor
    broadcastable against an ndim-dimensional logits/probs tensor whose last
    axis is the key axis."""
    s = kv.scale[..., 0]  # (B, Hkv, S)
    return s.reshape(s.shape[0], s.shape[1], *([1] * (ndim - 3)), s.shape[2])


def qk_logits(eq: str, qg: jax.Array, kv) -> jax.Array:
    """QK^T logits with a possibly-packed K operand (DESIGN.md §14).

    Packed K folds its group scale AFTER the dot: the scale is constant
    along the reduced D axis, and multiplying the f32 dot result by a power
    of two is exact, so this equals dequantize-then-dot bit for bit.  The
    float path is byte-identical to the pre-packed code (einsum in the
    operand dtype, then cast).
    """
    if isinstance(kv, PackedKVBlock):
        lg = jnp.einsum(eq, qg.astype(jnp.float32),
                        kv.qm.astype(jnp.float32), precision=HIGHEST)
        return lg * _scale_row(kv, lg.ndim)
    return jnp.einsum(eq, qg, kv, precision=HIGHEST).astype(jnp.float32)


def pv_out(eq: str, p: jax.Array, kv) -> jax.Array:
    """P·V with a possibly-packed V operand (DESIGN.md §14).

    Packed V folds its group scale INTO the probabilities: the scale varies
    along the reduced key axis, so it must scale each term — and because a
    pow2 multiply of each f32 product is exact and the summation order is
    unchanged, this equals dequantize-then-dot bit for bit.
    """
    if isinstance(kv, PackedKVBlock):
        return jnp.einsum(eq, p * _scale_row(kv, p.ndim),
                          kv.qm.astype(jnp.float32), precision=HIGHEST)
    return jnp.einsum(eq, p, kv.astype(jnp.float32), precision=HIGHEST)


@jax.named_scope("kv_gather")
def gather_kv_view(pool: jax.Array, table: jax.Array, s_c: int) -> jax.Array:
    """Materialize a dense per-lane cache view from a paged block pool.

    ``pool``: (NB, Hkv, bs, D) physical blocks; ``table``: (B, max_blocks)
    int32 block table (entry j holds ring slots [j*bs, (j+1)*bs));
    ``s_c``: the layer's logical cache length (must be a multiple of bs).
    Returns (B, Hkv, s_c, D) — VALUE-EXACT at every slot the writer ever
    touched, so feeding it to the unchanged :func:`decode_attention` /
    :func:`verify_attention` / :func:`blockwise_attention` math yields
    bit-identical outputs to the dense engine: slots never written hold
    recycled-block garbage, but every consumer masks them to exact zeros
    (NEG_INF logits underflow to 0.0 in the softmax) before any reduction.
    This gather IS the paged read path (DESIGN.md §12); the fused-kernel
    twin streams the same blocks via a scalar-prefetched table
    (kernels/flash_attention.paged_flash_attention_kernel_call).
    """
    bs = pool.shape[2]
    nb = s_c // bs
    if nb * bs != s_c:
        raise ValueError(f"cache length {s_c} not a multiple of block "
                         f"size {bs}")
    view = pool[table[:, :nb]]               # (B, nb, Hkv, bs, D)
    b, _, h, _, d = view.shape
    return view.transpose(0, 2, 1, 3, 4).reshape(b, h, s_c, d)


def _attend_block(q, k, v, qpos, kpos, kv_len, causal, window, state,
                  kv_lens=None):
    m_prev, l_prev, acc = state
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=HIGHEST).astype(jnp.float32)
    mask = jnp.broadcast_to(kpos[None, :] < kv_len, s.shape[-2:])
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    if kv_lens is not None:  # ragged batch: keys at/after a row's length are pad
        s = jnp.where((kpos[None, :] < kv_lens[:, None])[:, None, None], s, NEG_INF)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_cur[..., None])
    alpha = jnp.exp(m_prev - m_cur)
    l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32), precision=HIGHEST)
    return m_cur, l_cur, acc


@partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bkv", "q_offset"),
)
@jax.named_scope("attention")
def blockwise_attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,  # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 512,
    bkv: int = 1024,
    q_offset: int = 0,  # absolute position of q[0] (chunked prefill)
    kv_lens: jax.Array | None = None,  # (B,) valid KV length per row (ragged)
):
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    rep = hq // hkv
    scale = d**-0.5
    q = (q * scale).reshape(b, hkv, rep, sq, d)
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    nq, nkv = -(-sq // bq), -(-skv // bkv)
    pad_q, pad_kv = nq * bq - sq, nkv * bkv - skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))

    def q_block(args):
        qi, qblk = args  # qblk: (B, Hkv, rep, bq, D)
        qb = qblk.reshape(b, hkv * rep, bq, d)
        qpos = qi * bq + jnp.arange(bq) + q_offset

        def kv_step(state, ki):
            kb = jax.lax.dynamic_slice_in_dim(k, ki * bkv, bkv, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(v, ki * bkv, bkv, axis=2)
            kb = jnp.repeat(kb, rep, axis=1)
            vb = jnp.repeat(vb, rep, axis=1)
            kpos = ki * bkv + jnp.arange(bkv)
            state = _attend_block(qb, kb, vb, qpos, kpos, skv, causal, window,
                                  state, kv_lens=kv_lens)
            return state, None

        init = (
            jnp.full((b, hkv * rep, bq), NEG_INF, jnp.float32),
            jnp.zeros((b, hkv * rep, bq), jnp.float32),
            jnp.zeros((b, hkv * rep, bq, d), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nkv))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    blocks = q.reshape(b, hkv, rep, nq, bq, d).transpose(3, 0, 1, 2, 4, 5)
    out = jax.lax.map(q_block, (jnp.arange(nq), blocks))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, hq, nq * bq, d)
    return out[:, :, :sq].astype(jnp.promote_types(q.dtype, jnp.bfloat16))


@partial(jax.jit, static_argnames=("window",))
@jax.named_scope("attention")
def verify_attention(
    q: jax.Array,        # (B, Hq, T, D)  T speculated tokens per row
    k_new: jax.Array,    # (B, Hkv, T, D) their keys (NOT yet in the cache)
    v_new: jax.Array,    # (B, Hkv, T, D)
    k_cache: jax.Array,  # (B, Hkv, S_c, D) history (entries < pos valid)
    v_cache: jax.Array,  # (B, Hkv, S_c, D)
    pos: jax.Array,      # () or (B,) absolute position of q[:, :, 0]
    window: int = 0,
):
    """Multi-token decode: T queries per row attend over the cached history
    plus the T fresh keys, causally among themselves (DESIGN.md §10).

    The fresh K/V ride as a separate operand instead of being written first:
    on a ring cache (S_c = window) the T new entries would overwrite slots
    whose OLD content earlier queries still need (query j's window reaches
    back to pos+j-window+1, which the write at pos+j' (j' > j) would evict
    as position pos+j'-S_c).  Ring entry r holds absolute position
    ``(pos-1) - ((pos-1-r) mod S_c)``; new key j sits at position pos+j.
    """
    b, hq, t, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    qg = (q * d**-0.5).reshape(b, hkv, rep, t, d)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    qpos = posb[:, None] + jnp.arange(t)[None, :]  # (B, T) absolute positions
    r = jnp.arange(s)
    last = posb[:, None] - 1
    p_old = last - ((last - r[None, :]) % s)  # (B, S_c) cached abs positions
    valid_old = (p_old >= 0)[:, None, :]  # causal vs old is automatic
    j = jnp.arange(t)
    valid_new = j[None, None, :] <= j[None, :, None]  # key j <= query j'
    if window:
        valid_old &= p_old[:, None, :] > qpos[:, :, None] - window
        valid_new = valid_new & (j[None, None, :] > j[None, :, None] - window)
    lg_old = qk_logits("bhrtd,bhkd->bhrtk", qg, k_cache)
    lg_new = qk_logits("bhrtd,bhkd->bhrtk", qg, k_new)
    lg_old = jnp.where(valid_old[:, None, None], lg_old, NEG_INF)
    lg_new = jnp.where(
        jnp.broadcast_to(valid_new, (b, t, t))[:, None, None], lg_new, NEG_INF
    )
    p = jax.nn.softmax(jnp.concatenate([lg_old, lg_new], axis=-1), axis=-1)
    out = pv_out("bhrtk,bhkd->bhrtd", p[..., :s], v_cache)
    out += pv_out("bhrtk,bhkd->bhrtd", p[..., s:], v_new)
    return out.reshape(b, hq, t, d).astype(q.dtype)


@partial(jax.jit, static_argnames=("window",))
@jax.named_scope("attention")
def decode_attention(
    q: jax.Array,  # (B, Hq, 1, D)
    k_cache: jax.Array,  # (B, Hkv, S, D)
    v_cache: jax.Array,  # (B, Hkv, S, D)
    pos: jax.Array,  # () or (B,) current position (tokens < pos are valid)
    window: int = 0,
):
    b, hq, _, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    qg = (q * d**-0.5).reshape(b, hkv, rep, d)
    logits = qk_logits("bhrd,bhkd->bhrk", qg, k_cache)
    kpos = jnp.arange(s)
    pos = jnp.asarray(pos)
    posb = jnp.broadcast_to(pos, (b,))  # ragged slots advance independently
    valid = kpos[None, :] < posb[:, None]
    if window:
        valid &= kpos[None, :] >= (posb - window)[:, None]
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = pv_out("bhrk,bhkd->bhrd", p, v_cache)
    return out.reshape(b, hq, 1, d).astype(q.dtype)
