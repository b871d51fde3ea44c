"""Decoder blocks: attention (full/local) + FFN/MoE, RG-LRU, SSD — with a
uniform (params, x, cache) -> (x, cache) interface per layer kind so the
model can scan over heterogeneous repeating units.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kvq import init_packed_kv, quantize_like
from repro.parallel.context import anchor_batch, gather_unit_params

from . import moe as moe_mod
from . import recurrent as rec
from . import ssd as ssd_mod
from .attention import (blockwise_attention, decode_attention, gather_kv_view,
                        pv_out, qk_logits, verify_attention)
from .layers import (Quant, dense, init_dense, init_norm, rms_norm, rope,
                     rope_scaling)

__all__ = [
    "init_layer",
    "layer_seq",
    "layer_decode",
    "layer_verify",
    "layer_decode_paged",
    "layer_verify_paged",
    "init_layer_cache",
    "init_layer_cache_paged",
    "fill_kv_cache",
    "fill_kv_cache_paged",
    "write_kv_blocks",
    "rollback_kv_cache",
    "rollback_kv_cache_paged",
    "select_state_step",
    "freeze_state",
    "cache_len",
    "KIND_HAS_KV",
]

SCRATCH_BLOCK = 0  # physical block 0: masked-write sink (serve/blocks.py)

KIND_HAS_KV = {"attn_full": True, "attn_local": True, "rglru": False, "ssd": False}


# ---------------- init ----------------

def _init_attn(key, cfg, dtype):
    d, dh = cfg.d_model, cfg.d_head
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], d, cfg.n_heads * dh, dtype),
        "wk": init_dense(ks[1], d, cfg.n_kv_heads * dh, dtype),
        "wv": init_dense(ks[2], d, cfg.n_kv_heads * dh, dtype),
        "wo": init_dense(ks[3], cfg.n_heads * dh, d, dtype),
    }


def _init_ffn(key, cfg, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w1": init_dense(ks[0], d, ff, dtype),
        "w3": init_dense(ks[1], d, ff, dtype),
        "w2": init_dense(ks[2], ff, d, dtype),
    }


def init_layer(key, cfg, kind: str, dtype):
    k1, k2 = jax.random.split(key)
    p = {"norm1": init_norm(cfg.d_model, dtype)}
    if kind in ("attn_full", "attn_local"):
        p["attn"] = _init_attn(k1, cfg, dtype)
        p["norm2"] = init_norm(cfg.d_model, dtype)
        if cfg.n_experts:
            p["moe"] = moe_mod.init_moe(k2, cfg, dtype)
        else:
            p["ffn"] = _init_ffn(k2, cfg, dtype)
    elif kind == "rglru":
        p["rec"] = rec.init_rglru_block(k1, cfg, dtype)
        p["norm2"] = init_norm(cfg.d_model, dtype)
        p["ffn"] = _init_ffn(k2, cfg, dtype)
    elif kind == "ssd":
        p["ssd"] = ssd_mod.init_ssd_block(k1, cfg, dtype)
    else:  # pragma: no cover
        raise ValueError(kind)
    return p


# ---------------- ffn ----------------

def _ffn(params, x, quant):
    with jax.named_scope("mlp_in"):
        h1 = dense(params["w1"], x, quant, name="w1")
        h3 = dense(params["w3"], x, quant, name="w3")
        h = jax.nn.silu(h1.astype(jnp.float32)).astype(x.dtype) * h3
    with jax.named_scope("mlp_out"):
        return dense(params["w2"], h, quant, name="w2")


def _mlp_part(params, x, cfg, quant, no_drop=False, expert_rows=None):
    y = rms_norm(params["norm2"], x, cfg.norm_eps)
    if cfg.n_experts:
        return x + moe_mod.moe_ffn(params["moe"], y, cfg, quant, no_drop,
                                   expert_rows=expert_rows)
    return x + _ffn(params["ffn"], y, quant)


# ---------------- attention, sequence mode ----------------

@jax.named_scope("qkv")
def _qkv(params, y, cfg, quant, positions, kind):
    b, s, _ = y.shape
    dh = cfg.d_head
    q = dense(params["wq"], y, quant, name="wq").reshape(b, s, cfg.n_heads, dh)
    k = dense(params["wk"], y, quant, name="wk").reshape(b, s, cfg.n_kv_heads, dh)
    v = dense(params["wv"], y, quant, name="wv").reshape(b, s, cfg.n_kv_heads, dh)
    yarn = rope_scaling(cfg, kind)
    q = rope(q.transpose(0, 2, 1, 3), positions, cfg.rope_theta, yarn)
    k = rope(k.transpose(0, 2, 1, 3), positions, cfg.rope_theta, yarn)
    return q, k, v.transpose(0, 2, 1, 3)


@jax.named_scope("attn_out")
def _attn_out(params, o, x, cfg, quant):
    """``x`` plus the output projection of the heads ``o`` (B, Hq, S, D)."""
    b, _, s, _ = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.d_head)
    return x + dense(params["wo"], o.astype(x.dtype), quant, name="wo")


def _attn_seq(params, x, cfg, kind, quant, positions, lengths=None):
    y = rms_norm(params["norm1"], x, cfg.norm_eps)
    q, k, v = _qkv(params["attn"], y, cfg, quant, positions, kind)
    window = cfg.window if kind == "attn_local" else 0
    o = blockwise_attention(q, k, v, causal=True, window=window, kv_lens=lengths)
    x = _attn_out(params["attn"], o, x, cfg, quant)
    return x, (k, v)


# ---------------- per-kind sequence step ----------------

def layer_seq(params, x, cfg, kind, quant=None, positions=None, state=None,
              no_drop=False, lengths=None):
    """(x, carry_state) for one layer in sequence mode.

    Returns (x_out, aux) where aux is (k, v) for attention kinds (for cache
    construction during prefill) or the recurrent state dict.

    ``lengths`` ((B,) int32, optional) marks right-padded rows of a ragged
    batch: attention masks keys at/after each row's length, and the
    recurrent kinds freeze their state across pad steps, so aux/state is
    what each sequence would produce served alone at its true length.
    """
    if positions is None:
        positions = jnp.arange(x.shape[1])
    params = gather_unit_params(params)  # FSDP all-gather point (no-op
    x = anchor_batch(x)                  # outside a sharding_ctx)
    if kind in ("attn_full", "attn_local"):
        x, kv = _attn_seq(params, x, cfg, kind, quant, positions, lengths)
        x = _mlp_part(params, x, cfg, quant, no_drop)
        return x, kv
    if kind == "rglru":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, st = rec.rglru_block(params["rec"], y, cfg, quant, state,
                                lengths=lengths)
        x = x + o
        x = _mlp_part(params, x, cfg, quant, no_drop)
        return x, st
    if kind == "ssd":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, st = ssd_mod.ssd_block(params["ssd"], y, cfg, quant, state,
                                   chunk=cfg.ssd_chunk, lengths=lengths)
        return x + o, st
    raise ValueError(kind)  # pragma: no cover


# ---------------- caches ----------------

def cache_len(cfg, kind, max_len: int) -> int:
    if kind == "attn_local" and cfg.window:
        return min(max_len, cfg.window)
    return max_len


def _kv_entry(shp, dtype, kv):
    """One {'k','v'} cache container: float arrays, or packed DSBP blocks
    when a resolved ``kv`` spec (:class:`repro.kvq.KVQuantConfig`) is set."""
    if kv is not None:
        return {"k": init_packed_kv(shp, kv), "v": init_packed_kv(shp, kv)}
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


def init_layer_cache(cfg, kind, batch: int, max_len: int, dtype, kv=None):
    if kind in ("attn_full", "attn_local"):
        s = cache_len(cfg, kind, max_len)
        shp = (batch, cfg.n_kv_heads, s, cfg.d_head)
        return _kv_entry(shp, dtype, kv)
    if kind == "rglru":
        return rec.init_rglru_state(batch, cfg, dtype)
    if kind == "ssd":
        return ssd_mod.init_ssd_state(batch, cfg, dtype)
    raise ValueError(kind)  # pragma: no cover


def init_layer_cache_paged(cfg, kind, batch: int, num_blocks: int,
                           block_size: int, dtype, kv=None):
    """Paged twin of :func:`init_layer_cache`: attention layers store K/V
    in a shared physical block pool (NB, Hkv, bs, D) — no batch axis; lanes
    address it through per-request block tables.  Recurrent kinds keep
    their dense per-lane state (nothing pageable about an O(1) state)."""
    if kind in ("attn_full", "attn_local"):
        shp = (num_blocks, cfg.n_kv_heads, block_size, cfg.d_head)
        return _kv_entry(shp, dtype, kv)
    return init_layer_cache(cfg, kind, batch, 1, dtype)


def _fill_slot_sources(lengths, b: int, s: int):
    """THE prefill slot-source map, shared by the dense fill and the
    block-table scatter: cache slot r of row b receives the K/V of the LAST
    valid token whose absolute position ≡ r (mod S_c).  Returns
    ``(src (B, S_c) int32 token index, ok (B, S_c) bool)`` — one gather
    that covers plain caches (identity), ring/SWA caches (trailing window)
    and ragged batches (per-row lengths); slots with ``ok`` False have no
    valid token."""
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    r = jnp.arange(s, dtype=jnp.int32)
    last = lengths[:, None] - 1                       # (B, 1)
    src = last - ((last - r[None, :]) % s)            # (B, S_c)
    return src, src >= 0


def fill_kv_cache(cache, k, v, lengths):
    """Write prefill K/V (B,H,L,D) into the (possibly ring) cache buffer.

    ``lengths`` is a scalar (uniform batch) or a (B,) vector of valid
    right-padded prompt lengths; slot sourcing per
    :func:`_fill_slot_sources` — slots with no valid token keep their
    previous (zero) contents.
    """
    s = cache["k"].shape[2]
    b, l = k.shape[0], k.shape[2]
    src, ok = _fill_slot_sources(lengths, b, s)
    ok = ok[:, None, :, None]
    idx = jnp.clip(src, 0, l - 1)[:, None, :, None]   # (B, 1, S_c, 1)

    def wr(entry, fresh):
        # quantize ONCE at the write (repro.kvq write-path contract), then
        # one masked slot-gather per leaf — idx/ok broadcast over both the
        # mantissa (.., D) and scale (.., 1) trailing widths.
        fresh = quantize_like(entry, fresh)
        return jax.tree.map(
            lambda cl, fl: jnp.where(
                ok, jnp.take_along_axis(fl, idx, axis=2).astype(cl.dtype), cl),
            entry, fresh)

    return {"k": wr(cache["k"], k), "v": wr(cache["v"], v)}


def _scatter_pool(pool_leaf, table, slots, vals, mask):
    """THE block-table scatter every paged cache write goes through.

    ``pool_leaf``: (NB, H, bs, D) physical blocks; ``table``: (B, MB)
    int32; ``slots``: (B, T) logical ring-slot indices; ``vals``:
    (B, T, H, D); ``mask``: (B, T) — entries with False are routed to the
    scratch block (physical 0), making the scatter unconditional.  Writable
    blocks are refcount-1 by the COW protocol, so unmasked duplicate
    targets can only carry bit-identical values (shared-prefix recompute).
    """
    bs = pool_leaf.shape[2]
    phys = jnp.take_along_axis(table, slots // bs, axis=1)    # (B, T)
    phys = jnp.where(mask, phys, SCRATCH_BLOCK)
    off = jnp.where(mask, slots % bs, 0)
    return pool_leaf.at[phys, :, off].set(
        jnp.where(mask[..., None, None], vals,
                  pool_leaf[phys, :, off]).astype(pool_leaf.dtype))


@jax.named_scope("kv_write")
def write_kv_blocks(pool, table, k, v, pos, write_len, s_c: int,
                    write_start=None):
    """Write T fresh K/V entries per row through the block table — the ONE
    cache-write helper behind paged decode, verify/spec, and chunked
    prefill (DESIGN.md §12).

    ``pool``: {'k','v'} (NB, H, bs, D); ``table``: (B, MB) int32; ``k``/
    ``v``: (B, H, T, D), token j of row b at absolute position
    ``pos[b] + j`` (ring slot ``(pos+j) % s_c``); ``write_len``: (B,) —
    only tokens j < write_len[b] are written (0 freezes the row: idle or
    decode-phase lanes during a chunk step); ``write_start``: optional
    (B,) absolute-position floor — positions below it skip the write
    (shared-prefix blocks hold bit-identical content already, and skipping
    keeps them refcount-shared instead of forcing a pointless COW split).
    """
    b, _, t, _ = k.shape
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    j = jnp.arange(t, dtype=jnp.int32)[None, :]
    abs_pos = posb[:, None] + j                               # (B, T)
    mask = j < jnp.broadcast_to(jnp.asarray(write_len, jnp.int32), (b,))[:, None]
    if write_start is not None:
        mask &= abs_pos >= jnp.asarray(write_start, jnp.int32)[:, None]
    slots = abs_pos % s_c

    def wr(entry, fresh):
        # fresh may already be packed (spec commit-on-accept replays the
        # verify pass's exact quantization) — quantize_like passes it through.
        fresh = quantize_like(entry, fresh)
        return jax.tree.map(
            lambda pl, fl: _scatter_pool(pl, table, slots,
                                         fl.transpose(0, 2, 1, 3), mask),
            entry, fresh)

    return {"k": wr(pool["k"], k), "v": wr(pool["v"], v)}


@jax.named_scope("kv_write")
def fill_kv_cache_paged(pool, table, k, v, lengths, s_c: int,
                        write_start=None):
    """Prefill fill as a block-table scatter: the same per-ring-slot
    source gather as :func:`fill_kv_cache` (:func:`_fill_slot_sources`),
    written through the table instead of a dense slot axis.  ``k``/``v``:
    (B, H, L, D); content is value-identical to the dense fill at every
    written slot, so the paged engine's admission numerics equal the dense
    engine's."""
    s = s_c
    b, l = k.shape[0], k.shape[2]
    src, ok = _fill_slot_sources(lengths, b, s)
    if write_start is not None:  # shared-prefix positions stay unwritten
        ok &= src >= jnp.asarray(write_start, jnp.int32)[:, None]
    idx = jnp.clip(src, 0, l - 1)[:, None, :, None]
    slots = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))

    def wr(entry, fresh):
        # quantize BEFORE the slot gather: quantization is per-(token, head)
        # independent, so gather-then-quantize == quantize-then-gather and
        # the written content is exactly what the dense fill writes.
        fresh = quantize_like(entry, fresh)
        return jax.tree.map(
            lambda pl, fl: _scatter_pool(
                pl, table, slots,
                jnp.take_along_axis(fl, idx, axis=2).transpose(0, 2, 1, 3),
                ok),
            entry, fresh)

    return {"k": wr(pool["k"], k), "v": wr(pool["v"], v)}


# ---------------- decode ----------------

def _gather_kv_entry(pool_entry, table, s_c: int):
    """Per-leaf :func:`gather_kv_view`: a packed pool entry gathers its
    mantissa and scale children through the same block table (the gather
    body only reads the shared leading axes), returning a dense per-lane
    :class:`~repro.kvq.PackedKVBlock` view for the attention math."""
    return jax.tree.map(lambda a: gather_kv_view(a, table, s_c), pool_entry)


def _attn_decode(params, x, cfg, kind, quant, cache, pos):
    """x: (B, 1, d); cache k/v: (B, Hkv, S_c, D); pos: () or (B,) int32
    absolute position of the incoming token — a vector lets ragged slots
    advance independently (continuous batching)."""
    b = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    y = rms_norm(params["norm1"], x, cfg.norm_eps)
    q, k, v = _qkv(params["attn"], y, cfg, quant, posb[:, None], kind)
    s_c = cache["k"].shape[2]
    slot = posb % s_c  # (B,) per-slot ring position
    bidx = jnp.arange(b)

    def wr(entry, fresh):
        # quantize the fresh token at the write (repro.kvq contract); the
        # slot-set broadcasts over both mantissa and scale trailing widths.
        fresh = quantize_like(entry, fresh)
        return jax.tree.map(
            lambda cl, fl: cl.at[bidx, :, slot].set(
                fl[:, :, 0].astype(cl.dtype)),
            entry, fresh)

    ck = wr(cache["k"], k)
    cv = wr(cache["v"], v)
    if kind == "attn_local" and cfg.window and s_c < 2**31:
        # ring cache: entry r holds absolute position p_r = pos - ((pos - r) mod S_c)
        r = jnp.arange(s_c)
        p_r = posb[:, None] - ((posb[:, None] - r[None, :]) % s_c)  # (B, S_c)
        valid = (p_r >= 0) & (p_r >= posb[:, None] - cfg.window + 1)
        o = _ring_decode_attention(q, ck, cv, valid)
    else:
        o = decode_attention(q, ck, cv, posb + 1, window=0)
    x = _attn_out(params["attn"], o, x, cfg, quant)
    return x, {"k": ck, "v": cv}


@jax.named_scope("attention")
def _ring_decode_attention(q, k_cache, v_cache, valid):
    b, hq, _, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    qg = (q * d**-0.5).reshape(b, hkv, rep, d)
    logits = qk_logits("bhrd,bhkd->bhrk", qg, k_cache)
    logits = jnp.where(valid[:, None, None], logits, -1e30)  # valid: (B, S_c)
    p = jax.nn.softmax(logits, axis=-1)
    o = pv_out("bhrk,bhkd->bhrd", p, v_cache)
    return o.reshape(b, hq, 1, d).astype(q.dtype)


def _attn_decode_paged(params, x, cfg, kind, quant, pool, table, posb,
                       write_len, s_c: int):
    """Paged twin of :func:`_attn_decode`: the fresh K/V go through the
    block-table scatter (:func:`write_kv_blocks`), the cache is read back
    as a dense per-lane view (:func:`gather_kv_view`) and the UNCHANGED
    decode attention math runs on it — bit-identical to the dense engine
    for every lane with ``write_len`` 1.  Lanes with ``write_len`` 0
    (idle, or mid-chunked-prefill during a decode step) write nothing and
    their output is discarded by the engine."""
    y = rms_norm(params["norm1"], x, cfg.norm_eps)
    q, k, v = _qkv(params["attn"], y, cfg, quant, posb[:, None], kind)
    pool = write_kv_blocks(pool, table, k, v, posb, write_len, s_c)
    ck = _gather_kv_entry(pool["k"], table, s_c)
    cv = _gather_kv_entry(pool["v"], table, s_c)
    if kind == "attn_local" and cfg.window and s_c < 2**31:
        r = jnp.arange(s_c)
        p_r = posb[:, None] - ((posb[:, None] - r[None, :]) % s_c)  # (B, S_c)
        valid = (p_r >= 0) & (p_r >= posb[:, None] - cfg.window + 1)
        o = _ring_decode_attention(q, ck, cv, valid)
    else:
        o = decode_attention(q, ck, cv, posb + 1, window=0)
    x = _attn_out(params["attn"], o, x, cfg, quant)
    return x, pool


def _attn_verify(params, x, cfg, kind, quant, cache, posb):
    """T-token verify attention: queries at positions pos..pos+T-1 attend
    over the cached history plus themselves (causal), then ALL T fresh K/V
    entries are written into the (possibly ring) cache — the caller rolls
    back the entries past the accepted prefix (DESIGN.md §10)."""
    b, t, _ = x.shape
    positions = posb[:, None] + jnp.arange(t)[None, :]  # (B, T)
    y = rms_norm(params["norm1"], x, cfg.norm_eps)
    q, k, v = _qkv(params["attn"], y, cfg, quant, positions, kind)
    window = cfg.window if kind == "attn_local" else 0
    # quantize-first: the fresh K/V attend in their CACHED representation,
    # so a T-token verify equals T chained decode steps token for token
    # (each decode step also attends its own just-quantized entry).
    kq = quantize_like(cache["k"], k)
    vq = quantize_like(cache["v"], v)
    o = verify_attention(q, kq, vq, cache["k"], cache["v"], posb, window=window)
    x = _attn_out(params["attn"], o, x, cfg, quant)
    s_c = cache["k"].shape[2]
    slots = positions % s_c  # distinct while T <= S_c (engine contract)
    bidx = jnp.arange(b)[:, None]

    def wr(entry, fresh):
        return jax.tree.map(
            lambda cl, fl: cl.at[bidx, :, slots].set(
                fl.transpose(0, 2, 1, 3).astype(cl.dtype)),
            entry, fresh)

    return x, {"k": wr(cache["k"], kq), "v": wr(cache["v"], vq)}


def layer_verify(params, x, cfg, kind, cache, pos, quant=None):
    """T tokens through one layer in verify mode. x: (B, T, d); pos: () or
    (B,) absolute position of token 0 per row.  Returns
    (x, new_cache, steps): ``new_cache`` is the cache advanced by all T
    tokens; ``steps`` holds what rollback needs — per-step recurrent states
    for rglru/ssd (selected by :func:`select_state_step`), nothing for
    attention (KV rollback is a slot-mask select, :func:`rollback_kv_cache`).
    """
    b = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    params = gather_unit_params(params)
    x = anchor_batch(x)
    if kind in ("attn_full", "attn_local"):
        x, cache = _attn_verify(params, x, cfg, kind, quant, cache, posb)
        x = _mlp_part(params, x, cfg, quant, no_drop=True)
        return x, cache, {}
    if kind == "rglru":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, cache, steps = rec.rglru_verify(params["rec"], y, cfg, quant, cache)
        x = x + o
        x = _mlp_part(params, x, cfg, quant, no_drop=True)
        return x, cache, steps
    if kind == "ssd":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, cache, steps = ssd_mod.ssd_verify(params["ssd"], y, cfg, quant, cache)
        return x + o, cache, steps
    raise ValueError(kind)  # pragma: no cover


def _attn_verify_paged(params, x, cfg, kind, quant, pool, table, posb,
                       s_c: int):
    """Paged verify attention with DEFERRED writes: queries attend the
    pre-step block-pool view plus the T fresh K/V (which ride as separate
    operands, exactly like dense :func:`_attn_verify`), but nothing is
    written here — the fresh K/V are returned as ``steps`` and
    :func:`rollback_kv_cache_paged` commits only the accepted prefix.
    Commit-on-accept replaces dense write-then-rollback: the pool never
    holds rejected entries, so rollback is bit-exact by construction and
    no pre-step pool copy is kept alive."""
    t = x.shape[1]
    positions = posb[:, None] + jnp.arange(t)[None, :]  # (B, T)
    y = rms_norm(params["norm1"], x, cfg.norm_eps)
    q, k, v = _qkv(params["attn"], y, cfg, quant, positions, kind)
    window = cfg.window if kind == "attn_local" else 0
    ck = _gather_kv_entry(pool["k"], table, s_c)
    cv = _gather_kv_entry(pool["v"], table, s_c)
    # quantize-first (see _attn_verify); returning the PACKED fresh K/V as
    # steps makes commit-on-accept replay this pass's exact quantization
    # (write_kv_blocks passes already-packed values through untouched).
    kq = quantize_like(pool["k"], k)
    vq = quantize_like(pool["v"], v)
    o = verify_attention(q, kq, vq, ck, cv, posb, window=window)
    x = _attn_out(params["attn"], o, x, cfg, quant)
    return x, {"k": kq, "v": vq}


def layer_verify_paged(params, x, cfg, kind, cache, table, pos, quant=None,
                       s_c: int = 0, expert_rows=None):
    """T tokens through one layer in paged verify mode (spec verify AND
    chunked prefill ride this path).  Unlike dense :func:`layer_verify`
    nothing is committed here: returns (x, steps) where ``steps`` holds the
    fresh per-layer K/V (attention) or per-step recurrent states, and
    :func:`rollback_kv_cache_paged` / :func:`select_state_step` commit the
    accepted prefix (``keep`` 0 freezes a lane entirely).  A grouped MoE
    layer appends its per-expert row counts to ``expert_rows``."""
    b = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    params = gather_unit_params(params)
    x = anchor_batch(x)
    if kind in ("attn_full", "attn_local"):
        x, steps = _attn_verify_paged(params, x, cfg, kind, quant, cache,
                                      table, posb, s_c)
        x = _mlp_part(params, x, cfg, quant, no_drop=True,
                      expert_rows=expert_rows)
        return x, steps
    if kind == "rglru":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, _, steps = rec.rglru_verify(params["rec"], y, cfg, quant, cache)
        x = x + o
        x = _mlp_part(params, x, cfg, quant, no_drop=True)
        return x, steps
    if kind == "ssd":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, _, steps = ssd_mod.ssd_verify(params["ssd"], y, cfg, quant, cache)
        return x + o, steps
    raise ValueError(kind)  # pragma: no cover


def rollback_kv_cache(old, new, keep, pos, n_new):
    """Roll a verify-advanced KV cache back to its accepted-prefix state.

    ``new`` holds ``n_new`` fresh entries per row at ring slots
    ``(pos + j) % S_c``; row b accepts the first ``keep[b]`` (>= 1) of them.
    Slots written only by rejected entries are restored from ``old``
    bit-for-bit — on a ring cache those slots still alias live history that
    the next decode step must see (slot r reads as position
    pos' - ((pos' - r) mod S_c), so a stale rejected write would be
    misread as an older position's K/V).
    """
    b, s = old["k"].shape[0], old["k"].shape[2]
    keep = jnp.broadcast_to(jnp.asarray(keep, jnp.int32), (b,))
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    slots = (posb[:, None] + jnp.arange(n_new)[None, :]) % s  # (B, n_new)
    kept = jnp.arange(n_new)[None, :] < keep[:, None]
    mask = jnp.zeros((b, s), bool).at[jnp.arange(b)[:, None], slots].max(kept)
    m = mask[:, None, :, None]  # broadcasts over mantissa AND scale widths

    def mix(entry_new, entry_old):
        return jax.tree.map(lambda n, o: jnp.where(m, n, o),
                            entry_new, entry_old)

    return {"k": mix(new["k"], old["k"]), "v": mix(new["v"], old["v"])}


def rollback_kv_cache_paged(pool, table, k_new, v_new, keep, pos, s_c: int):
    """Paged rollback = commit-on-accept: verify deferred its writes
    (:func:`_attn_verify_paged`), so restoring the accepted-prefix state is
    just writing the first ``keep[b]`` fresh entries per row through the
    block table.  ``keep`` 0 commits nothing (frozen/idle lane).  The pool
    ends bit-identical to dense write-then-:func:`rollback_kv_cache` at
    every written slot: both equal old-contents + accepted writes."""
    return write_kv_blocks(pool, table, k_new, v_new, pos, keep, s_c)


def select_state_step(steps, keep, old=None):
    """Per-row state after the accepted prefix: entry ``keep[b]-1`` of every
    per-step leaf (B, T, ...) collected by a verify pass.  With ``old``
    (the pre-verify state tree), rows with ``keep`` 0 keep their old state
    bit-for-bit — paged lanes frozen through a spec round or chunk step."""
    keep = jnp.asarray(keep, jnp.int32)

    def sel(leaf):
        idx = jnp.clip(keep - 1, 0).reshape(-1, *([1] * (leaf.ndim - 1)))
        return jnp.take_along_axis(leaf, idx, axis=1)[:, 0]

    picked = jax.tree.map(sel, steps)
    if old is None:
        return picked
    return freeze_state(old, picked, keep)


def freeze_state(old, new, write_len):
    """Row-select two state trees: rows with ``write_len`` > 0 take ``new``,
    the rest keep ``old`` bit-for-bit — how paged decode/verify freeze
    recurrent state on lanes that are idle or mid-chunked-prefill (their KV
    twin freezes via the scratch-routed masked scatter)."""
    m = jnp.asarray(write_len, jnp.int32) > 0

    def mix(n, o):
        return jnp.where(m.reshape(-1, *([1] * (n.ndim - 1))), n,
                         o.astype(n.dtype))

    return jax.tree.map(mix, new, old)


def layer_decode(params, x, cfg, kind, cache, pos, quant=None):
    """One decode step. x: (B, 1, d); pos: () or (B,). Returns (x, new_cache)."""
    params = gather_unit_params(params)
    x = anchor_batch(x)
    if kind in ("attn_full", "attn_local"):
        x, cache = _attn_decode(params, x, cfg, kind, quant, cache, pos)
        x = _mlp_part(params, x, cfg, quant, no_drop=True)
        return x, cache
    if kind == "rglru":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, cache = rec.rglru_decode_step(params["rec"], y, cache, cfg, quant)
        x = x + o
        x = _mlp_part(params, x, cfg, quant, no_drop=True)
        return x, cache
    if kind == "ssd":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, cache = ssd_mod.ssd_decode_step(params["ssd"], y, cache, cfg, quant)
        return x + o, cache
    raise ValueError(kind)  # pragma: no cover


def layer_decode_paged(params, x, cfg, kind, cache, table, pos, write_len,
                       quant=None, s_c: int = 0, expert_rows=None):
    """One paged decode step.  ``cache`` is the layer's pooled {'k','v'}
    (attention kinds, block axis leading) or its dense per-lane state
    (recurrent kinds, frozen via :func:`freeze_state` when
    ``write_len[b]`` is 0).  Returns (x, new_cache); active lanes
    (``write_len`` 1) are bit-identical to :func:`layer_decode`.  A grouped
    MoE layer appends its per-expert row counts to ``expert_rows``."""
    b = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    params = gather_unit_params(params)
    x = anchor_batch(x)
    if kind in ("attn_full", "attn_local"):
        x, cache = _attn_decode_paged(params, x, cfg, kind, quant, cache,
                                      table, posb, write_len, s_c)
        x = _mlp_part(params, x, cfg, quant, no_drop=True,
                      expert_rows=expert_rows)
        return x, cache
    if kind == "rglru":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, new = rec.rglru_decode_step(params["rec"], y, cache, cfg, quant)
        x = x + o
        x = _mlp_part(params, x, cfg, quant, no_drop=True)
        return x, freeze_state(cache, new, write_len)
    if kind == "ssd":
        y = rms_norm(params["norm1"], x, cfg.norm_eps)
        o, new = ssd_mod.ssd_decode_step(params["ssd"], y, cache, cfg, quant)
        return x + o, freeze_state(cache, new, write_len)
    raise ValueError(kind)  # pragma: no cover
