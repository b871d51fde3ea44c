"""LM assembly: embeddings/frontends → scanned decoder stack → head.

The repeating layer pattern (cfg.pattern) is scanned with jax.lax.scan over
stacked per-unit parameters (optionally remat'ed); the remainder layers
(cfg.tail) are unrolled.  Three entry points:

  loss_fn / forward   : training & evaluation (sequence mode)
  prefill             : sequence mode + cache construction
  decode_step         : one token through the cached stack

Modality frontends are stubs per the brief: audio = K codebook embeddings
summed (+K output heads); vlm = precomputed patch embeddings prepended.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.kvq import kv_policy_cfg

from . import blocks
from . import moe as MOE
from .attention import HIGHEST
from .layers import Quant, init_norm, rms_norm

__all__ = ["init", "forward", "loss_fn", "init_cache", "prefill",
           "decode_step", "verify_step", "rollback_cache",
           "init_paged_cache", "prefill_paged", "decode_step_paged",
           "verify_step_paged", "rollback_cache_paged"]


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _vocab_rows(cfg) -> int:
    """Embedding/head rows: padded vocab (x codebooks for audio)."""
    if cfg.frontend == "audio_codebooks":
        return cfg.padded_vocab_size * cfg.n_codebooks
    return cfg.padded_vocab_size


# ---------------- init ----------------

def _keep(path, layer):
    del path
    return layer


@partial(jax.jit, static_argnames=("cfg", "kind", "dt", "path", "layer_fn"))
def _init_units(keys, cfg, kind, dt, path, layer_fn):
    """One pattern position's stacked units, one layer per ``lax.map``
    step: only ``layer_fn``'s output is ever stacked."""
    return jax.lax.map(
        lambda k: layer_fn(path, blocks.init_layer(k, cfg, kind, dt)), keys)


def init(key, cfg: ArchConfig, layer_fn=_keep):
    """Random parameters for ``cfg``.

    ``layer_fn(path, layer_params)`` maps each layer's fresh parameters as
    soon as they exist (``path`` is ``("units", "<li>")`` or
    ``("tail", "<i>")``).  The stacked units are built one layer at a time
    inside a jitted ``lax.map``, so only ``layer_fn``'s outputs are ever
    stacked: with a packing ``layer_fn`` (``serve.engine.init_packed``)
    the float model never exists on the device.  Every layer draws the
    same key and runs the same program whatever ``layer_fn`` is, so
    ``init(key, cfg, f)`` is ``f`` applied to each layer of
    ``init(key, cfg)``.
    """
    dt = _dtype(cfg)
    keys = jax.random.split(key, cfg.n_layers + 3)
    d = cfg.d_model
    params: dict = {
        "embed": (jax.random.normal(keys[0], (_vocab_rows(cfg), d), jnp.float32)
                  * d**-0.5).astype(dt),
        "final_norm": init_norm(d, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[1], (d, _vocab_rows(cfg)), jnp.float32) * d**-0.5
        ).astype(dt)

    pat, n_units = cfg.pattern, cfg.n_units
    # stacked unit params: per pattern position, a pytree with leading n_units
    params["units"] = [
        _init_units(keys[2 + li * n_units: 2 + (li + 1) * n_units], cfg,
                    kind, dt, ("units", str(li)), layer_fn)
        for li, kind in enumerate(pat)
    ]
    tail_keys = keys[2 + len(pat) * n_units:]
    params["tail"] = [
        layer_fn(("tail", str(i)), blocks.init_layer(k, cfg, kind, dt))
        for i, (k, kind) in enumerate(zip(tail_keys, cfg.tail))
    ]
    return params


# ---------------- embedding / frontend ----------------

def embed_tokens(params, batch: dict, cfg: ArchConfig):
    """Returns (x (B,S,d), positions (S,))."""
    emb = params["embed"]
    if cfg.frontend == "audio_codebooks":
        tok = batch["tokens"]  # (B, S, K)
        offs = jnp.arange(cfg.n_codebooks, dtype=tok.dtype) * cfg.padded_vocab_size
        x = jnp.take(emb, tok + offs[None, None, :], axis=0).sum(axis=2)
    elif cfg.frontend == "vlm_patches":
        tok = batch["tokens"]  # (B, S_txt)
        tx = jnp.take(emb, tok, axis=0)
        img = batch["image_embeds"].astype(tx.dtype)  # (B, S_img, d)
        x = jnp.concatenate([img, tx], axis=1)
    else:
        x = jnp.take(emb, batch["tokens"], axis=0)
    positions = jnp.arange(x.shape[1])
    return x, positions


@jax.named_scope("lm_head")
def _head(params, x, cfg):
    """Logits over the PADDED vocab; padded rows masked to -inf.

    The last dim is ``padded_vocab_size`` for text heads and K stacked
    blocks of that width for the audio-codebooks frontend — ``col % vp < v``
    masks the pad rows of every block (identity modulo for text)."""
    from repro.parallel.context import constrain  # no-op outside sharding_ctx

    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    w = constrain(w, None, "model")  # vocab-sharded head (_GATHERED rule)
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype),
                        precision=HIGHEST)
    vp, v = cfg.padded_vocab_size, cfg.vocab_size
    if vp != v:
        valid = (jnp.arange(logits.shape[-1]) % vp) < v
        logits = jnp.where(valid, logits, jnp.asarray(-1e30, logits.dtype))
    return logits


# ---------------- sequence-mode stack ----------------

def _scan_layers(body, x, xs, cfg: ArchConfig):
    """``lax.scan`` of ``body`` over the stacked pattern units, under the
    ``layer_stack`` scope: its own ops (the per-layer slices of the stacked
    weights and caches, the restacked outputs, and the norms and residual
    adds between the named parts) carry that name in the trace."""
    with jax.named_scope("layer_stack"):
        return jax.lax.scan(body, x, xs, unroll=cfg.scan_unroll)


def _unit_seq(unit_params, x, cfg, quant, positions, with_cache: bool,
              no_drop: bool = False, lengths=None):
    """Apply one pattern unit; returns (x, list_of_aux per layer)."""
    auxs = []
    for p_layer, kind in zip(unit_params, cfg.pattern):
        x, aux = blocks.layer_seq(p_layer, x, cfg, kind, quant, positions,
                                  no_drop=no_drop, lengths=lengths)
        auxs.append(aux if (with_cache or not blocks.KIND_HAS_KV[kind]) else None)
    return x, auxs


def forward(params, batch: dict, cfg: ArchConfig, collect_cache: bool = False,
            no_drop: bool = False):
    """Sequence-mode logits.  ``no_drop=True`` disables MoE capacity
    dropping (as prefill does), making the outputs independent of batch
    composition — required for batch-invariant likelihood scoring
    (repro.eval.harness)."""
    quant = Quant(cfg.quant, cfg.quant_method)
    x, positions = embed_tokens(params, batch, cfg)

    def unit_body(xc, stacked):
        xx, auxs = _unit_seq(stacked, xc, cfg, quant, positions, collect_cache,
                             no_drop=no_drop)
        return xx, auxs

    body = jax.checkpoint(unit_body) if cfg.remat else unit_body
    x, unit_auxs = _scan_layers(body, x, tuple(params["units"]), cfg)
    tail_auxs = []
    for p_layer, kind in zip(params["tail"], cfg.tail):
        x, aux = blocks.layer_seq(p_layer, x, cfg, kind, quant, positions,
                                  no_drop=no_drop)
        tail_auxs.append(aux)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = _head(params, x, cfg)
    if collect_cache:
        return logits, (unit_auxs, tail_auxs)
    return logits


def _ce(logits, labels, mask=None):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def loss_fn(params, batch: dict, cfg: ArchConfig):
    """Next-token cross entropy; returns (loss, metrics)."""
    logits = forward(params, batch, cfg)
    labels = batch["labels"]
    if cfg.frontend == "audio_codebooks":
        b, s, kv = logits.shape
        logits = logits.reshape(b, s, cfg.n_codebooks, cfg.padded_vocab_size)
        loss = _ce(logits, labels)  # labels (B, S, K)
    elif cfg.frontend == "vlm_patches":
        s_img = batch["image_embeds"].shape[1]
        loss = _ce(logits[:, s_img:], labels, batch.get("loss_mask"))
    else:
        loss = _ce(logits, labels, batch.get("loss_mask"))
    return loss, {"loss": loss}


# ---------------- caches / serving ----------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int, kv=None):
    """``kv``: optional KV-quant spec (preset name / bits / KVQuantConfig,
    or a per-entry mapping keyed ``units.{li}`` / ``tail.{i}`` with a
    ``default`` — the shape a DSBPPolicy's kv_layers takes).  Per-entry
    granularity is the finest the stacked-unit layout admits: the caches of
    one pattern position are stacked into ONE container, whose static aux
    (bits) must be uniform across units."""
    dt = _dtype(cfg)
    unit_caches = []
    for li, kind in enumerate(cfg.pattern):
        ckv = kv_policy_cfg(kv, f"units.{li}")
        per_unit = [
            blocks.init_layer_cache(cfg, kind, batch, max_len, dt, kv=ckv)
            for _ in range(cfg.n_units)
        ]
        unit_caches.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_unit))
    tail_caches = [
        blocks.init_layer_cache(cfg, kind, batch, max_len, dt,
                                kv=kv_policy_cfg(kv, f"tail.{i}"))
        for i, kind in enumerate(cfg.tail)
    ]
    return {"units": unit_caches, "tail": tail_caches}


def _prefill_trunk(params, batch: dict, cfg: ArchConfig, lengths=None):
    """THE prompt forward both prefill flavors share: sequence-mode stack,
    per-row last-valid-token logits.  Returns (logits, unit_auxs,
    tail_auxs, fill_len) — auxs are (k, v) for KV kinds (unit stacks carry
    a leading R axis from the scan) or the recurrent end states.  Dense
    :func:`prefill` and :func:`prefill_paged` differ ONLY in where the
    auxs land, so paged admission logits are bit-identical to dense."""
    quant = Quant(cfg.quant, cfg.quant_method)
    x, positions = embed_tokens(params, batch, cfg)
    length = x.shape[1]
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)

    def unit_body(xc, stacked):
        xx, auxs = _unit_seq(stacked, xc, cfg, quant, positions, True,
                             no_drop=True, lengths=lengths)
        return xx, auxs

    body = jax.checkpoint(unit_body) if cfg.remat else unit_body
    x, unit_auxs = _scan_layers(body, x, tuple(params["units"]), cfg)
    tail_auxs = []
    for p_layer, kind in zip(params["tail"], cfg.tail):
        x, aux = blocks.layer_seq(p_layer, x, cfg, kind, quant, positions,
                                  no_drop=True, lengths=lengths)
        tail_auxs.append(aux)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    if lengths is None:
        x_last = x[:, -1:]
    else:  # per-sequence last valid position, not the pad slot
        idx = jnp.clip(lengths - 1, 0, length - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, idx, axis=1)
    logits = _head(params, x_last, cfg)
    return logits, unit_auxs, tail_auxs, (length if lengths is None else lengths)


def prefill(params, batch: dict, cfg: ArchConfig, max_len: int, lengths=None,
            kv=None):
    """Run the prompt; returns (last-valid-position logits, cache, lengths).

    ``lengths`` — optional (B,) int32 of valid prompt lengths for a
    right-padded ragged batch, counted in EMBEDDED positions (i.e. including
    the image prefix for the vlm frontend).  When given, attention masks pad
    keys, recurrent state freezes across pad steps, the returned logits are
    gathered at each row's own last valid token, the KV caches hold each
    row's true prefix, and ``lengths`` is returned as the per-slot decode
    position vector.  When None the whole batch uses x.shape[1] and a python
    int is returned (legacy uniform-batch contract).
    """
    logits, unit_auxs, tail_auxs, fill_len = _prefill_trunk(
        params, batch, cfg, lengths)
    cache = init_cache(cfg, batch["tokens"].shape[0], max_len, kv=kv)

    def pack(kind, c, aux):
        if blocks.KIND_HAS_KV[kind]:
            k, v = aux
            return blocks.fill_kv_cache(c, k, v, fill_len)
        return jax.tree.map(lambda a, cc: a.astype(cc.dtype), aux, c)

    new_units = []
    for li, kind in enumerate(cfg.pattern):
        c = cache["units"][li]
        aux = unit_auxs[li]
        if blocks.KIND_HAS_KV[kind]:
            # aux k/v have leading unit axis (R, B, H, L, D) from the scan
            new_units.append(
                jax.vmap(lambda cc, kk, vv: blocks.fill_kv_cache(cc, kk, vv, fill_len))(
                    c, aux[0], aux[1]
                )
            )
        else:
            new_units.append(jax.tree.map(lambda a, cc: a.astype(cc.dtype), aux, c))
    new_tail = [
        pack(kind, cache["tail"][i], tail_auxs[i]) for i, kind in enumerate(cfg.tail)
    ]
    return logits, {"units": new_units, "tail": new_tail}, fill_len


# ---------------- paged cache (DESIGN.md §12) ----------------

def init_paged_cache(cfg: ArchConfig, batch: int, num_blocks: int,
                     block_size: int, kv=None):
    """Block-pool cache tree: same {"units", "tail"} structure as
    :func:`init_cache`, but KV leaves are physical block pools
    ((R,) NB, Hkv, bs, D) shared by every lane, addressed through per-lane
    block tables; recurrent-state leaves keep their dense per-lane
    ((R,) B, ...) layout.  One block id spans ``block_size`` ring slots of
    EVERY KV layer at once (the layers' pools are separate arrays), so
    host-side accounting (serve/blocks.BlockAllocator) is per-table-entry."""
    dt = _dtype(cfg)
    unit_caches = []
    for li, kind in enumerate(cfg.pattern):
        ckv = kv_policy_cfg(kv, f"units.{li}")
        per_unit = [
            blocks.init_layer_cache_paged(cfg, kind, batch, num_blocks,
                                          block_size, dt, kv=ckv)
            for _ in range(cfg.n_units)
        ]
        unit_caches.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_unit))
    tail_caches = [
        blocks.init_layer_cache_paged(cfg, kind, batch, num_blocks,
                                      block_size, dt,
                                      kv=kv_policy_cfg(kv, f"tail.{i}"))
        for i, kind in enumerate(cfg.tail)
    ]
    return {"units": unit_caches, "tail": tail_caches}


def prefill_paged(params, batch: dict, cache, table, cfg: ArchConfig,
                  max_len: int, lengths=None, write_start=None):
    """Prompt admission into the block pool: the SAME sequence-mode trunk
    as :func:`prefill` (bit-identical logits), with each KV layer scattered
    through ``table`` ((B_adm, MB) int32) instead of a dense slot axis.

    ``cache`` is the pool tree from :func:`init_paged_cache` — but batched
    to the ADMITTED rows, not the lane pool: KV leaves are the shared
    physical pools (updated in place through the tables), recurrent leaves
    come back REPLACED by the admitted rows' fresh end states (B_adm, ...)
    for the engine to scatter into its lane axis.  ``write_start``
    (optional (B_adm,)) skips writing positions below it — prefix-cache
    hits whose blocks already hold bit-identical content stay shared.
    Returns (logits, new_cache_tree, fill_len)."""
    logits, unit_auxs, tail_auxs, fill_len = _prefill_trunk(
        params, batch, cfg, lengths)

    new_units = []
    for li, kind in enumerate(cfg.pattern):
        if blocks.KIND_HAS_KV[kind]:
            s_c = blocks.cache_len(cfg, kind, max_len)
            k, v = unit_auxs[li]  # (R, B, H, L, D) from the scan
            new_units.append(jax.vmap(
                lambda pool, kk, vv: blocks.fill_kv_cache_paged(
                    pool, table, kk, vv, fill_len, s_c, write_start)
            )(cache["units"][li], k, v))
        else:
            new_units.append(jax.tree.map(
                lambda a, cc: a.astype(cc.dtype), unit_auxs[li],
                cache["units"][li]))
    new_tail = []
    for i, kind in enumerate(cfg.tail):
        if blocks.KIND_HAS_KV[kind]:
            s_c = blocks.cache_len(cfg, kind, max_len)
            k, v = tail_auxs[i]
            new_tail.append(blocks.fill_kv_cache_paged(
                cache["tail"][i], table, k, v, fill_len, s_c, write_start))
        else:
            new_tail.append(jax.tree.map(
                lambda a, cc: a.astype(cc.dtype), tail_auxs[i],
                cache["tail"][i]))
    return logits, {"units": new_units, "tail": new_tail}, fill_len


def _embed_step(params, token_batch: dict, cfg: ArchConfig):
    """Token embedding for decode/verify steps: (B, T) -> (B, T, d) (audio:
    (B, T, K) codebook ids summed) — the step-mode twin of
    :func:`embed_tokens`, without its position vector."""
    emb = params["embed"]
    if cfg.frontend == "audio_codebooks":
        tok = token_batch["tokens"]
        offs = jnp.arange(cfg.n_codebooks, dtype=tok.dtype) * cfg.padded_vocab_size
        return jnp.take(emb, tok + offs[None, None, :], axis=0).sum(axis=2)
    return jnp.take(emb, token_batch["tokens"], axis=0)


def decode_step(params, token_batch: dict, cache, pos, cfg: ArchConfig):
    """One token for every sequence. token_batch['tokens']: (B, 1) (or
    (B,1,K) audio). pos: int32 absolute position — a scalar (uniform batch)
    or a (B,) vector so ragged slots advance independently (continuous
    batching). Returns (logits (B,1,V), new_cache)."""
    quant = Quant(cfg.quant, cfg.quant_method)
    x = _embed_step(params, token_batch, cfg)

    def unit_body(carry, stacked):
        xc = carry
        p_stack, c_stack = stacked
        new_caches = []
        for i, kind in enumerate(cfg.pattern):
            xc, nc = blocks.layer_decode(
                {k: v for k, v in p_stack[i].items()}, xc, cfg, kind,
                c_stack[i], pos, quant,
            )
            new_caches.append(nc)
        return xc, tuple(new_caches)

    x, new_unit_caches = _scan_layers(
        unit_body, x, (tuple(params["units"]), tuple(cache["units"])), cfg)
    new_tail = []
    for i, kind in enumerate(cfg.tail):
        x, nc = blocks.layer_decode(
            params["tail"][i], x, cfg, kind, cache["tail"][i], pos, quant
        )
        new_tail.append(nc)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = _head(params, x, cfg)
    return logits, {"units": list(new_unit_caches), "tail": new_tail}


def decode_step_paged(params, token_batch: dict, cache, table, pos, write_len,
                      cfg: ArchConfig, max_len: int,
                      expert_rows: bool = False):
    """One token per lane through the paged cached stack.  Mirrors
    :func:`decode_step` with the KV write/read going through ``table``
    ((B, MB) int32): KV pool leaves have no batch axis, so the unit scan
    strips only their unit axis; recurrent lane states keep the dense (B,)
    layout.  ``write_len`` (B,) gates the step per lane — 1 writes+advances
    (bit-identical to dense), 0 freezes KV and recurrent state (idle lanes
    and chunk-phase lanes mid-prefill).  ``max_len`` is static (it fixes
    each layer's logical ring length S_c, which dense reads off the cache
    shape).  Returns (logits (B, 1, V), new_cache), and with
    ``expert_rows`` a third output: :func:`_layer_rows`."""
    quant = Quant(cfg.quant, cfg.quant_method)
    x = _embed_step(params, token_batch, cfg)
    units, held = MOE.hold_experts(params["units"])

    def unit_body(carry, stacked):
        xc = carry
        p_stack, c_stack, u = _unit_xs(stacked)
        new_caches, rows = [], []
        for i, kind in enumerate(cfg.pattern):
            xc, nc = blocks.layer_decode_paged(
                MOE.with_experts(dict(p_stack[i]), held[i], u), xc, cfg,
                kind, c_stack[i], table, pos, write_len, quant,
                s_c=blocks.cache_len(cfg, kind, max_len), expert_rows=rows,
            )
            new_caches.append(nc)
        out = tuple(new_caches)
        return xc, ((out, _rows_of(rows, cfg, len(cfg.pattern)))
                    if expert_rows else out)

    x, unit_out = _scan_layers(
        unit_body, x, _scan_xs(units, cache["units"], held, cfg), cfg)
    new_unit_caches, unit_rows = unit_out if expert_rows else (unit_out, None)
    new_tail, tail_rows = [], []
    for i, kind in enumerate(cfg.tail):
        x, nc = blocks.layer_decode_paged(
            params["tail"][i], x, cfg, kind, cache["tail"][i], table, pos,
            write_len, quant, s_c=blocks.cache_len(cfg, kind, max_len),
            expert_rows=tail_rows,
        )
        new_tail.append(nc)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = _head(params, x, cfg)
    new_cache = {"units": list(new_unit_caches), "tail": new_tail}
    if expert_rows:
        return logits, new_cache, _layer_rows(unit_rows, tail_rows, cfg)
    return logits, new_cache


def _scan_xs(units, cache_units, held, cfg: ArchConfig):
    """What a paged step scans: the unit params and caches, and the unit
    index where expert stacks are held out of the scan
    (:func:`repro.models.moe.hold_experts`)."""
    xs = (tuple(units), tuple(cache_units))
    if any(h is not None for h in held):
        xs += (jnp.arange(cfg.n_units, dtype=jnp.int32),)
    return xs


def _unit_xs(stacked):
    """(unit params, unit caches, unit index or None) of one scan step."""
    return (*stacked, None) if len(stacked) == 2 else stacked


def _rows_of(rows, cfg: ArchConfig, n_layers: int):
    """Layers' per-expert row counts as (n_layers, E) int32; zeros where
    no layer ran the grouped expert path."""
    if rows:
        return jnp.stack(rows)
    return jnp.zeros((n_layers, cfg.n_experts), jnp.int32)


def _layer_rows(unit_rows, tail_rows, cfg: ArchConfig):
    """(n_layers, E) int32 routed rows per layer and expert, in stack
    order (unit u's pattern position i is layer ``u * len(pattern) + i``,
    the tail after), from the grouped expert path
    (``moe_ffn``'s ``expert_rows``)."""
    parts = [unit_rows.reshape(-1, cfg.n_experts)]
    if cfg.tail:
        parts.append(_rows_of(tail_rows, cfg, len(cfg.tail)))
    return jnp.concatenate(parts)


# ---------------- speculative verification (DESIGN.md §10) ----------------

def verify_step(params, token_batch: dict, cache, pos, cfg: ArchConfig,
                collect_rollback: bool = False):
    """T tokens per sequence through the cached stack in ONE forward —
    the multi-token decode contract speculative decoding verifies with.

    token_batch['tokens']: (B, T) (or (B, T, K) audio) — token j of row b
    sits at absolute position ``pos[b] + j``; attention attends over the
    cached history plus the new tokens causally, recurrent kinds advance
    their state T steps with the decode-step op chain.  ``pos``: () or (B,)
    int32.  T must not exceed any layer's cache length S_c (ring slots must
    stay distinct within one call).

    Returns ``(logits (B, T, V), new_cache)`` — equal to T chained
    :func:`decode_step` calls, with ``new_cache`` advanced by ALL T tokens —
    plus, with ``collect_rollback=True``, a third ``rollback`` pytree for
    :func:`rollback_cache` (per-step recurrent states; nothing for KV
    layers).
    """
    quant = Quant(cfg.quant, cfg.quant_method)
    x = _embed_step(params, token_batch, cfg)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))

    def unit_body(carry, stacked):
        xc = carry
        p_stack, c_stack = stacked
        new_caches, steps = [], []
        for i, kind in enumerate(cfg.pattern):
            xc, nc, st = blocks.layer_verify(
                {k: v for k, v in p_stack[i].items()}, xc, cfg, kind,
                c_stack[i], posb, quant,
            )
            new_caches.append(nc)
            steps.append(st)
        return xc, (tuple(new_caches), tuple(steps))

    x, (new_unit_caches, unit_steps) = _scan_layers(
        unit_body, x, (tuple(params["units"]), tuple(cache["units"])), cfg)
    new_tail, tail_steps = [], []
    for i, kind in enumerate(cfg.tail):
        x, nc, st = blocks.layer_verify(
            params["tail"][i], x, cfg, kind, cache["tail"][i], posb, quant
        )
        new_tail.append(nc)
        tail_steps.append(st)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = _head(params, x, cfg)
    new_cache = {"units": list(new_unit_caches), "tail": new_tail}
    if collect_rollback:
        return logits, new_cache, {"units": list(unit_steps),
                                   "tail": tail_steps}
    return logits, new_cache


def rollback_cache(old_cache, new_cache, rollback, keep, pos,
                   cfg: ArchConfig, n_new: int):
    """Roll a :func:`verify_step`-advanced cache back to the accepted-prefix
    state: row b keeps its first ``keep[b]`` (>= 1, <= n_new) verified
    tokens and the result is bit-identical to having verified only those.

    KV layers select per ring slot between the fresh write and the old
    content (:func:`blocks.rollback_kv_cache`); recurrent layers select the
    per-step state at ``keep-1`` from the verify pass's ``rollback`` pytree
    (:func:`blocks.select_state_step`).  ``old_cache`` is the cache that was
    PASSED to verify_step; ``n_new`` its token count T.
    """
    keep = jnp.asarray(keep, jnp.int32)
    new_units = []
    for li, kind in enumerate(cfg.pattern):
        if blocks.KIND_HAS_KV[kind]:
            # stacked unit caches carry a leading unit axis (R, B, ...)
            new_units.append(jax.vmap(
                lambda o, n: blocks.rollback_kv_cache(o, n, keep, pos, n_new)
            )(old_cache["units"][li], new_cache["units"][li]))
        else:
            new_units.append(jax.vmap(
                lambda s: blocks.select_state_step(s, keep)
            )(rollback["units"][li]))
    new_tail = []
    for i, kind in enumerate(cfg.tail):
        if blocks.KIND_HAS_KV[kind]:
            new_tail.append(blocks.rollback_kv_cache(
                old_cache["tail"][i], new_cache["tail"][i], keep, pos, n_new))
        else:
            new_tail.append(blocks.select_state_step(rollback["tail"][i], keep))
    return {"units": new_units, "tail": new_tail}


def verify_step_paged(params, token_batch: dict, cache, table, pos,
                      cfg: ArchConfig, max_len: int,
                      expert_rows: bool = False):
    """Paged multi-token step with DEFERRED commit — spec verification AND
    chunked prefill ride this one path.  Same logits contract as
    :func:`verify_step` (T chained decode steps), but NOTHING is written:
    returns (logits, steps) where ``steps`` mirrors the cache tree with the
    fresh per-layer K/V ((R,) B, H, T, D) for KV kinds and per-step
    recurrent states for the rest; :func:`rollback_cache_paged` commits the
    accepted prefix per lane (``keep[b]`` in [0, T], 0 = frozen lane).
    With ``expert_rows`` a third output: :func:`_layer_rows`."""
    quant = Quant(cfg.quant, cfg.quant_method)
    x = _embed_step(params, token_batch, cfg)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))
    units, held = MOE.hold_experts(params["units"])

    def unit_body(carry, stacked):
        xc = carry
        p_stack, c_stack, u = _unit_xs(stacked)
        steps, rows = [], []
        for i, kind in enumerate(cfg.pattern):
            xc, st = blocks.layer_verify_paged(
                MOE.with_experts(dict(p_stack[i]), held[i], u), xc, cfg,
                kind, c_stack[i], table, posb, quant,
                s_c=blocks.cache_len(cfg, kind, max_len), expert_rows=rows,
            )
            steps.append(st)
        out = tuple(steps)
        return xc, ((out, _rows_of(rows, cfg, len(cfg.pattern)))
                    if expert_rows else out)

    x, unit_out = _scan_layers(
        unit_body, x, _scan_xs(units, cache["units"], held, cfg), cfg)
    unit_steps, unit_rows = unit_out if expert_rows else (unit_out, None)
    tail_steps, tail_rows = [], []
    for i, kind in enumerate(cfg.tail):
        x, st = blocks.layer_verify_paged(
            params["tail"][i], x, cfg, kind, cache["tail"][i], table, posb,
            quant, s_c=blocks.cache_len(cfg, kind, max_len),
            expert_rows=tail_rows,
        )
        tail_steps.append(st)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = _head(params, x, cfg)
    steps = {"units": list(unit_steps), "tail": tail_steps}
    if expert_rows:
        return logits, steps, _layer_rows(unit_rows, tail_rows, cfg)
    return logits, steps


@jax.named_scope("kv_write")
def rollback_cache_paged(cache, table, steps, keep, pos, cfg: ArchConfig,
                         max_len: int):
    """Commit the accepted prefix of a :func:`verify_step_paged` round: KV
    layers write their first ``keep[b]`` fresh entries through the block
    table (:func:`blocks.rollback_kv_cache_paged` — commit-on-accept, the
    pool never saw the rejected ones), recurrent layers select the state at
    step ``keep[b]-1`` with the pre-round state as the ``keep`` 0 fallback.
    Bit-identical per lane to dense verify+:func:`rollback_cache`."""
    keep = jnp.asarray(keep, jnp.int32)
    new_units = []
    for li, kind in enumerate(cfg.pattern):
        if blocks.KIND_HAS_KV[kind]:
            s_c = blocks.cache_len(cfg, kind, max_len)
            new_units.append(jax.vmap(
                lambda pool, kk, vv: blocks.rollback_kv_cache_paged(
                    pool, table, kk, vv, keep, pos, s_c)
            )(cache["units"][li], steps["units"][li]["k"],
              steps["units"][li]["v"]))
        else:
            new_units.append(jax.vmap(
                lambda st, old: blocks.select_state_step(st, keep, old=old)
            )(steps["units"][li], cache["units"][li]))
    new_tail = []
    for i, kind in enumerate(cfg.tail):
        if blocks.KIND_HAS_KV[kind]:
            s_c = blocks.cache_len(cfg, kind, max_len)
            new_tail.append(blocks.rollback_kv_cache_paged(
                cache["tail"][i], table, steps["tail"][i]["k"],
                steps["tail"][i]["v"], keep, pos, s_c))
        else:
            new_tail.append(blocks.select_state_step(
                steps["tail"][i], keep, old=cache["tail"][i]))
    return {"units": new_units, "tail": new_tail}
