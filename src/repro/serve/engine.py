"""Serving engine: length-aware continuous batching over packed DSBP weights.

The engine owns the KV caches and the packed DSBP weight representation
(DESIGN.md §2): when the arch config carries a quant preset, every
projection matrix is offline-quantized ONCE at ``__init__`` into a
:class:`~repro.core.packed.PackedDSBPWeight` — int8 aligned mantissas
(weights are <= 7 magnitude bits + sign) + one f32 scale per 64-group — and
prefill/decode run entirely off that packed tree.  That is the paper's
offline-weight / on-the-fly-input split: only the activation path quantizes
per token, and the HBM footprint drops ~3.8x vs f32 (1.9x vs bf16) per
projection (reported via :func:`packed_nbytes` in ``Engine.pack_report``).
Projections execute through the fused one-pass quantize-align-MAC kernel by
default (``quant_method='dsbp_fused'``, DESIGN.md §8), consuming the
container's kernel-layout operands with zero per-call relayout.

Serving is length-aware end to end (DESIGN.md §7): ragged prompts prefill
with a per-sequence ``lengths`` vector (pad-masked attention, per-row last
logits and KV fill), and decode advances a per-slot ``pos`` vector, so a
batch of mixed-length prompts generates token-for-token what each prompt
generates alone.  :meth:`Engine.serve` runs true continuous batching on top
of that contract: a fixed pool of ``batch_size`` slots, admission of queued
requests into freed slots, per-slot EOS / token-budget termination, and one
jitted decode step per pool with the KV cache donated (updated in place,
not copied per token).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.core.packed import (PackedDSBPWeight, key_entry_str,
                               pack_weights_sharded, packed_nbytes,
                               tree_is_packed)
from repro.core.quantized import PRESETS, pack_weights
from repro.kvq import is_kv_leaf_path, kv_cache_nbytes, tree_has_packed_kv
from repro.models import model as M
from repro.models import moe as MOE
from repro.obs import ServeRecorder, span

__all__ = ["ServeConfig", "Request", "Engine", "pack_weights_int8",
           "pack_tree", "init_packed", "packed_nbytes", "sample_tokens"]

# terminal request lifecycle states (DESIGN.md §13); every served uid ends
# in exactly one of these, reported via last_stats["request_status"]
REQUEST_STATES = ("ok", "preempted", "cancelled", "deadline", "quarantined")

_GUARD_POLICIES = ("fail-fast", "quarantine", "fallback")

# projection leaf names that carry a DSBP-quantizable GEMM (the sharding
# contract of models/layers.py keys these same names)
PROJ_NAMES = frozenset({
    "wq", "wk", "wv", "wo", "w1", "w2", "w3", "w_in", "w_gate", "w_out",
    "wa", "wx",
})


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 512
    batch_size: int = 4          # slot-pool size for serve()
    temperature: float = 0.0     # 0 = greedy
    seed: int = 0
    # pack projections once at Engine.__init__ when a preset is configured
    # (cfg.quant, overridable via pack_preset); False serves raw weights,
    # re-quantizing them on every matmul call.  pack_preset accepts a
    # PRESETS name, a full QuantizedMatmulConfig, or a
    # repro.policy.DSBPPolicy (per-layer configs — mixed presets in one
    # model; serving then runs in the 'policy' quant mode, DESIGN.md §9).
    pack: bool = True
    pack_preset: object | None = None
    # quantized-linear method for serving.  None defaults to 'dsbp_fused'
    # (the one-pass quantize-align-MAC kernel, DESIGN.md §8) when the arch
    # config quantizes but names no method; set 'dsbp_kernel' to fall back
    # to the two-kernel path (or 'dsbp_ref' for the jnp reference).
    quant_method: str | None = None
    eos_id: int | None = None    # serve(): slot frees when this is sampled
    prefill_bucket: int = 16     # admission prompts pad up to a multiple of
                                 # this (bounds prefill retraces per shape)
    # --- self-speculative decoding (DESIGN.md §10) ---
    # spec_k > 0 turns serve() speculative: per pool step, draft spec_k
    # tokens per slot with the MSB-slice view of the packed weights, verify
    # them in ONE batched target forward, commit the longest matching greedy
    # prefix (1..spec_k+1 tokens) and roll the cache back past it.  Greedy
    # only (temperature must be 0).  Committed tokens are always the target
    # model's own argmax over verify logits, which match sequential decode
    # logits to float round-off (~2e-5 relative: batched reductions order
    # sums differently), so the served stream equals the non-speculative
    # one token-for-token unless a decode position has an exact near-tie at
    # that tolerance — asserted empirically across archs in tests/test_spec
    # and the CI spec gate.
    spec_k: int = 0
    # aligned-mantissa width of the draft view: an int, or a per-layer
    # artifact {path: bits, 'default': bits} priced from calibration stats
    # (repro.policy.spec_bits.price_draft_bits)
    spec_draft_bits: object = 4
    # quantized-linear method for the DRAFT forward ('dsbp_ref' = the jnp
    # integer path; None inherits the serving method).  The draft is an
    # approximation by construction — verification pins the numerics — so
    # it may run the cheapest backend available.
    spec_draft_method: str | None = "dsbp_ref"
    # --- DSBP-quantized KV cache (DESIGN.md §14) ---
    # packed KV representation every cache write quantizes into: a preset
    # name ('kv8'/'kv6'/'kv4'), an int total bitwidth in [2, 8], a
    # repro.kvq.KVQuantConfig, True (the full-width 'kv8' preset), or a
    # per-entry mapping {'units.<i>': spec, 'tail.<i>': spec,
    # 'default': spec} — the shape policy.autotune emits as
    # DSBPPolicy.kv_layers.  A DSBPPolicy carrying kv_layers is accepted
    # directly.  None (default) serves the float cache unchanged.
    kv_quant: object = None
    # uniform total-bits shorthand for kv_quant (mutually exclusive)
    kv_bits: int | None = None
    # speculative rounds draft on an even narrower MSB-slice view of the
    # packed cache (repro.kvq.kv_narrow_view); verification and
    # commit-on-accept keep the full serving width, so served tokens never
    # change — only acceptance can.  Requires kv_quant; None drafts on the
    # serving-width cache.
    kv_draft_bits: int | None = None
    # --- multi-device serving (DESIGN.md §11) ---
    # mesh_shape (e.g. (2, 4)) turns the engine multi-device: weights pack
    # straight into per-shard kernel layouts, projections run the fused
    # GEMM under shard_map ('dsbp_fused_sharded' — bit-exact vs one
    # device, so a mesh can never change served tokens), KV caches shard
    # over the batch axes, and prefill/decode/speculation jit sharded-in/
    # sharded-out with cache donation preserved.  The axes name the mesh
    # dims: 'data' shards token rows + cache batch, 'model' carries the
    # Megatron TP split, an 'expert' axis additionally shards MoE expert
    # stacks.  mesh_shape=None (default) is the single-device engine.
    mesh_shape: tuple[int, ...] | None = None
    mesh_axes: tuple[str, ...] = ("data", "model")
    # device-scaled slot pool: serve() runs mesh.size * per_device_batch_size
    # slots (None keeps the flat batch_size pool)
    per_device_batch_size: int | None = None
    # --- paged KV cache (DESIGN.md §12) ---
    # paged=True swaps serve()'s dense per-slot KV caches for a fixed pool
    # of kv_block_size-token physical blocks addressed through per-lane
    # block tables: admission is gated on free BLOCKS (a memory budget)
    # instead of free slots, requests sharing a prompt prefix share
    # refcounted blocks (copy-on-write on first divergent write), and long
    # prompts prefill in prefill_bucket-sized chunks interleaved with
    # decode steps.  Token-for-token identical to the dense engine at
    # temperature 0 (tests/test_paged.py).
    paged: bool = False
    kv_block_size: int = 16      # ring slots per physical block; must divide
                                 # every KV layer's cache length
    # physical blocks in the pool INCLUDING the reserved scratch block 0.
    # None sizes it to the dense engine's KV HBM budget at batch_size
    # slots: batch_size * blocks-per-lane + 1 — prefix sharing then fits
    # strictly more than batch_size concurrent requests in the same bytes.
    kv_blocks: int | None = None
    # concurrent lane count for the paged scheduler (None = the slot-pool
    # size): lanes are cheap (a table row + recurrent state), blocks are
    # the real budget, so set this above batch_size to let sharing admit
    # more requests than the dense engine could hold
    max_active: int | None = None
    # prompts STRICTLY longer than this admit via chunked prefill
    # (prefill_bucket tokens per scheduler iteration, decode lanes advance
    # every iteration in between — zero decode stall).  None defaults to
    # 4 * prefill_bucket; chunked admissions skip prefix sharing.
    chunk_prefill_tokens: int | None = None
    prefix_sharing: bool = True  # hash-chained prefix cache + COW splits
    # --- robustness layer (DESIGN.md §13) ---
    # per-step isfinite check on the logits every sampling decision reads,
    # with a policy for non-finite lanes:
    #   None / 'off'      — no guard (the fault silently poisons the stream)
    #   'fail-fast'       — raise serve.faults.NumericFault (whole batch)
    #   'quarantine'      — release the lane, keep its partial output,
    #                       status 'quarantined' ('quarantine-lane' alias)
    #   'fallback'        — retry the step through the dsbp_ref reference
    #                       path (decode jits keep the pre-step cache:
    #                       donation is disabled in this mode only), then
    #                       quarantine if still non-finite.  Incompatible
    #                       with spec_k (the round commits in-jit).
    numeric_guard: str | None = None
    # paged scheduler: preempt a victim lane (recompute-on-resume) instead
    # of raising BlockError when a reservation / COW split cannot be
    # satisfied; False restores hard-failure semantics
    preemption: bool = True
    # assert serve/faults.check_invariants after every scheduler iteration
    # (always on while a FaultPlan is active)
    invariant_checks: bool = False
    # --- observability (DESIGN.md §15) ---
    # observe=True threads the repro.obs.ServeRecorder through the
    # scheduler: per-request lifecycle spans (Engine.obs.trace,
    # Chrome-trace exportable), a metrics registry (Engine.obs.metrics,
    # JSON/Prometheus snapshots) and quantization-health telemetry
    # (Engine.obs.health, guard-trip attribution feeding
    # policy.reprice_from_telemetry).  last_stats is identical either way
    # — it stays the backwards-compatible snapshot view.
    observe: bool = False
    # trace-event capacity; past it events are COUNTED as dropped, never
    # silently lost (the obs CI gate holds dropped == 0)
    obs_max_events: int = 200_000


@dataclasses.dataclass
class Request:
    """One queued generation request for :meth:`Engine.serve`."""
    uid: object
    tokens: np.ndarray           # (L,) prompt token ids
    max_new_tokens: int = 32
    # higher admits first and is never preempted by a lower value; the
    # paged scheduler only evicts a victim strictly below the contender
    priority: int = 0
    # scheduler iterations the request may stay resident after admission
    # before it is released with status 'deadline' (None = no deadline).
    # Counted from FIRST admission — a preempt-resume does not reset it.
    deadline_steps: int | None = None


@dataclasses.dataclass
class _ServeControl:
    """Per-serve() robustness bookkeeping shared by both schedulers and
    every helper they call (one bundle instead of six positional dicts)."""
    stats: dict
    out: dict                    # uid -> emitted token list
    status: dict                 # uid -> lifecycle state (REQUEST_STATES)
    faults: object | None = None
    step: int = 0                # scheduler iteration counter
    admit_step: dict = dataclasses.field(default_factory=dict)
    preempts: dict = dataclasses.field(default_factory=dict)
    # MoE: passes whose per-expert row counts the host has not read yet
    # ((kind, step, rows, row block)), and the latest read per pass kind
    expert_pending: list = dataclasses.field(default_factory=list)
    expert_last: dict = dataclasses.field(default_factory=dict)


def pack_weights_int8(params, preset="precise", mesh=None):
    """Offline DSBP pass over every projection matrix, run ONCE: returns a
    pytree where 2-D+ projection leaves become
    :class:`~repro.core.packed.PackedDSBPWeight` containers (int8 aligned
    mantissas, f32 group scales, per-channel tscale, logical (K, N) shape),
    plus bit statistics for the energy model.

    ``preset`` is a :data:`~repro.core.quantized.PRESETS` name, a full
    :class:`~repro.core.quantized.QuantizedMatmulConfig` (one config for
    every projection), or a :class:`~repro.policy.policy.DSBPPolicy` —
    per-layer configs keyed by projection path (``units/0/attn/wq``-style,
    DESIGN.md §9), so one model carries mixed presets; projections the
    policy does not cover stay raw.

    With ``mesh`` set, every projection packs through
    :func:`~repro.core.packed.pack_weights_sharded`: each device quantizes
    only its own output-column shard under shard_map, so the full-size
    container is never materialized on one device (bit-identical to
    pack-then-shard, DESIGN.md §11)."""
    packed = pack_tree(params, preset, mesh)
    return packed, _pack_stats(packed)


def init_packed(key, cfg: ArchConfig, preset="precise", mesh=None):
    """``pack_weights_int8(M.init(key, cfg), preset, mesh)`` built layer by
    layer: each layer's float weights are generated and packed at once
    (``M.init``'s ``layer_fn``), so the float model — 17.7 GB for yi-9b in
    bf16, more than one v5e holds — never exists on the device; only the
    packed stack (~9.9 GB for yi-9b) does.  Bit-identical to packing the
    whole float tree (tests/test_packed.py)."""
    packed = M.init(key, cfg, layer_fn=lambda path, layer: pack_tree(
        layer, preset, mesh, prefix=path))
    return packed, _pack_stats(packed)


def pack_tree(params, preset="precise", mesh=None, prefix=()):
    """:func:`pack_weights_int8` without the statistics, so it also runs
    under a trace: packs the projection leaves of ``params``, whose paths
    are relative to ``prefix`` (a policy keys projections by full path)."""
    policy = preset if hasattr(preset, "config_for") else None
    cfg0 = None
    if policy is None:
        if isinstance(preset, str):
            if preset not in PRESETS:
                raise ValueError(
                    f"unknown quant preset {preset!r}: valid presets are "
                    f"{sorted(PRESETS)}; pass a repro.policy.DSBPPolicy for "
                    f"per-layer configs (serving then runs with "
                    f"quant='policy')")
            cfg0 = PRESETS[preset]
        else:
            cfg0 = preset

    def pack(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        if name not in PROJ_NAMES or getattr(leaf, "ndim", 0) < 2:
            return leaf
        if policy is not None:
            cfg = policy.config_for("/".join(
                [*prefix, *(key_entry_str(p) for p in path)]))
            if cfg is None:
                return leaf
        else:
            cfg = cfg0
        if leaf.shape[-2] < cfg.weight_cfg.group_size:
            return leaf
        return (pack_weights_sharded(leaf, cfg, mesh) if mesh is not None
                else pack_weights(leaf, cfg))

    packed = jax.tree_util.tree_map_with_path(pack, params)
    # one device: MoE gate and up experts become one (E, d, 2f) container,
    # the grouped serving GEMM's operand (models/moe.py); a mesh keeps the
    # two for the sharding rules, which bind to their names
    return _fuse_expert_gate_up(packed) if mesh is None else packed


def _fuse_expert_gate_up(tree):
    from repro.models.moe import fuse_gate_up

    if isinstance(tree, list):
        return [_fuse_expert_gate_up(t) for t in tree]
    if not isinstance(tree, dict):
        return tree
    w1, w3 = tree.get("w1"), tree.get("w3")
    if ("router" in tree and isinstance(w1, PackedDSBPWeight)
            and isinstance(w3, PackedDSBPWeight) and w1.cfg == w3.cfg):
        rest = {k: v for k, v in tree.items() if k not in ("w1", "w3")}
        return {**rest, "w13": fuse_gate_up(w1, w3)}
    return {k: _fuse_expert_gate_up(v) for k, v in tree.items()}


def _pack_stats(packed) -> dict:
    is_pw = lambda x: isinstance(x, PackedDSBPWeight)
    bits_sum, groups, layers = 0.0, 0, 0
    for leaf in jax.tree.leaves(packed, is_leaf=is_pw):
        if is_pw(leaf):
            bits_sum += float(jnp.sum(leaf.bits.astype(jnp.int32) + 1))
            groups += int(np.prod(leaf.bits.shape))
            layers += 1
    return {"avg_w_bits": bits_sum / max(groups, 1), "layers_packed": layers}


def sample_tokens(logits, cfg: ArchConfig, temperature: float = 0.0,
                  rng=None):
    """THE token-selection implementation: greedy argmax (temperature 0) or
    categorical sampling over (possibly audio-codebook-stacked) padded-vocab
    logits.  ``logits``: (B, V).  Shared by ``Engine.generate``,
    ``Engine.serve`` and the speculative verify loop, so every path commits
    exactly the same greedy choices."""
    if cfg.frontend == "audio_codebooks":
        logits = logits.reshape(
            logits.shape[0], cfg.n_codebooks, cfg.padded_vocab_size)
    if temperature <= 0:
        tok = jnp.argmax(logits, axis=-1)
    else:
        tok = jax.random.categorical(rng, logits / temperature, axis=-1)
    if cfg.frontend == "audio_codebooks":
        return tok.reshape(tok.shape[0], -1)
    return tok


def _cache_insert(pool, src, rows, slots, kv_mode: str = "scatter"):
    """THE host-side cache-row insert every admission path goes through:
    copy ``src`` batch rows ``rows`` into pool lane ``slots`` in ONE pass
    over the pool (a per-request loop would reallocate the full multi-layer
    pool once per admission).  Unit-stack leaves carry batch at axis 1,
    tail leaves at axis 0 — ONE path-aware rule instead of the old dual
    tree.map branches.

    ``kv_mode`` says what KV leaves mean (everything else always scatters):
      * 'scatter' — dense engine: KV rows scatter like state rows.
      * 'src'     — paged admission: KV leaves are the shared block pools,
                    already row-written by the block-table scatter
                    (models.blocks.write_kv_blocks / fill_kv_cache_paged —
                    the device-side helper chunked prefill and spec
                    rollback also write through); take them from ``src``.
      * 'pool'    — paged chunk-lane state reset: keep the pool's KV
                    untouched, scatter only the recurrent lane states.
    """
    rows = jnp.asarray(rows, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)

    def ins(path, p, s):
        names = [key_entry_str(e) for e in path]
        # KV leaves are float k/v arrays or the qm/scale children of packed
        # ones (repro.kvq.is_kv_leaf_path — inlined on names we already have)
        is_kv = names[-1] in ("k", "v") or (
            names[-1] in ("qm", "scale")
            and len(names) >= 2 and names[-2] in ("k", "v"))
        if kv_mode != "scatter" and is_kv:
            return s if kv_mode == "src" else p
        if "units" in names:  # stacked (R, B, ...): batch is axis 1
            return p.at[:, slots].set(s[:, rows].astype(p.dtype))
        return p.at[slots].set(s[rows].astype(p.dtype))

    return jax.tree_util.tree_map_with_path(ins, pool, src)


class Engine:
    """Length-aware continuous-batching server over M.prefill / M.decode_step.

    Two entry points:

    * :meth:`generate` — one batch in, ``(B, n_new)`` out.  Ragged prompts
      are supported via ``lengths``; every row's generation is identical to
      serving it alone at batch size 1.
    * :meth:`serve` — a queue of :class:`Request` through a fixed pool of
      ``batch_size`` slots: freed slots (EOS or token budget) are refilled
      from the queue mid-flight; one jitted, cache-donating decode step
      advances the whole pool per token.

    With ``cfg.quant`` set and ``scfg.pack`` (the default), weights are
    packed once here and every subsequent prefill/decode consumes the int8
    representation directly — generations are bit-identical to serving the
    raw weights through the same preset (which re-quantizes per call), see
    tests/test_packed.py.
    """

    def __init__(self, params, cfg: ArchConfig, scfg: ServeConfig):
        preset = scfg.pack_preset if scfg.pack_preset is not None else cfg.quant
        # a DSBPPolicy pack spec flips serving into the per-layer 'policy'
        # quant mode: each packed container executes under its own embedded
        # config (models/layers.Quant.cfg_for, DESIGN.md §9)
        if hasattr(preset, "config_for") or (
                cfg.quant == "policy" and tree_is_packed(params)):
            cfg = cfg.replace(quant="policy")
        self.mesh = self._build_mesh(scfg)
        # serving default: the fused one-pass kernel (DESIGN.md §8) — its
        # shard_map form under a mesh (§11), unless the arch config or
        # ServeConfig pins a method explicitly.  Token parity with
        # 'dsbp_kernel' / 'dsbp_ref' (and 1-device vs mesh) is asserted in
        # tests/test_serving.py + tests/test_sharded_serving.py, so the
        # swap can never change served tokens.
        if cfg.quant is not None and (scfg.quant_method or cfg.quant_method) is None:
            cfg = cfg.replace(quant_method=(
                "dsbp_fused_sharded" if self.mesh is not None else "dsbp_fused"))
        elif scfg.quant_method is not None:
            cfg = cfg.replace(quant_method=scfg.quant_method)
        self.cfg = cfg
        self.scfg = scfg
        # device-scaled slot pool (§11): one mesh carries
        # mesh.size * per_device_batch_size concurrent slots
        self.pool_size = scfg.batch_size
        if self.mesh is not None and scfg.per_device_batch_size:
            self.pool_size = self.mesh.size * scfg.per_device_batch_size
        self.pack_report = None
        self.last_stats: dict | None = None
        # --- DSBP-quantized KV cache (DESIGN.md §14) ---
        # resolved once: None, a KVQuantConfig, or a per-entry mapping —
        # threaded into EVERY cache construction site (prefill, dense pool,
        # paged pool, chunk-lane reset) so all trees share one structure
        self.kv_spec = self._norm_kv(scfg)
        # --- robustness layer (DESIGN.md §13) ---
        self._guard = self._norm_guard(scfg.numeric_guard)
        if self._guard == "fallback" and scfg.spec_k:
            raise ValueError(
                "numeric_guard='fallback' retries a decode step through the "
                "reference path, but a speculative round commits its tokens "
                "inside one jit and cannot be re-run — use 'quarantine' or "
                "'fail-fast' with spec_k")
        self._cancel_pending: set = set()
        # one jitted all-finite reduction per guarded step: B bools cross
        # the host boundary, never the logits
        self._finite = (jax.jit(lambda lg: jnp.all(
            jnp.isfinite(lg.astype(jnp.float32)),
            axis=tuple(range(1, lg.ndim)))) if self._guard else None)
        self._ref_decode_jit = None        # lazy 'fallback' retry paths
        self._ref_decode_paged_jit = None
        self._last_alloc = None            # post-serve conservation checks
        self._last_prefix = None
        # --- observability (DESIGN.md §15) ---
        # one recorder for both schedulers: lifecycle spans, the metrics
        # registry, and guard-trip health telemetry.  Disabled it is a
        # bag of no-ops, so every hook below costs one attribute test.
        self.obs = ServeRecorder(enabled=scfg.observe,
                                 max_events=scfg.obs_max_events)
        if scfg.pack and preset is not None and not tree_is_packed(params):
            if preset == "policy":
                raise ValueError(
                    "cfg.quant='policy' needs weights already packed under a "
                    "DSBPPolicy, or the policy itself via "
                    "ServeConfig.pack_preset")
            raw_nbytes = packed_nbytes(params)
            params, stats = pack_weights_int8(params, preset, mesh=self.mesh)
            self.pack_report = {
                "preset": (f"policy[{len(preset)} layers]"
                           if hasattr(preset, "config_for") else preset),
                "raw_nbytes": raw_nbytes,
                "packed_nbytes": packed_nbytes(params),
                "avg_w_bits": stats["avg_w_bits"],
                "layers_packed": stats["layers_packed"],
            }
        if self.mesh is not None:
            # compute-layout placement: every container shard lives exactly
            # where its shard_map GEMM consumes it — zero weight movement
            # per decode step (parallel/sharding.serve_pspecs)
            from repro.parallel import sharding as SH

            params = jax.device_put(
                params, SH.named(self.mesh, SH.serve_pspecs(params, self.mesh)))
        self.params = params
        self._score_jit = None  # built lazily by score_continuations
        # donate the cache: KV buffers update in place every step instead of
        # being copied (tests/test_serving.py asserts the aliasing)

        def _decode_fn(p, tok, cache, pos):
            with self._trace_ctx():
                return M.decode_step(p, tok, cache, pos, cfg)

        # 'fallback' is the ONE mode that cannot donate: the retry re-runs
        # the step from the pre-step cache, which donation would invalidate
        self._decode = jax.jit(
            _decode_fn,
            donate_argnums=(() if self._guard == "fallback" else (2,)))
        # jitted sharded-in/sharded-out prefill (mesh only: the 1-device
        # engine keeps its eager prefill path unchanged)
        self._prefill = None
        if self.mesh is not None:
            def _prefill_fn(p, toks, lens):
                with self._trace_ctx():
                    return M.prefill(p, {"tokens": toks}, cfg,
                                     max_len=scfg.max_len, lengths=lens,
                                     kv=self.kv_spec)

            self._prefill = jax.jit(_prefill_fn)
        self._spec = None
        self.spec_report = None
        if scfg.spec_k:
            if scfg.temperature > 0:
                raise ValueError(
                    "speculative serving uses greedy token-match acceptance; "
                    "set temperature=0 (temperature sampling acceptance is "
                    "not implemented)")
            if cfg.window and 0 < cfg.window <= scfg.spec_k:
                raise ValueError(
                    f"spec_k={scfg.spec_k} needs spec_k+1 <= window "
                    f"({cfg.window}): a verify pass must not wrap its own "
                    f"tokens around the SWA ring cache")
            from repro.spec.decode import build_spec_round  # local: optional

            _round = build_spec_round(cfg, scfg.spec_k, scfg.spec_draft_bits,
                                      scfg.spec_draft_method,
                                      guard=self._guard is not None,
                                      kv_draft_bits=scfg.kv_draft_bits)

            def _spec_fn(p, cache, tok, pos):
                # the whole round — draft, verify, accept, rollback — traces
                # under the mesh context, so every projection of both the
                # draft and target forwards runs the sharded fused GEMM
                with self._trace_ctx():
                    return _round(p, cache, tok, pos)

            self._spec = jax.jit(_spec_fn, donate_argnums=(1,))
            # the draft view is derived inside the jitted round — no second
            # weight tree is ever stored (asserted in tests/test_spec.py)
            self.spec_report = {
                "spec_k": scfg.spec_k,
                "draft_bits": scfg.spec_draft_bits,
                "draft_method": scfg.spec_draft_method,
                "kv_draft_bits": scfg.kv_draft_bits,
                "extra_weight_nbytes": 0,
            }
        if scfg.paged:
            self._init_paged()

    # ------------------------------------------------------------------
    # paged KV cache plumbing (DESIGN.md §12)
    # ------------------------------------------------------------------

    def _init_paged(self):
        from repro.models import blocks as MB
        from repro.serve import blocks as SB

        cfg, scfg = self.cfg, self.scfg
        bs = int(scfg.kv_block_size)
        if bs < 1:
            raise ValueError(f"kv_block_size must be >= 1, got {bs}")
        kinds = list(cfg.pattern) + list(cfg.tail)
        self._kv_scs = sorted({
            MB.cache_len(cfg, k, scfg.max_len)
            for k in kinds if MB.KIND_HAS_KV[k]})
        for s_c in self._kv_scs:
            if s_c % bs:
                raise ValueError(
                    f"kv_block_size {bs} must divide every KV cache length; "
                    f"layer S_c {s_c} (max_len {scfg.max_len}, window "
                    f"{cfg.window}) is not a multiple")
        # S_c of every KV layer, in stack order: what a decode step gathers
        self._kv_layer_scs = np.asarray(
            [MB.cache_len(cfg, k, scfg.max_len)
             for k in list(cfg.pattern) * cfg.n_units + list(cfg.tail)
             if MB.KIND_HAS_KV[k]], np.int64)
        s_max = self._kv_scs[-1] if self._kv_scs else 0
        # one table entry spans kv_block_size ring slots of EVERY KV layer
        self._table_width = max(SB.block_span(s_max, bs), 1)
        # blocks-per-lane the dense engine effectively pins per slot — the
        # default pool budget is batch_size dense slots' worth (+ scratch)
        self.kv_blocks = (int(scfg.kv_blocks) if scfg.kv_blocks is not None
                          else scfg.batch_size * SB.block_span(s_max, bs) + 1)
        if self._kv_scs and self.kv_blocks < 2:
            raise ValueError(f"kv_blocks must be >= 2, got {self.kv_blocks}")
        self.lanes = int(scfg.max_active or self.pool_size)
        # prefix sharing is sound only while NO KV layer has wrapped its
        # ring during prefill (a shared entry must hold pure prefix content
        # in every layer's pool at once), so prompts longer than the
        # smallest KV ring neither take nor register hits
        self._share_limit = self._kv_scs[0] if self._kv_scs else 0
        self._chunk_threshold = int(scfg.chunk_prefill_tokens
                                    or 4 * scfg.prefill_bucket)
        # chunk width: a verify pass must keep its ring slots distinct
        self._chunk_T = min(scfg.prefill_bucket,
                            *(self._kv_scs or [scfg.prefill_bucket]))
        cfg_, max_len = cfg, scfg.max_len
        # MoE steps also return their per-layer, per-expert routed rows
        xr = self._expert_rows = bool(cfg.n_experts)

        def _decode_paged_fn(p, tok, cache, table, pos, write_len):
            with self._trace_ctx():
                return M.decode_step_paged(p, tok, cache, table, pos,
                                           write_len, cfg_, max_len,
                                           expert_rows=xr)

        def _verify_paged_fn(p, tok, cache, table, pos):
            with self._trace_ctx():
                return M.verify_step_paged(p, tok, cache, table, pos, cfg_,
                                           max_len, expert_rows=xr)

        def _commit_paged_fn(cache, table, steps, keep, pos):
            with self._trace_ctx():
                return M.rollback_cache_paged(cache, table, steps, keep, pos,
                                              cfg_, max_len)

        def _prefill_paged_fn(p, toks, cache, table, lens, write_start):
            with self._trace_ctx():
                return M.prefill_paged(p, {"tokens": toks}, cache, table,
                                       cfg_, max_len, lengths=lens,
                                       write_start=write_start)

        self._decode_paged = jax.jit(
            _decode_paged_fn,
            donate_argnums=(() if self._guard == "fallback" else (2,)))
        self._verify_paged = jax.jit(_verify_paged_fn)
        self._commit_paged = jax.jit(_commit_paged_fn, donate_argnums=(0,))
        # eager on one device (mirrors the dense admission path); jitted
        # sharded-in/sharded-out under a mesh
        self._prefill_paged = (jax.jit(_prefill_paged_fn)
                               if self.mesh is not None else _prefill_paged_fn)
        self._spec_paged = None
        if scfg.spec_k:
            from repro.spec.decode import build_spec_round_paged

            _round = build_spec_round_paged(
                cfg, scfg.spec_k, scfg.spec_draft_bits,
                scfg.spec_draft_method, max_len,
                guard=self._guard is not None,
                kv_draft_bits=scfg.kv_draft_bits)

            def _spec_paged_fn(p, cache, table, tok, pos, live):
                with self._trace_ctx():
                    return _round(p, cache, table, tok, pos, live)

            self._spec_paged = jax.jit(_spec_paged_fn, donate_argnums=(1,))

    # ------------------------------------------------------------------
    # multi-device plumbing (DESIGN.md §11)
    # ------------------------------------------------------------------

    @staticmethod
    def _build_mesh(scfg: ServeConfig):
        if scfg.mesh_shape is None:
            return None
        shape = tuple(int(s) for s in scfg.mesh_shape)
        if len(shape) != len(scfg.mesh_axes):
            raise ValueError(
                f"mesh_shape {shape} needs one size per axis name "
                f"{scfg.mesh_axes}")
        n = int(np.prod(shape))
        if n > jax.device_count():
            raise ValueError(
                f"mesh_shape {shape} needs {n} devices; "
                f"{jax.device_count()} available (simulate CPU devices with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        from repro.parallel.sharding import make_mesh

        return make_mesh(shape, scfg.mesh_axes, devices=jax.devices()[:n])

    def _trace_ctx(self):
        """Sharding context entered while tracing every model call: the
        'dsbp_fused_sharded' method reads it (parallel.context.active_ctx)
        to pick each projection's shard_map specs.  gather=False — the
        shard_map in_specs fully determine weight movement, and weights
        already live at their compute layout."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.parallel import context as PC
        from repro.parallel import sharding as SH

        return PC.sharding_ctx(self.mesh, SH.batch_axes(self.mesh),
                               gather=False)

    def _shard_cache(self, pool, batch_size: int, paged: bool = False):
        """Place a fresh cache pool batch-sharded over the mesh
        (parallel.sharding.cache_pspecs); identity on one device."""
        if self.mesh is None:
            return pool
        from repro.parallel import sharding as SH

        return jax.device_put(
            pool, SH.named(self.mesh,
                           SH.cache_pspecs(pool, self.mesh, batch_size,
                                           paged=paged)))

    # ------------------------------------------------------------------
    # robustness layer: lifecycle control, numeric guards (DESIGN.md §13)
    # ------------------------------------------------------------------

    @staticmethod
    def _norm_guard(policy):
        if policy in (None, "off"):
            return None
        if policy == "quarantine-lane":  # the ISSUE/CLI spelling
            return "quarantine"
        if policy not in _GUARD_POLICIES:
            raise ValueError(
                f"unknown numeric_guard {policy!r}: pick one of "
                f"{sorted(_GUARD_POLICIES)} (or 'off')")
        return policy

    @staticmethod
    def _norm_kv(scfg: ServeConfig):
        """Resolve ``kv_quant``/``kv_bits`` to None, a KVQuantConfig, or a
        per-entry mapping of resolved configs; validate ``kv_draft_bits``.
        Spec errors surface at construction, never mid-serve."""
        from collections.abc import Mapping

        from repro.kvq import KV_MAX_BITS, KV_MIN_BITS, resolve_kv_spec

        kv = scfg.kv_quant
        if scfg.kv_bits is not None:
            if kv is not None:
                raise ValueError(
                    "kv_bits is a uniform shorthand for kv_quant: set one, "
                    "not both")
            kv = int(scfg.kv_bits)
        # a DSBPPolicy with KV pricing: use its per-entry mapping (plus
        # kv_default for entries the mapping does not name); a policy
        # without a KV side serves a float cache
        if hasattr(kv, "kv_layers"):
            pol = kv
            kv = dict(getattr(pol, "kv_layers", None) or {})
            kv.setdefault("default", getattr(pol, "kv_default", None))
            if not any(v is not None for v in kv.values()):
                kv = None
        if isinstance(kv, Mapping):
            kv = {str(k): resolve_kv_spec(v) for k, v in kv.items()}
        else:
            kv = resolve_kv_spec(kv)
        if scfg.kv_draft_bits is not None:
            if kv is None:
                raise ValueError(
                    "kv_draft_bits needs a packed KV cache: set kv_quant "
                    "(or kv_bits) as well")
            db = int(scfg.kv_draft_bits)
            if not KV_MIN_BITS <= db <= KV_MAX_BITS:
                raise ValueError(
                    f"kv_draft_bits must be in [{KV_MIN_BITS}, "
                    f"{KV_MAX_BITS}], got {db}")
        return kv

    def cancel(self, uid) -> None:
        """Request cancellation of ``uid``, queued or mid-generation: the
        scheduler frees its slot/lane and blocks at the next iteration
        boundary, keeps whatever tokens were already emitted, and records
        status 'cancelled'.  Unknown or already-finished uids are ignored
        (cancellation is idempotent)."""
        self._cancel_pending.add(uid)

    @staticmethod
    def _robust_stats() -> dict:
        return {"cancelled": 0, "deadline_expired": 0, "quarantined": 0,
                "numeric_faults": 0, "guard_checks": 0, "fallback_steps": 0,
                "preemptions": 0, "resumed": 0, "invariant_checks": 0}

    def _build_queue(self, requests, max_new_tokens: int) -> deque:
        """Validated admission queue: normalized Requests, unique uids,
        max_len feasibility, stable highest-priority-first order."""
        reqs = [self._norm_request(r, i, max_new_tokens)
                for i, r in enumerate(requests)]
        if len({r.uid for r in reqs}) != len(reqs):
            raise ValueError("request uids must be unique (results key on uid)")
        headroom = self.scfg.spec_k
        for r in reqs:
            if len(r.tokens) + r.max_new_tokens + headroom > self.scfg.max_len:
                raise ValueError(
                    f"request {r.uid!r}: prompt {len(r.tokens)} + budget "
                    f"{r.max_new_tokens}"
                    f"{f' + spec_k {headroom}' if headroom else ''}"
                    f" exceeds max_len {self.scfg.max_len}")
        return deque(sorted(reqs, key=lambda r: -r.priority))

    def _drain_control(self, ctl: _ServeControl, queue, live) -> None:
        """Top-of-iteration control sweep: apply pending cancellations
        (``Engine.cancel`` + the fault plan's schedule), then expire
        deadlines.  ``live`` maps uid -> (Request, release_fn); release_fn
        returns the slot/lane AND every block it holds atomically."""
        cancels = list(self._cancel_pending)
        self._cancel_pending.clear()
        if ctl.faults is not None:
            cancels += list(ctl.faults.cancels_at(ctl.step))
        for uid in cancels:
            if uid in live:
                _, release = live.pop(uid)
                release()
                ctl.status[uid] = "cancelled"
                ctl.stats["cancelled"] += 1
                ctl.out.setdefault(uid, [])
                self.obs.terminal(uid, "cancelled", ctl.step,
                                  tokens=len(ctl.out[uid]))
            elif any(r.uid == uid for r in queue):
                rest = [r for r in queue if r.uid != uid]
                queue.clear()
                queue.extend(rest)
                ctl.status[uid] = "cancelled"
                ctl.stats["cancelled"] += 1
                ctl.out.setdefault(uid, [])
                self.obs.terminal(uid, "cancelled", ctl.step,
                                  tokens=len(ctl.out[uid]))
        for uid, (r, release) in list(live.items()):
            if r.deadline_steps is None:
                continue
            if ctl.step - ctl.admit_step.get(uid, ctl.step) >= r.deadline_steps:
                live.pop(uid)
                release()
                ctl.status[uid] = "deadline"
                ctl.stats["deadline_expired"] += 1
                ctl.out.setdefault(uid, [])
                self.obs.terminal(uid, "deadline", ctl.step,
                                  tokens=len(ctl.out[uid]))

    def _apply_guard(self, logits, occ, uid_of, ctl: _ServeControl, *,
                     retry: bool = False, inject: bool = True, cache=None):
        """Fault injection + numeric guard over one step's sampling logits.
        ``occ`` are the row/lane ids actually serving; ``uid_of(i)`` names
        them for diagnostics.  Returns ``(logits, bad_ids)`` — the caller
        applies its policy action (quarantine / fallback retry) to
        ``bad_ids``.  'fail-fast' raises here.  ``cache`` (the post-step
        KV tree) lets the recorder attribute the trip to the cache entry a
        real numeric fault poisoned (DESIGN.md §15)."""
        faults = ctl.faults
        if faults is not None and inject:
            logits = faults.corrupt_logits(logits, occ, retry=retry)
        if self._guard is None:
            return logits, []
        with span("serve.guard_wait"):
            finite = np.asarray(self._finite(jnp.asarray(logits)))
        ctl.stats["guard_checks"] += 1
        bad = [i for i in occ if not finite[i]]
        if bad:
            ctl.stats["numeric_faults"] += len(bad)
            # telemetry BEFORE the policy action, while the cache still
            # holds whatever the fault wrote
            self.obs.guard_trip([uid_of(i) for i in bad], ctl.step,
                                cache=cache)
            if self._guard == "fail-fast":
                from repro.serve.faults import NumericFault

                raise NumericFault([uid_of(i) for i in bad], ctl.step)
        return logits, bad

    def _quarantine(self, uid, ctl: _ServeControl, release) -> None:
        release()
        ctl.status[uid] = "quarantined"
        ctl.stats["quarantined"] += 1
        ctl.out.setdefault(uid, [])
        self.obs.terminal(uid, "quarantined", ctl.step,
                          tokens=len(ctl.out[uid]))

    def _ref_decode(self):
        """Lazily-jitted dense decode through the reference quant path (the
        'fallback' guard's retry; never donates — the caller re-feeds the
        pre-step cache)."""
        if self._ref_decode_jit is None:
            rcfg = (self.cfg.replace(quant_method="dsbp_ref")
                    if self.cfg.quant is not None else self.cfg)

            def _fn(p, tok, cache, pos):
                with self._trace_ctx():
                    return M.decode_step(p, tok, cache, pos, rcfg)

            self._ref_decode_jit = jax.jit(_fn)
        return self._ref_decode_jit

    def _ref_decode_paged(self):
        if self._ref_decode_paged_jit is None:
            rcfg = (self.cfg.replace(quant_method="dsbp_ref")
                    if self.cfg.quant is not None else self.cfg)
            max_len = self.scfg.max_len

            def _fn(p, tok, cache, table, pos, write_len):
                with self._trace_ctx():
                    return M.decode_step_paged(p, tok, cache, table, pos,
                                               write_len, rcfg, max_len)

            self._ref_decode_paged_jit = jax.jit(_fn)
        return self._ref_decode_paged_jit

    def _finish(self, ctl: _ServeControl, uid) -> None:
        """Terminal bookkeeping for a request that completed its stream:
        'ok', or 'preempted' when it survived >= 1 eviction on the way."""
        ctl.status[uid] = "preempted" if ctl.preempts.get(uid) else "ok"
        self.obs.terminal(uid, ctl.status[uid], ctl.step,
                          tokens=len(ctl.out.get(uid) or ()))

    @staticmethod
    def _requeue(queue, r: Request) -> None:
        """Re-insert a preempted request respecting priority order, ahead
        of equal-priority waiters (it was admitted first — resume ASAP
        minimizes recompute staleness without starving higher priorities)."""
        idx = 0
        for idx, q in enumerate(queue):
            if q.priority <= r.priority:
                break
        else:
            idx = len(queue)
        queue.insert(idx, r)

    # ------------------------------------------------------------------
    # batch API
    # ------------------------------------------------------------------

    def generate(self, prompts: np.ndarray, n_new: int,
                 extra: dict | None = None, lengths=None):
        """prompts: (B, L) (or (B, L, K) audio) token ids, right-padded when
        ragged; ``lengths`` (B,) gives each row's true prompt length.
        Greedy/temp sampling of ``n_new`` tokens.  Returns (B, n_new)."""
        cfg, scfg = self.cfg, self.scfg
        batch = {"tokens": jnp.asarray(prompts)}
        if extra:
            batch.update({k: jnp.asarray(v) for k, v in extra.items()})
        if lengths is not None:
            lengths = jnp.asarray(lengths, jnp.int32)
            if cfg.frontend == "vlm_patches":  # embedded positions incl. image
                lengths = lengths + batch["image_embeds"].shape[1]
        with self._trace_ctx():
            logits, cache, length = M.prefill(
                self.params, batch, cfg, max_len=scfg.max_len,
                lengths=lengths, kv=self.kv_spec,
            )
        b = logits.shape[0]
        pos = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
        rng = jax.random.PRNGKey(scfg.seed)
        outs = []
        tok, rng = self._sample_next(logits[:, -1], rng)
        for _ in range(n_new):
            outs.append(np.asarray(tok))
            step_tok = {"tokens": tok[:, None]}
            if cfg.frontend == "audio_codebooks":
                step_tok = {"tokens": tok.reshape(-1, 1, cfg.n_codebooks)}
            logits, cache = self._decode(self.params, step_tok, cache, pos)
            pos = pos + 1
            tok, rng = self._sample_next(logits[:, -1], rng)
        return np.stack(outs, axis=1)

    # ------------------------------------------------------------------
    # likelihood scoring (multiple-choice eval, repro.eval.harness)
    # ------------------------------------------------------------------

    def score_continuations(self, sequences, prompt_lens) -> np.ndarray:
        """Sum of continuation log-probs under the engine's weights.

        ``sequences`` — list of 1-D token arrays (context + continuation);
        ``prompt_lens`` — per-sequence context length.  Returns (B,) f32:
        Σ_p log P(tok_p | tok_<p) over p in [prompt_len, len).  Sequences
        right-pad to a shared bucketed length and run one ``M.forward``
        with MoE capacity dropping disabled, so each row's score equals
        scoring it alone at batch size 1 (batch invariance,
        tests/test_policy.py) — the contract the eval harness and the
        policy autotuner rely on.  Each call is one ``score.call`` span:
        ``score.prepare`` (padding, host arrays), ``score.run`` (dispatch)
        and ``score.wait`` (the device sync on the scores).
        """
        cfg, scfg = self.cfg, self.scfg
        if cfg.frontend in ("audio_codebooks", "vlm_patches"):
            raise NotImplementedError(
                "score_continuations() takes plain token sequences; "
                f"unsupported for the {cfg.frontend} frontend")
        with span("score.call"):
            with span("score.prepare"):
                seqs = [np.asarray(s, np.int64) for s in sequences]
                lens = np.asarray([len(s) for s in seqs], np.int32)
                plens = np.asarray(prompt_lens, np.int32)
                if np.any(plens >= lens):
                    raise ValueError(
                        "every sequence needs >= 1 continuation token")
                bucket = scfg.prefill_bucket
                L = max(-(-int(lens.max()) // bucket) * bucket, bucket)
                toks = np.zeros((len(seqs), L), np.int64)
                for i, s in enumerate(seqs):
                    toks[i, : lens[i]] = s
                args = (jnp.asarray(toks), jnp.asarray(plens),
                        jnp.asarray(lens))
            if self._score_jit is None:
                def _score(p, toks, plens, slens):
                    logits = M.forward(p, {"tokens": toks}, cfg, no_drop=True)
                    with jax.named_scope("lm_head"):
                        logp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                                  axis=-1)
                        lp = jnp.take_along_axis(logp[:, :-1],
                                                 toks[:, 1:, None],
                                                 axis=-1)[..., 0]
                    pos = jnp.arange(1, toks.shape[1])
                    mask = ((pos[None] >= plens[:, None])
                            & (pos[None] < slens[:, None]))
                    return jnp.sum(lp * mask, axis=1)

                self._score_jit = jax.jit(_score)
            with span("score.run"):
                scores = self._score_jit(self.params, *args)
            with span("score.wait"):
                return np.asarray(scores)

    # ------------------------------------------------------------------
    # continuous batching
    # ------------------------------------------------------------------

    def serve(self, requests, max_new_tokens: int = 32, faults=None):
        """Run a queue of requests through the slot pool; returns
        {uid: np.ndarray(generated token ids)} and records scheduler stats
        in ``self.last_stats`` (decode_steps, occupancy, admissions,
        per-request lifecycle states under ``request_status``, ...).

        ``requests`` items are :class:`Request` or plain token sequences
        (uid = queue index, budget = ``max_new_tokens``).  ``faults`` takes
        a :class:`repro.serve.faults.FaultPlan` — a deterministic schedule
        of injected allocator failures / NaNs / cancellations (DESIGN.md
        §13); invariant checks then run after every scheduler iteration."""
        cfg, scfg = self.cfg, self.scfg
        if cfg.frontend in ("audio_codebooks", "vlm_patches"):
            raise NotImplementedError(
                "serve() schedules plain token prompts; use generate() for "
                f"the {cfg.frontend} frontend")
        if scfg.paged:
            return self._serve_paged(requests, max_new_tokens, faults)
        queue = self._build_queue(requests, max_new_tokens)
        nreq = len(queue)
        self.obs.serve_start("dense", [(r.uid, len(r.tokens))
                                       for r in queue])
        if faults is not None:
            faults.reset()
            faults.observer = self.obs.fault_injected
        B = self.pool_size
        pool = self._shard_cache(
            M.init_cache(cfg, B, scfg.max_len, kv=self.kv_spec), B)
        # KV HBM one slot's token pins (stats): actual leaf dtypes — int8
        # mantissas + f32 scales under kv_quant, the model dtype otherwise
        kv_bpt = kv_cache_nbytes(pool) / max(B * scfg.max_len, 1)
        active: list[Request | None] = [None] * B
        tok = np.zeros(B, np.int64)        # last sampled token per slot
        pos = np.zeros(B, np.int32)        # next absolute position per slot
        rng = jax.random.PRNGKey(scfg.seed)
        stats = {"decode_steps": 0, "occupied_lanes": 0, "admissions": 0,
                 "prefill_tokens": 0, "decode_tokens": 0,
                 # wall time of the decode/speculation phase alone (admission
                 # prefills excluded), so decode throughput is measurable
                 # independently of prefill shapes: decode_tps in last_stats
                 "decode_time_s": 0.0, **self._robust_stats()}
        ctl = _ServeControl(stats=stats, out={},
                            status={r.uid: "queued" for r in queue},
                            faults=faults)
        if self._spec is not None:
            stats.update(
                spec_rounds=0, draft_tokens=0,
                # accepted-length histogram over occupied lanes: index j =
                # rounds that committed j tokens (1..spec_k+1)
                accepted_hist=np.zeros(scfg.spec_k + 2, np.int64),
            )
            slot_accepted = np.zeros(B, np.int64)
            slot_rounds = np.zeros(B, np.int64)
        completed = False
        try:
            while queue or any(s is not None for s in active):
                with span("serve.iter", step=ctl.step):
                    with span("serve.control"):
                        live = {active[i].uid:
                                (active[i],
                                 functools.partial(active.__setitem__, i,
                                                   None))
                                for i in range(B) if active[i] is not None}
                        self._drain_control(ctl, queue, live)
                    free = [i for i in range(B) if active[i] is None]
                    if queue and free:
                        with span("serve.admit"):
                            pool, rng = self._admit(pool, queue, free, active,
                                                    tok, pos, ctl, rng)
                    if not any(s is not None for s in active):
                        ctl.step += 1
                        continue  # every admitted request finished at token 1
                    stats["decode_steps"] += 1
                    n_occ = sum(s is not None for s in active)
                    stats["occupied_lanes"] += n_occ
                    t_step = time.perf_counter()
                    if self._spec is not None:
                        pool = self._spec_advance(pool, active, tok, pos, ctl,
                                                  slot_accepted, slot_rounds)
                        dt = time.perf_counter() - t_step
                        stats["decode_time_s"] += dt
                        self.obs.decode_step(ctl.step, n_occ, dt)
                        ctl.step += 1
                        continue
                    occ = [i for i in range(B) if active[i] is not None]
                    prev = pool if self._guard == "fallback" else None
                    with span("serve.decode"):
                        step_toks = {"tokens": jnp.asarray(tok)[:, None]}
                        logits, pool = self._decode(
                            self.params, step_toks, pool, jnp.asarray(pos))
                        last, bad = self._apply_guard(
                            logits[:, -1], occ, lambda i: active[i].uid, ctl,
                            cache=pool)
                        if bad and self._guard == "fallback":
                            # retry the whole step through the reference quant
                            # path from the (undonated) pre-step cache — a
                            # fused-kernel fault clears, a persistent one falls
                            # to quarantine
                            stats["fallback_steps"] += 1
                            logits, pool = self._ref_decode()(
                                self.params, step_toks, prev,
                                jnp.asarray(pos))
                            last, bad = self._apply_guard(
                                logits[:, -1], occ, lambda i: active[i].uid,
                                ctl, retry=True, cache=pool)
                        for i in bad:
                            self._quarantine(
                                active[i].uid, ctl,
                                functools.partial(active.__setitem__, i, None))
                        nxt, rng = self._sample_next(jnp.asarray(last), rng)
                    with span("serve.wait"):  # the step's device sync
                        nxt = np.asarray(nxt)
                    dt = time.perf_counter() - t_step
                    stats["decode_time_s"] += dt
                    self.obs.decode_step(ctl.step, n_occ, dt)
                    with span("serve.tokens"):
                        for i in range(B):
                            r = active[i]
                            if r is None:
                                continue  # idle lane: output ignored
                            pos[i] += 1
                            t = int(nxt[i])
                            ctl.out[r.uid].append(t)
                            tok[i] = t
                            stats["decode_tokens"] += 1
                            if self._done(t, ctl.out[r.uid], r):
                                active[i] = None  # freed for admission
                                self._finish(ctl, r.uid)
                    ctl.step += 1
            completed = True
        finally:
            # last_stats lands even when an exception unwinds mid-loop —
            # a failed serve still reports what it did ('completed' False)
            self.last_stats = dict(
                stats,
                requests=nreq,
                completed=completed,
                request_status=dict(ctl.status),
                occupancy=stats["occupied_lanes"]
                / max(stats["decode_steps"] * B, 1),
                decode_tps=stats["decode_tokens"]
                / max(stats["decode_time_s"], 1e-9),
                kv_bytes_per_token=kv_bpt,
                kv_packed=tree_has_packed_kv(pool),
            )
            if self._spec is not None:
                self._spec_summary(stats, slot_accepted, slot_rounds)
            self.obs.serve_end(self.last_stats)
        for uid in ctl.status:  # every uid reports, however it ended
            ctl.out.setdefault(uid, [])
        return {uid: np.asarray(toks, np.int64)
                for uid, toks in ctl.out.items()}

    def _spec_summary(self, stats, slot_accepted=None,
                      slot_rounds=None) -> None:
        """Speculation epilogue shared by both schedulers: fold the
        accepted-length histogram into ``last_stats`` (dense additionally
        reports per-slot means) and mirror it into the recorder."""
        from repro.spec.decode import acceptance_summary

        self.last_stats.update(acceptance_summary(
            stats["accepted_hist"], self.scfg.spec_k,
            slot_accepted=slot_accepted, slot_rounds=slot_rounds))
        self.obs.spec_summary(self.last_stats)

    def _spec_advance(self, pool, active, tok, pos, ctl, slot_accepted,
                      slot_rounds):
        """One speculation round for the whole pool: draft -> verify ->
        accept -> rollback inside the jitted ``self._spec``, then commit the
        accepted greedy tokens per occupied slot (every committed token is
        the target model's own argmax — the non-speculative stream)."""
        stats = ctl.stats
        occ = [i for i, s in enumerate(active) if s is not None]
        res = self._spec(
            self.params, pool, jnp.asarray(tok), jnp.asarray(pos))
        if self._guard is not None:
            target, keep, pool, finite = res
            finite = np.asarray(finite)
        else:
            target, keep, pool = res
            finite = None
        target, keep = np.asarray(target), np.asarray(keep)
        if ctl.faults is not None:
            if finite is not None:
                finite = ctl.faults.corrupt_finite(finite, occ)
            keep = ctl.faults.clip_spec_keep(keep)
        if finite is not None:
            # guard the round BEFORE committing: a non-finite verify pass
            # quarantines its lane with the pre-round output intact
            stats["guard_checks"] += 1
            bad = [i for i in occ if not finite[i]]
            if bad:
                stats["numeric_faults"] += len(bad)
                self.obs.guard_trip([active[i].uid for i in bad], ctl.step,
                                    cache=pool)
                if self._guard == "fail-fast":
                    from repro.serve.faults import NumericFault

                    raise NumericFault([active[i].uid for i in bad], ctl.step)
                for i in bad:
                    self._quarantine(
                        active[i].uid, ctl,
                        functools.partial(active.__setitem__, i, None))
        stats["spec_rounds"] += 1
        stats["draft_tokens"] += self.scfg.spec_k * sum(
            s is not None for s in active)
        self.obs.spec_round(ctl.step, [int(keep[i]) for i, s
                                       in enumerate(active) if s is not None])
        for i in range(len(active)):
            r = active[i]
            if r is None:
                continue  # idle lane: rolled-back writes are overwritten at
                # the slot's next admission prefill
            kp = int(keep[i])
            stats["accepted_hist"][kp] += 1
            slot_accepted[i] += kp
            slot_rounds[i] += 1
            committed = 0
            for j in range(kp):
                t = int(target[i, j])
                ctl.out[r.uid].append(t)
                committed += 1
                stats["decode_tokens"] += 1
                if self._done(t, ctl.out[r.uid], r):
                    active[i] = None  # tokens past EOS/budget are dropped
                    self._finish(ctl, r.uid)
                    break
            pos[i] += committed
            tok[i] = int(target[i, committed - 1])
        return pool

    def _admit(self, pool, queue, free, active, tok, pos, ctl, rng):
        """Admit up to len(free) queued requests: one ragged group prefill
        (padded to a bucket multiple, per-row lengths), then copy each row's
        cache into its slot.  Returns (pool, advanced rng)."""
        scfg = self.scfg
        stats = ctl.stats
        group = [queue.popleft() for _ in range(min(len(free), len(queue)))]
        lens = np.asarray([len(r.tokens) for r in group], np.int32)
        for j, r in enumerate(group):
            self.obs.admitted(r.uid, ctl.step, prompt_len=int(lens[j]))
        bucket = scfg.prefill_bucket
        L = max(-(-int(lens.max()) // bucket) * bucket, bucket)
        toks = np.zeros((len(group), L), np.int64)
        for j, r in enumerate(group):
            toks[j, : lens[j]] = np.asarray(r.tokens)
        if self._prefill is not None:  # jitted sharded prefill (mesh)
            logits, cache, _ = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens, jnp.int32))
        else:
            logits, cache, _ = M.prefill(
                self.params, {"tokens": jnp.asarray(toks)}, self.cfg,
                max_len=scfg.max_len, lengths=lens, kv=self.kv_spec,
            )
        # admission guard: inject=False — the plan's NaN schedule targets
        # decode-phase calls only, but REAL non-finite prefill logits must
        # still never reach sampling ('fallback' degrades to quarantine
        # here: there is no cheap per-row prefill retry)
        last, badrows = self._apply_guard(
            logits[:, -1], list(range(len(group))),
            lambda j: group[j].uid, ctl, inject=False)
        first, rng = self._sample_next(jnp.asarray(last), rng)
        with span("serve.admit_wait"):
            first = np.asarray(first)
        stats["admissions"] += len(group)
        stats["prefill_tokens"] += int(lens.sum())
        badset = set(badrows)
        rows, slots = [], []
        for j, r in enumerate(group):
            if j in badset:
                self._quarantine(r.uid, ctl, lambda: None)
                continue
            t = int(first[j])
            ctl.out[r.uid] = [t]
            ctl.admit_step.setdefault(r.uid, ctl.step)
            self.obs.first_token(r.uid, ctl.step)
            if self._done(t, ctl.out[r.uid], r):
                self._finish(ctl, r.uid)
                continue  # finished at its first token: slot stays free
            slot = free.pop(0)
            rows.append(j)
            slots.append(slot)
            active[slot] = r
            tok[slot] = t
            pos[slot] = int(lens[j])
        if rows:
            pool = _cache_insert(pool, cache, rows, slots)
        return pool, rng

    # ------------------------------------------------------------------
    # paged serving: block tables, COW prefix sharing, chunked prefill
    # (DESIGN.md §12)
    # ------------------------------------------------------------------

    def _serve_paged(self, requests, max_new_tokens: int = 32, faults=None):
        """Paged twin of the dense serve loop: one physical block pool, one
        int32 block table per lane.  Per iteration: drain control events
        (cancellations, deadlines) -> admit (reserve blocks -> grouped short
        prefill / chunk-lane setup, preempting a strictly-lower-priority
        victim when reservation fails) -> COW-split shared blocks the step
        writes (preempting a victim when the split cannot be satisfied) ->
        ONE decode step over every decode lane -> one chunk step -> optional
        invariant check.  Token-for-token identical to the dense engine
        (tests/test_paged.py); preempt-resumes replay bit-exactly
        (tests/test_robustness.py).
        """
        from repro.serve import blocks as SB
        from repro.serve import faults as FA

        cfg, scfg = self.cfg, self.scfg
        queue = self._build_queue(requests, max_new_tokens)
        nreq = len(queue)
        headroom = scfg.spec_k
        B, bs = self.lanes, scfg.kv_block_size
        if self._kv_scs:
            # a reservation that exceeds the whole pool can NEVER succeed:
            # fail fast instead of deadlocking the admission loop
            for r in queue:
                blocks = SB.block_span(
                    min(len(r.tokens) + r.max_new_tokens + headroom,
                        self._kv_scs[-1]), bs)
                if blocks > self.kv_blocks - 1:
                    raise SB.BlockError(
                        f"request {r.uid!r} cannot be admitted even with an "
                        f"idle pool: its reservation ({blocks} blocks) exceeds "
                        f"kv_blocks={self.kv_blocks} ({self.kv_blocks - 1} "
                        f"usable)")
        self.obs.serve_start("paged", [(r.uid, len(r.tokens))
                                       for r in queue])
        if faults is not None:
            faults.reset()
            faults.observer = self.obs.fault_injected
        check = scfg.invariant_checks or faults is not None
        alloc = None
        if self._kv_scs:
            alloc = (faults.allocator(self.kv_blocks, bs)
                     if faults is not None
                     else SB.BlockAllocator(self.kv_blocks, bs))
        prefix = (SB.PrefixCache(alloc)
                  if alloc is not None and scfg.prefix_sharing else None)
        self._last_alloc, self._last_prefix = alloc, prefix
        nb_pool = self.kv_blocks if self._kv_scs else 1
        cache = self._shard_cache(
            M.init_paged_cache(cfg, B, nb_pool, bs, kv=self.kv_spec), B,
            paged=True)
        # bytes one table entry pins across every KV layer's pool (stats) —
        # summed from the ACTUAL cache leaves (is_kv_leaf_path walks float
        # k/v arrays AND the qm/scale children of packed ones), so the
        # report reflects int8+f32 packed bytes, not the model dtype
        blk_bytes = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
            if is_kv_leaf_path(path):
                blk_bytes += (leaf.size * leaf.dtype.itemsize) // nb_pool
        kv_row_bytes = blk_bytes // max(bs * len(self._kv_layer_scs), 1)
        tables = np.zeros((B, self._table_width), np.int32)
        lanes: list[dict | None] = [None] * B
        tok = np.zeros(B, np.int64)
        pos = np.zeros(B, np.int32)
        rng = jax.random.PRNGKey(scfg.seed)
        stats = {"decode_steps": 0, "occupied_lanes": 0, "admissions": 0,
                 "prefill_tokens": 0, "decode_tokens": 0, "decode_time_s": 0.0,
                 "cow_splits": 0, "chunk_steps": 0, "chunked_requests": 0,
                 # decode lanes always advance every iteration regardless of
                 # in-flight chunked prefills — 0 by construction, asserted
                 # by benchmarks/check_paged_gate.py
                 "stalled_decode_steps": 0,
                 "interleaved_decode_steps": 0, "max_concurrent": 0,
                 "shared_blocks_peak": 0, "admission_blocked": 0,
                 # KV rows the decode steps gathered (every lane's whole
                 # table, per KV layer) and the rows decoding lanes held
                 "kv_rows_gathered": 0, "kv_rows_live": 0,
                 **self._robust_stats()}
        if self._expert_rows:
            stats.update(expert_passes=0, expert_rows_real=0,
                         expert_rows_padded=0, experts_touched=0,
                         expert_rows_max=0)
        ctl = _ServeControl(stats=stats, out={},
                            status={r.uid: "queued" for r in queue},
                            faults=faults)
        if self._spec_paged is not None:
            stats.update(spec_rounds=0, draft_tokens=0,
                         accepted_hist=np.zeros(scfg.spec_k + 2, np.int64))
        idle_spins = 0
        completed = False
        try:
            while queue or any(l is not None for l in lanes):
                with span("serve.iter", step=ctl.step):
                    with span("serve.control"):
                        live = {lanes[i]["req"].uid:
                                (lanes[i]["req"],
                                 functools.partial(self._release_lane, i,
                                                   lanes, tables, alloc))
                                for i in range(B) if lanes[i] is not None}
                        self._drain_control(ctl, queue, live)
                    free = [i for i in range(B) if lanes[i] is None]
                    if queue and free:
                        with span("serve.admit"):
                            cache, rng = self._admit_paged(
                                cache, queue, free, lanes, tables, alloc,
                                prefix, tok, pos, ctl, rng)
                    dec = [i for i, l in enumerate(lanes)
                           if l is not None and l["phase"] == "decode"]
                    chk = [i for i, l in enumerate(lanes)
                           if l is not None and l["phase"] == "chunk"]
                    if not dec and not chk:
                        if queue:
                            # blocked admission with an idle pool: transient
                            # under fault injection / prefix evictions, but
                            # a pathological plan must terminate, not spin
                            idle_spins += 1
                            if idle_spins > 4 * self.kv_blocks + 64:
                                raise SB.BlockError(
                                    f"scheduler made no progress for "
                                    f"{idle_spins} iterations with an idle "
                                    f"pool: request {queue[0].uid!r} cannot "
                                    f"reserve its blocks")
                        ctl.step += 1
                        continue  # every admission finished at token 1
                    idle_spins = 0
                    stats["max_concurrent"] = max(stats["max_concurrent"],
                                                  len(dec) + len(chk))
                    if alloc is not None:
                        stats["shared_blocks_peak"] = max(
                            stats["shared_blocks_peak"],
                            alloc.shared_blocks())
                        self.obs.pool_sample(ctl.step, alloc, prefix)
                    if dec:
                        t_step = time.perf_counter()
                        # COW before the step: every ring slot this round
                        # writes (spec rounds write up to spec_k+1) must be
                        # exclusively owned — shared prefix blocks split
                        # here.  Under pool pressure this may preempt a
                        # victim lane (possibly one in dec): re-derive the
                        # decode set afterwards.
                        with span("serve.cow"):
                            cache = self._cow_writable(
                                cache, tables, alloc, prefix,
                                [(i, int(pos[i]), 1 + headroom) for i in dec],
                                stats, lanes=lanes, queue=queue, ctl=ctl)
                        dec = [i for i in dec if lanes[i] is not None]
                        chk = [i for i in chk if lanes[i] is not None]
                    if dec:
                        stats["decode_steps"] += 1
                        stats["occupied_lanes"] += len(dec) + len(chk)
                        if chk:
                            stats["interleaved_decode_steps"] += 1
                        if self._spec_paged is not None:
                            cache = self._spec_advance_paged(
                                cache, lanes, tables, alloc, prefix, dec, tok,
                                pos, ctl)
                        else:
                            cache, rng = self._decode_advance_paged(
                                cache, lanes, tables, alloc, dec, tok, pos,
                                ctl, rng, kv_row_bytes)
                        dt = time.perf_counter() - t_step
                        stats["decode_time_s"] += dt
                        self.obs.decode_step(ctl.step, len(dec) + len(chk),
                                             dt)
                    if chk:
                        with span("serve.chunk",
                                  **ctl.expert_last.get("chunk", {})):
                            cache, rng = self._chunk_step(
                                cache, lanes, tables, alloc, prefix, queue,
                                chk, tok, pos, ctl, rng)
                    if check and alloc is not None:
                        FA.check_invariants(alloc, tables, lanes, prefix)
                        stats["invariant_checks"] += 1
                    ctl.step += 1
            self._expert_flush(ctl)
            completed = True
        finally:
            # conservation on ANY exit: every live lane's block references
            # return to the pool, the prefix cache releases its own, and
            # last_stats reports the partial run ('completed' False)
            for i in range(B):
                if lanes[i] is not None:
                    self._release_lane(i, lanes, tables, alloc)
            if prefix is not None:
                prefix.drop_all()
            usable = (self.kv_blocks - 1) if alloc is not None else 0
            self.last_stats = dict(
                stats,
                requests=nreq,
                paged=True,
                lanes=B,
                kv_block_size=bs,
                kv_blocks=self.kv_blocks if alloc is not None else 0,
                completed=completed,
                request_status=dict(ctl.status),
                occupancy=stats["occupied_lanes"]
                / max(stats["decode_steps"] * B, 1),
                decode_tps=stats["decode_tokens"]
                / max(stats["decode_time_s"], 1e-9),
                block_peak_used=alloc.peak_used if alloc is not None else 0,
                block_utilization=(alloc.peak_used / usable) if usable
                else 0.0,
                block_bytes=blk_bytes,
                prefix_lookups=prefix.lookups if prefix is not None else 0,
                prefix_hit_blocks=prefix.hits if prefix is not None else 0,
                # every prefix hit is one block of KV HBM NOT re-materialized
                bytes_saved_sharing=(prefix.hits if prefix is not None
                                     else 0) * blk_bytes,
                kv_bytes_per_token=blk_bytes / max(bs, 1),
                kv_packed=tree_has_packed_kv(cache),
            )
            if self._spec_paged is not None:
                self._spec_summary(stats)
            self.obs.serve_end(self.last_stats)
        for uid in ctl.status:  # every uid reports, however it ended
            ctl.out.setdefault(uid, [])
        return {uid: np.asarray(toks, np.int64)
                for uid, toks in ctl.out.items()}

    def _reserve_blocks(self, alloc, prefix, r, headroom, use_prefix=True,
                        done: int = 0):
        """Reserve the lane's whole logical span up front: enough blocks for
        min(prompt + remaining budget + headroom, s_c_max) ring slots, minus
        prefix hits.  ``done`` is how many tokens the request already
        emitted (a preempt-resume carries them inside ``r.tokens``, so only
        the REMAINING budget needs new room).  Returns (block_ids,
        n_hit_blocks) or None when the pool cannot cover it even after
        evicting cache-only prefix blocks — admission then waits or
        preempts (``_admit_paged``)."""
        from repro.serve import blocks as SB

        if alloc is None:
            return [], 0
        bs = self.scfg.kv_block_size
        total = min(len(r.tokens) + max(r.max_new_tokens - done, 1) + headroom,
                    self._kv_scs[-1])
        span = SB.block_span(total, bs)
        hits = []
        if (use_prefix and prefix is not None
                and len(r.tokens) <= self._share_limit):
            hits = prefix.lookup(r.tokens)
        need = span - len(hits)
        while need > alloc.free_blocks:
            if prefix is None or not prefix.evict_one():
                break
        if need > alloc.free_blocks:
            if hits:
                alloc.free(hits)
            return None
        try:
            fresh = alloc.alloc(need)
        except SB.BlockError:
            # a fault-injected refusal (or a race with eviction accounting)
            # must leave the reservation atomic: hand the hits back and wait
            if hits:
                alloc.free(hits)
            return None
        return hits + fresh, len(hits)

    def _admit_paged(self, cache, queue, free, lanes, tables, alloc, prefix,
                     tok, pos, ctl, rng):
        """Admit queued requests into free lanes.  Short prompts run one
        grouped ``prefill_paged`` (per-row write_start skips re-writing
        prefix-hit blocks); prompts past the chunk threshold become 'chunk'
        lanes that prefill incrementally between decode steps.  Priority
        order with FIFO among equals: a request that cannot reserve its
        blocks parks the queue UNLESS a strictly-lower-priority victim lane
        exists — then the victim is preempted (recompute-on-resume) and
        admission retries.  A resumed request (its uid already has output)
        re-prefills prompt+emitted and APPENDS from there — bit-exact
        continuation by the prefill/decode parity contract."""
        scfg = self.scfg
        stats, out = ctl.stats, ctl.out
        headroom = scfg.spec_k
        group, chunk_new = [], []
        while queue and free:
            r = queue[0]
            done = len(out.get(r.uid, []))
            chunked = len(r.tokens) > self._chunk_threshold
            res = self._reserve_blocks(alloc, prefix, r, headroom,
                                       use_prefix=not chunked, done=done)
            if res is None:
                victim = (self._pick_victim(lanes, tables)
                          if scfg.preemption else None)
                if (victim is not None
                        and lanes[victim]["req"].priority < r.priority):
                    self._preempt_lane(victim, lanes, tables, alloc, prefix,
                                       queue, ctl)
                    free.append(victim)
                    continue  # retry the reservation with the freed blocks
                stats["admission_blocked"] += 1
                break
            queue.popleft()
            bids, n_hit = res
            lane = free.pop(0)
            ctl.admit_step.setdefault(r.uid, ctl.step)
            self.obs.admitted(r.uid, ctl.step, prompt_len=len(r.tokens),
                              resumed=bool(done), chunked=chunked)
            if done:
                stats["resumed"] += 1
            tables[lane, :] = 0
            tables[lane, : len(bids)] = bids
            # 'done0' = output length at THIS admission: a later preemption
            # re-queues tokens = r.tokens + out[uid][done0:] (r.tokens
            # already carries anything emitted before an earlier resume)
            if chunked:
                lanes[lane] = {"req": r, "phase": "chunk", "done": 0,
                               "done0": done}
                chunk_new.append(lane)
                stats["chunked_requests"] += 1
                stats["admissions"] += 1
                continue
            # own the row from reservation on — an exception between here
            # and the prefill landing must release these blocks (the serve
            # loop's finally sweeps every non-None lane)
            lanes[lane] = {"req": r, "phase": "prefill", "done0": done}
            # register at RESERVATION time: within one grouped prefill every
            # pool write lands before any lane's first pool read, so later
            # group members (same iteration!) already share these entries
            if prefix is not None and len(r.tokens) <= self._share_limit:
                prefix.register(r.tokens, tables[lane])
            group.append((lane, r, n_hit * scfg.kv_block_size))
        if chunk_new:
            # chunk lanes start from pristine recurrent state; their KV
            # arrives chunk by chunk through the block table
            cache = _cache_insert(
                cache,
                M.init_paged_cache(self.cfg, 1, 1, scfg.kv_block_size,
                                   kv=self.kv_spec),
                [0] * len(chunk_new), chunk_new, kv_mode="pool")
        if group:
            lens = np.asarray([len(r.tokens) for _, r, _ in group], np.int32)
            bucket = scfg.prefill_bucket
            L = max(-(-int(lens.max()) // bucket) * bucket, bucket)
            toks = np.zeros((len(group), L), np.int64)
            for j, (_, r, _) in enumerate(group):
                toks[j, : lens[j]] = np.asarray(r.tokens)
            starts = np.asarray([s for _, _, s in group], np.int32)
            logits, src, _ = self._prefill_paged(
                self.params, jnp.asarray(toks), cache,
                jnp.asarray(tables[[ln for ln, _, _ in group]]),
                jnp.asarray(lens), jnp.asarray(starts))
            last, badrows = self._apply_guard(
                logits[:, -1], list(range(len(group))),
                lambda j: group[j][1].uid, ctl, inject=False)
            first, rng = self._sample_next(jnp.asarray(last), rng)
            with span("serve.admit_wait"):
                first = np.asarray(first)
            stats["admissions"] += len(group)
            stats["prefill_tokens"] += int(lens.sum())
            badset = set(badrows)
            rows, slots = [], []
            for j, (lane, r, _) in enumerate(group):
                if j in badset:
                    self._quarantine(
                        r.uid, ctl,
                        functools.partial(self._release_lane, lane, lanes,
                                          tables, alloc))
                    continue
                t = int(first[j])
                prev = out.get(r.uid)
                if prev is not None:
                    prev.append(t)  # preempt-resume: continue the stream
                else:
                    out[r.uid] = [t]
                self.obs.first_token(r.uid, ctl.step)
                if self._done(t, out[r.uid], r):
                    self._release_lane(lane, lanes, tables, alloc)
                    self._finish(ctl, r.uid)
                    continue
                rows.append(j)
                slots.append(lane)
                lanes[lane] = {"req": r, "phase": "decode",
                               "done0": lanes[lane]["done0"]}
                tok[lane] = t
                pos[lane] = int(lens[j])
            # KV already landed in the shared pools through the block-table
            # scatter; only recurrent lane states need the row insert
            cache = _cache_insert(cache, src, rows, slots, kv_mode="src")
        return cache, rng

    def _pick_victim(self, lanes, tables):
        """Victim-selection rule (DESIGN.md §13): lowest priority first,
        then most blocks held (one eviction frees the most pool), then
        lowest lane id (deterministic).  None when no lane is evictable."""
        cand = [i for i, l in enumerate(lanes) if l is not None]
        if not cand:
            return None
        return min(cand, key=lambda i: (lanes[i]["req"].priority,
                                        -int(np.count_nonzero(tables[i])), i))

    def _preempt_lane(self, lane, lanes, tables, alloc, prefix, queue, ctl):
        """Evict one lane under pool pressure: register its still-valid
        prefix KV (prompt + emitted[:-1] — the positions actually written)
        so the resume replays them as prefix hits, release every block
        reference, and re-queue the request with ``tokens = prompt +
        emitted`` (recompute-on-resume).  Greedy decode is deterministic
        and prefill matches decode token-for-token (the §10/§12 parity
        contract), so the resumed stream continues exactly where the lane
        stopped."""
        l = lanes[lane]
        r = l["req"]
        done0 = int(l.get("done0", 0))
        emitted = list(ctl.out.get(r.uid, []))[done0:]
        if (prefix is not None and l.get("phase") == "decode" and emitted):
            written = np.concatenate([
                np.asarray(r.tokens, np.int64),
                np.asarray(emitted[:-1], np.int64)])
            if len(written) <= self._share_limit:
                prefix.register(written, tables[lane])
        self._release_lane(lane, lanes, tables, alloc)
        toks = (np.concatenate([np.asarray(r.tokens, np.int64),
                                np.asarray(emitted, np.int64)])
                if emitted else np.asarray(r.tokens, np.int64))
        self._requeue(queue, dataclasses.replace(r, tokens=toks))
        ctl.preempts[r.uid] = ctl.preempts.get(r.uid, 0) + 1
        ctl.status[r.uid] = "preempted"
        ctl.stats["preemptions"] += 1
        self.obs.preempted(r.uid, ctl.step)

    def _release_lane(self, lane, lanes, tables, alloc):
        """Free one reference on every block the lane's table holds (prefix
        cache refs keep shared blocks alive) and zero the row."""
        lanes[lane] = None
        if alloc is not None:
            alloc.free(int(b) for b in tables[lane] if b)
        tables[lane, :] = 0

    def _cow_writable(self, cache, tables, alloc, prefix, writes, stats, *,
                      lanes=None, queue=None, ctl=None):
        """Copy-on-write pre-step: for each (lane, start_pos, n_tokens)
        write this iteration will issue, split every shared block it touches
        (union over the distinct KV ring lengths — SWA wraparound folds high
        positions back into low logical blocks) and device-copy contents in
        batched calls.  Under pool pressure, evicts cache-only prefix
        blocks and retries; with ``lanes``/``queue``/``ctl`` provided (and
        ``ServeConfig.preemption``) an unsatisfiable split preempts a
        victim lane instead of raising — the caller must re-derive its
        decode set afterwards."""
        from repro.serve import blocks as SB

        if alloc is None:
            return cache
        bs = self.scfg.kv_block_size
        allow_preempt = (self.scfg.preemption and lanes is not None
                         and queue is not None and ctl is not None)
        src_all, dst_all = [], []

        def flush(cache):
            nonlocal src_all, dst_all
            if src_all:
                stats["cow_splits"] += len(src_all)
                cache = SB.copy_blocks(cache, src_all, dst_all)
                src_all, dst_all = [], []
            return cache

        for lane, p0, n in writes:
            if lanes is not None and lanes[lane] is None:
                continue  # victimized earlier in this very pass
            ent = set()
            for s_c in self._kv_scs:
                ent.update(SB.blocks_written(p0, n, s_c, bs))
            while True:
                try:
                    s, d = alloc.ensure_writable(tables[lane], sorted(ent))
                    src_all += s
                    dst_all += d
                    break
                except SB.BlockError:
                    if prefix is not None and prefix.evict_one():
                        continue
                    # next: un-register a to-be-overwritten block the cache
                    # ALONE shares with this lane (refcount exactly 2) —
                    # the write invalidates its cached content anyway, and
                    # releasing the cache ref makes it writable in place
                    forgot = False
                    if prefix is not None:
                        for j in ent:
                            bid = int(tables[lane][j])
                            if (alloc.refcount(bid) == 2
                                    and prefix.forget(bid)):
                                forgot = True
                    if forgot:
                        continue
                    if not allow_preempt:
                        raise
                    # graceful degradation: evict a victim lane and retry.
                    # Flush pending copies FIRST — the victim's fresh COW
                    # blocks return to the pool, and a deferred copy must
                    # never land in a block that may be re-allocated.
                    cache = flush(cache)
                    victim = self._pick_victim(lanes, tables)
                    if victim is None:
                        raise  # nothing left to evict: real exhaustion
                    self._preempt_lane(victim, lanes, tables, alloc,
                                       prefix, queue, ctl)
                    if victim == lane:
                        break  # the writer itself was evicted: write moot
        return flush(cache)

    def _chunk_step(self, cache, lanes, tables, alloc, prefix, queue, chk,
                    tok, pos, ctl, rng):
        """Advance every chunk lane by one <=chunk_T-token slice through the
        verify path (teacher-forced forward over known prompt tokens) and
        commit keep=n_valid — the SAME cache-write helper spec rollback
        uses.  The final chunk's last logit samples the first token (its
        device sync is ``serve.chunk_wait``) and the lane flips to
        'decode'."""
        scfg = self.scfg
        stats, out = ctl.stats, ctl.out
        B, T = self.lanes, self._chunk_T
        toks = np.zeros((B, T), np.int64)
        posv = np.zeros(B, np.int32)
        keep = np.zeros(B, np.int32)  # 0 freezes idle/decode lanes
        fin = []  # (lane, n_valid in this chunk)
        for i in chk:
            l = lanes[i]
            r = l["req"]
            start = l["done"]
            n = min(T, len(r.tokens) - start)
            toks[i, :n] = np.asarray(r.tokens[start:start + n])
            posv[i] = start
            keep[i] = n
            l["done"] = start + n
            self.obs.chunk(r.uid, ctl.step, n, l["done"], len(r.tokens))
            if l["done"] == len(r.tokens):
                fin.append((i, n))
        cache = self._cow_writable(
            cache, tables, alloc, prefix,
            [(i, int(posv[i]), int(keep[i])) for i in chk], stats,
            lanes=lanes, queue=queue, ctl=ctl)
        # a COW preemption may have evicted a chunk lane mid-pass: its
        # zeroed table row would route the write to scratch (harmless),
        # but freeze it outright and drop it from the finishers
        for i in chk:
            if lanes[i] is None:
                keep[i] = 0
        fin = [(i, n) for i, n in fin if lanes[i] is not None]
        logits, steps, *rows = self._verify_paged(
            self.params, {"tokens": jnp.asarray(toks)}, cache,
            jnp.asarray(tables), jnp.asarray(posv))
        self._expert_pass(ctl, "chunk", rows, B * T, T)
        cache = self._commit_paged(cache, jnp.asarray(tables), steps,
                                   jnp.asarray(keep), jnp.asarray(posv))
        stats["chunk_steps"] += 1
        stats["prefill_tokens"] += int(sum(int(keep[i]) for i in chk))
        if fin:
            sel = logits[jnp.asarray([i for i, _ in fin]),
                         jnp.asarray([n - 1 for _, n in fin])]
            sel, badrows = self._apply_guard(
                sel, list(range(len(fin))),
                lambda j: lanes[fin[j][0]]["req"].uid, ctl, inject=False)
            first, rng = self._sample_next(jnp.asarray(sel), rng)
            with span("serve.chunk_wait"):
                first = np.asarray(first)
            self._expert_flush(ctl)
            badset = set(badrows)
            for j, (i, _) in enumerate(fin):
                r = lanes[i]["req"]
                if j in badset:
                    self._quarantine(
                        r.uid, ctl,
                        functools.partial(self._release_lane, i, lanes,
                                          tables, alloc))
                    continue
                done0 = int(lanes[i].get("done0", 0))
                t = int(first[j])
                prev = out.get(r.uid)
                if prev is not None:
                    prev.append(t)  # preempt-resume continues the stream
                else:
                    out[r.uid] = [t]
                self.obs.first_token(r.uid, ctl.step)
                # register only now — the blocks filled progressively
                if prefix is not None and len(r.tokens) <= self._share_limit:
                    prefix.register(r.tokens, tables[i])
                if self._done(t, out[r.uid], r):
                    self._release_lane(i, lanes, tables, alloc)
                    self._finish(ctl, r.uid)
                    continue
                lanes[i] = {"req": r, "phase": "decode", "done0": done0}
                tok[i] = t
                pos[i] = len(r.tokens)
        return cache, rng

    def _decode_advance_paged(self, cache, lanes, tables, alloc, dec, tok,
                              pos, ctl, rng, kv_row_bytes):
        """One decode step over every decode lane: its dispatch
        (``serve.decode``), the device sync on the sampled tokens
        (``serve.wait``) and the per-lane bookkeeping (``serve.tokens``).
        Returns (cache, advanced rng)."""
        stats = ctl.stats
        live_m = np.zeros(self.lanes, np.int32)
        live_m[dec] = 1  # idle/chunk lanes: write_len 0
        scs = self._kv_layer_scs
        gathered = self.lanes * int(scs.sum())
        live_rows = int(np.minimum(pos[dec][None, :] + 1, scs[:, None]).sum())
        stats["kv_rows_gathered"] += gathered
        stats["kv_rows_live"] += live_rows
        self.obs.kv_read(ctl.step, gathered, live_rows, kv_row_bytes)
        with span("serve.decode", kv_rows_gathered=gathered,
                  kv_rows_live=live_rows,
                  **ctl.expert_last.get("decode", {})):
            step_toks = {"tokens": jnp.asarray(tok)[:, None]}
            prev = cache if self._guard == "fallback" else None
            logits, cache, *rows = self._decode_paged(
                self.params, step_toks, cache, jnp.asarray(tables),
                jnp.asarray(pos), jnp.asarray(live_m))
            self._expert_pass(ctl, "decode", rows, self.lanes, 1)
            last, bad = self._apply_guard(
                logits[:, -1], dec, lambda i: lanes[i]["req"].uid, ctl,
                cache=cache)
            if bad and self._guard == "fallback":
                stats["fallback_steps"] += 1
                logits, cache = self._ref_decode_paged()(
                    self.params, step_toks, prev, jnp.asarray(tables),
                    jnp.asarray(pos), jnp.asarray(live_m))
                last, bad = self._apply_guard(
                    logits[:, -1], dec, lambda i: lanes[i]["req"].uid, ctl,
                    retry=True, cache=cache)
            for i in bad:
                self._quarantine(
                    lanes[i]["req"].uid, ctl,
                    functools.partial(self._release_lane, i, lanes, tables,
                                      alloc))
            nxt, rng = self._sample_next(jnp.asarray(last), rng)
        with span("serve.wait"):
            nxt = np.asarray(nxt)
        self._expert_flush(ctl)
        with span("serve.tokens"):
            for i in dec:
                if lanes[i] is None:
                    continue  # quarantined this step
                r = lanes[i]["req"]
                pos[i] += 1
                t = int(nxt[i])
                ctl.out[r.uid].append(t)
                tok[i] = t
                stats["decode_tokens"] += 1
                if self._done(t, ctl.out[r.uid], r):
                    self._release_lane(i, lanes, tables, alloc)
                    self._finish(ctl, r.uid)
        return cache, rng

    def _spec_advance_paged(self, cache, lanes, tables, alloc, prefix, dec,
                            tok, pos, ctl):
        """One speculation round through the block tables.  The jitted round
        drafts + verifies WITHOUT touching the pool, then commits only the
        accepted prefix (models.rollback_cache_paged — commit-on-accept:
        rejected draft positions never reach a shared block)."""
        stats, out = ctl.stats, ctl.out
        live = np.zeros(self.lanes, np.int32)
        live[dec] = 1
        res = self._spec_paged(
            self.params, cache, jnp.asarray(tables), jnp.asarray(tok),
            jnp.asarray(pos), jnp.asarray(live))
        if self._guard is not None:
            target, keep, cache, finite = res
            finite = np.asarray(finite)
        else:
            target, keep, cache = res
            finite = None
        target, keep = np.asarray(target), np.asarray(keep)
        if ctl.faults is not None:
            if finite is not None:
                finite = ctl.faults.corrupt_finite(finite, dec)
            # an injected verify mismatch clamps acceptance to 1 — safe:
            # every committed token is the target's own argmax, so the
            # stream is unchanged, only throughput drops
            keep = ctl.faults.clip_spec_keep(keep)
        if finite is not None:
            stats["guard_checks"] += 1
            bad = [i for i in dec if not finite[i]]
            if bad:
                stats["numeric_faults"] += len(bad)
                self.obs.guard_trip([lanes[i]["req"].uid for i in bad],
                                    ctl.step, cache=cache)
                if self._guard == "fail-fast":
                    from repro.serve.faults import NumericFault

                    raise NumericFault(
                        [lanes[i]["req"].uid for i in bad], ctl.step)
                for i in bad:  # quarantine BEFORE committing their tokens
                    self._quarantine(
                        lanes[i]["req"].uid, ctl,
                        functools.partial(self._release_lane, i, lanes,
                                          tables, alloc))
                dec = [i for i in dec if lanes[i] is not None]
        stats["spec_rounds"] += 1
        stats["draft_tokens"] += self.scfg.spec_k * len(dec)
        self.obs.spec_round(ctl.step, [int(keep[i]) for i in dec])
        for i in dec:
            r = lanes[i]["req"]
            kp = int(keep[i])
            stats["accepted_hist"][kp] += 1
            committed = 0
            for j in range(kp):
                t = int(target[i, j])
                out[r.uid].append(t)
                committed += 1
                stats["decode_tokens"] += 1
                if self._done(t, out[r.uid], r):
                    self._release_lane(i, lanes, tables, alloc)
                    self._finish(ctl, r.uid)
                    break
            pos[i] += committed
            tok[i] = int(target[i, committed - 1])
        return cache

    def _expert_pass(self, ctl: _ServeControl, kind: str, rows, tokens: int,
                     seq: int) -> None:
        """Queue a paged pass's (layers, E) routed-row counts (``rows`` is
        the step's extra output, empty for a model without experts); the
        host reads them at its next sync, with the tokens it fetches."""
        if rows:
            bm = MOE.block_rows(tokens, seq, self.cfg.top_k)
            ctl.expert_pending.append((kind, ctl.step, rows[0], bm))

    def _expert_flush(self, ctl: _ServeControl) -> None:
        """Read the queued passes' expert row counts into the serve stats
        and the ``expert_rows`` hook: rows routed, padding rows added to
        reach each expert's row block, (layer, expert) pairs touched and
        the most rows one expert took.  The next ``serve.decode`` /
        ``serve.chunk`` span carries the latest pass of its kind."""
        stats = ctl.stats
        for kind, step, rows, bm in ctl.expert_pending:
            c = np.asarray(rows).astype(np.int64)
            got = {"expert_rows_real": int(c.sum()),
                   "expert_rows_padded": int((-(-c // bm) * bm - c).sum()),
                   "experts_touched": int((c > 0).sum()),
                   "expert_rows_max": int(c.max(initial=0))}
            for key in ("expert_rows_real", "expert_rows_padded",
                        "experts_touched"):
                stats[key] += got[key]
            stats["expert_rows_max"] = max(stats["expert_rows_max"],
                                           got["expert_rows_max"])
            stats["expert_passes"] += 1
            ctl.expert_last[kind] = got
            self.obs.expert_rows(step, kind, **got)
        ctl.expert_pending.clear()

    def _done(self, t: int, emitted: list, r: Request) -> bool:
        eos = self.scfg.eos_id
        return (eos is not None and t == eos) or len(emitted) >= r.max_new_tokens

    @staticmethod
    def _norm_request(r, i: int, max_new: int) -> Request:
        """Normalize + validate one queue entry.  Bad fields fail HERE with
        actionable messages instead of as shape errors deep inside prefill
        (or as silently lost results keyed on an unhashable uid)."""
        if not isinstance(r, Request):
            r = Request(uid=i, tokens=np.asarray(r, np.int64),
                        max_new_tokens=max_new)
        toks = np.asarray(r.tokens, np.int64)
        if toks.ndim != 1 or toks.shape[0] == 0:
            raise ValueError(
                f"request {r.uid!r}: prompt must be a non-empty 1-D token "
                f"sequence, got shape {tuple(toks.shape)} — an empty prompt "
                f"has no logits to sample a first token from")
        if int(r.max_new_tokens) < 1:
            raise ValueError(
                f"request {r.uid!r}: max_new_tokens must be >= 1, got "
                f"{r.max_new_tokens} (admission samples the first token "
                f"from the prefill logits, so every request emits >= 1)")
        try:
            hash(r.uid)
        except TypeError:
            raise ValueError(
                f"request uid {r.uid!r} is unhashable: results, statuses "
                f"and cancellation all key on uid — use a str/int/tuple "
                f"id") from None
        if r.deadline_steps is not None and int(r.deadline_steps) < 1:
            raise ValueError(
                f"request {r.uid!r}: deadline_steps must be >= 1 scheduler "
                f"iterations (or None), got {r.deadline_steps}")
        return dataclasses.replace(r, tokens=toks)

    def _sample(self, logits, rng):
        with jax.named_scope("sample"):
            return sample_tokens(logits, self.cfg, self.scfg.temperature, rng)

    def _sample_next(self, logits, rng):
        """Split-then-sample: every draw gets a fresh subkey (never a key
        that is later split) — the one RNG discipline shared by generate()
        and serve().  Returns (tokens, advanced rng)."""
        rng, sub = jax.random.split(rng)
        return self._sample(logits, sub), rng
