"""Mixture-of-Experts FFN: grouped DSBP serving path, GShard training path.

Serving with DSBP-packed experts (``moe_ffn(no_drop=True)`` under an
active quant context) runs the experts on the paper's datapath: the
(token, choice) rows are sorted by expert, each expert's group is padded
only to a row block, and the gate-and-up and down projections run as
grouped fused DSBP GEMMs (``kernels/dsbp_grouped.py``) straight off the
stacked packed containers; the gates then combine each token's rows.

Training, and serving of float or weight-only experts, split tokens into
dispatch groups of ``moe_group``; within each group a capacity-limited
one-hot dispatch tensor routes tokens to experts via einsums (dense,
shardable — the standard GSPMD MoE formulation, cf. GShard/MaxText).
Experts' FFN weights carry a leading expert axis; under the production
mesh the ffn dim shards over 'model' and the token/group dims over 'data'
(EP over a dedicated expert axis is exercised separately in
tests/test_parallel.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import per_tensor_scale
from repro.core.packed import PackedDSBPWeight
from repro.parallel.context import active_ctx

from .layers import Quant, dense

__all__ = ["init_moe", "moe_ffn", "fuse_gate_up", "block_rows",
           "grouped_rows", "hold_experts", "with_experts"]

HIGHEST = jax.lax.Precision.HIGHEST
# quant methods whose grouped expert GEMM is the Pallas kernel on one
# device; the others run its jnp reference
# (core.quantized.grouped_packed_matmul_ref)
_KERNEL_METHODS = ("dsbp_fused", "dsbp_fused_sharded")


def block_rows(tokens: int, seq: int, top_k: int) -> int:
    """Row block of a pass's grouped expert GEMMs: 8 for a one-token step
    (a decode batch routes a few rows to each expert), 128 for a
    multi-token pass, never more than the pass routes."""
    bm = 8 if seq == 1 else 128
    return min(bm, -(-tokens * top_k // 8) * 8)


def grouped_rows(tokens: int, top_k: int, n_experts: int, bm: int) -> int:
    """Static row count of a pass's grouped GEMMs: the routed rows plus
    the most padding any routing can need (each expert holding rows pads
    at most ``bm - 1``), rounded to the row block."""
    routed = tokens * top_k
    rows = routed + min(n_experts, routed) * (bm - 1)
    return -(-rows // bm) * bm


def fuse_gate_up(w1: PackedDSBPWeight, w3: PackedDSBPWeight):
    """One ``(E, d, 2f)`` container of the gate (``w1``) and up (``w3``)
    projections side by side along N.  Exact: DSBP weight groups run along
    K within one output column, and the weight scales are per column, so
    packing the concatenated float weights gives the same container."""
    cat = lambda a, b, ax: jnp.concatenate([a, b], axis=ax)
    tw = jnp.asarray(w1.tscale)
    return PackedDSBPWeight(
        ka=cat(w1.ka, w3.ka, -1), kscale=cat(w1.kscale, w3.kscale, -1),
        tscale=cat(w1.tscale, w3.tscale, -2 if tw.ndim >= 2 else -1),
        bits=cat(w1.bits, w3.bits, -2), k=w1.k, n=w1.n + w3.n,
        group_size=w1.group_size, cfg=w1.cfg, version=w1.version)


def init_moe(key, cfg, dtype):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    s_in, s_out = d**-0.5, ff**-0.5
    return {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32) * s_in).astype(dtype),
        "w1": (jax.random.normal(ks[1], (e, d, ff), jnp.float32) * s_in).astype(dtype),
        "w3": (jax.random.normal(ks[2], (e, d, ff), jnp.float32) * s_in).astype(dtype),
        "w2": (jax.random.normal(ks[3], (e, ff, d), jnp.float32) * s_out).astype(dtype),
    }


def moe_ffn(params, x: jax.Array, cfg, quant: Quant | None = None,
            no_drop: bool = False, expert_rows: list | None = None):
    """x: (B, S, d) -> (B, S, d); top-k routing.

    Training uses GShard capacity dropping (cfg.capacity_factor); serving
    paths pass ``no_drop=True``: nothing is dropped, and packed experts
    under an active quant context take the grouped DSBP path
    (:func:`_moe_grouped`).  A token's output then does not depend on
    which tokens share its batch except through the per-tensor FP8 input
    scales, which are powers of two taken over the whole batch (as every
    DSBP projection's are): a batch with a larger activation may shift
    them and move the quantization of small values.  The grouped path
    appends the layer's per-expert routed row counts ((E,) int32) to
    ``expert_rows`` when given.
    """
    if no_drop and quant and _packed(params):
        out, counts = _moe_grouped(params, x, cfg, quant)
        if expert_rows is not None:
            expert_rows.append(counts)
        return out
    if "unit" in params:  # held stacks (hold_experts): take this layer's
        unit = params["unit"]
        params = {k: (jax.tree.map(lambda a: a[unit], v)
                      if isinstance(v, PackedDSBPWeight) else v)
                  for k, v in params.items() if k != "unit"}
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    g_sz = min(cfg.moe_group, t)
    pad = (-t) % g_sz
    xf = x.reshape(t, d)
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, d), x.dtype)])
    valid = (jnp.arange(t + pad) < t).reshape(-1, g_sz)  # (G, S)
    n_g = (t + pad) // g_sz
    xg = xf.reshape(n_g, g_sz, d)

    logits = dense(params["router"], xg)  # (G, S, E) — router stays fp
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # (G, S, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    if no_drop:
        cap = g_sz
    else:
        cap = max(int(cfg.capacity_factor * k * g_sz / e), 1)

    # dispatch/combine tensors, k choices in priority order
    dispatch = jnp.zeros((n_g, g_sz, e, cap), jnp.bool_)
    combine = jnp.zeros((n_g, g_sz, e, cap), jnp.float32)
    # position of each token within its expert's queue, computed jointly
    # over the k choices so capacity is shared (GShard priority order)
    prev_counts = jnp.zeros((n_g, 1, e), jnp.int32)
    for choice in range(k):
        mask = jax.nn.one_hot(idx[..., choice], e, dtype=jnp.int32)  # (G,S,E)
        mask = mask * valid[..., None]  # pad tokens never dispatch
        pos = jnp.cumsum(mask, axis=1) - 1 + prev_counts
        prev_counts = prev_counts + jnp.sum(mask, axis=1, keepdims=True)
        within = (pos < cap) & (mask > 0)
        posc = jnp.clip(pos, 0, cap - 1)
        oh = jax.nn.one_hot(posc, cap, dtype=jnp.float32) * within[..., None]
        dispatch = dispatch | (oh > 0)
        combine = combine + oh * gate_vals[..., choice, None, None]

    def _expert_w(wp):
        # DSBP-packed expert weights dequantize for the dispatch einsums
        # (weight-only consumption: experts contract against activations of
        # mixed tokens, so the per-row on-the-fly path stays in `dense`).
        # The logical (d_in, d_out) comes from the container, so the group
        # padding of d_in is stripped explicitly: (E, N, ng, G) int8 ->
        # (E, d_in, d_out).
        if isinstance(wp, PackedDSBPWeight):
            return wp.dequantize(x.dtype)
        return wp

    if "w13" in params:  # a packed serving tree's fused gate and up
        w13 = _expert_w(params["w13"])
        w1, w3 = jnp.split(w13, 2, axis=-1)
    else:
        w1 = _expert_w(params["w1"])
        w3 = _expert_w(params["w3"])
    w2 = _expert_w(params["w2"])
    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)
    h1 = jnp.einsum("gecd,edf->gecf", xe, w1)
    h3 = jnp.einsum("gecd,edf->gecf", xe, w3)
    h = jax.nn.silu(h1.astype(jnp.float32)).astype(x.dtype) * h3
    ye = jnp.einsum("gecf,efd->gecd", h, w2)
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)
    y = y.reshape(-1, d)[:t]
    return y.reshape(b, s, d)


def hold_experts(units):
    """Split the packed expert containers out of stacked unit params (one
    tree per pattern position, leading unit axis), so a layer loop does
    not slice them: ``(units without them, held stacks or None per
    position)``.  :func:`with_experts` puts them back inside the loop."""
    kept, held = [], []
    for p in units:
        moe = p.get("moe") if isinstance(p, dict) else None
        stacks = {k: v for k, v in (moe or {}).items()
                  if isinstance(v, PackedDSBPWeight)}
        if not stacks:
            kept.append(p)
            held.append(None)
            continue
        kept.append({**p, "moe": {k: v for k, v in moe.items()
                                  if k not in stacks}})
        held.append(stacks)
    return kept, held


def with_experts(layer_params, held, unit):
    """One layer's params inside the loop, its held expert stacks read at
    ``unit`` (see :func:`_moe_grouped`)."""
    if held is None:
        return layer_params
    return {**layer_params,
            "moe": {**layer_params["moe"], **held, "unit": unit}}


def _packed(params) -> bool:
    return all(isinstance(params[n], PackedDSBPWeight)
               for n in ("w2", "w13" if "w13" in params else "w1"))


def _grouped_gemm(pw, xs, ts, block_expert, n_used, bm, quant, unit):
    icfg = quant.cfg_for(pw).input_cfg
    # under a mesh the jnp reference runs (bit-identical): GSPMD cannot
    # partition a Mosaic kernel, and the grouped GEMM has no shard_map form
    if quant.method.name in _KERNEL_METHODS and active_ctx() is None:
        from repro.kernels import ops as kops  # local import: optional dep

        return kops.dsbp_matmul_grouped(xs, pw, ts, block_expert, n_used,
                                        bm, unit=unit, input_cfg=icfg)
    from repro.core.quantized import grouped_packed_matmul_ref

    return grouped_packed_matmul_ref(xs, ts, pw, block_expert, bm,
                                     unit=unit, input_cfg=icfg)


def _moe_grouped(params, x, cfg, quant: Quant):
    """The serving MoE: softmax router, top-k with renormalized gates,
    (token, choice) rows sorted by expert and padded per expert to the row
    block, gate-and-up then down through the grouped DSBP GEMM, and the
    gates' weighted sum of each token's rows.

    The FP8 input scale of the gate-and-up GEMM is taken over the layer's
    tokens before the gather, and the down GEMM's over every routed row,
    so grouping changes no row's quantization.  Returns the output and
    the layer's per-expert routed row counts ((E,) int32).

    With a ``unit`` entry in ``params`` the expert containers are a layer
    loop's whole ``(U, E, ...)`` stacks, read in place at that layer
    (``models/model.py`` holds them out of the loop's slicing).
    """
    unit = params.get("unit")
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = x.reshape(t, d)
    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            jnp.asarray(params["router"], jnp.float32),
                            precision=HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)  # (T, k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    bm = block_rows(t, s, k)
    rows = grouped_rows(t, k, e, bm)
    with jax.named_scope("expert_dispatch"):
        flat = idx.reshape(-1)  # pair p = (token p // k, choice p % k)
        counts = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        padded = -(-counts // bm) * bm
        ends = jnp.cumsum(padded)
        order = jnp.argsort(flat, stable=True)
        sorted_e = flat[order]
        rank = jnp.arange(t * k) - (jnp.cumsum(counts) - counts)[sorted_e]
        dest = jnp.zeros((t * k,), jnp.int32).at[order].set(
            (ends - padded)[sorted_e] + rank)
        row_tok = jnp.zeros((rows,), jnp.int32).at[dest].set(
            jnp.arange(t * k, dtype=jnp.int32) // k)
        row_ok = jnp.zeros((rows, 1), bool).at[dest].set(True)
        xs = jnp.where(row_ok, jnp.take(xf, row_tok, axis=0), 0)
        n_used = ends[-1:] // bm
        block_expert = jnp.minimum(
            jnp.searchsorted(ends, jnp.arange(rows // bm) * bm, side="right"),
            e - 1).astype(jnp.int32)
    if "w13" in params:
        w13 = params["w13"]
    else:
        w13 = fuse_gate_up(params["w1"], params["w3"])
    fmt = quant.cfg_for(w13).input_cfg.fmt
    f = w13.n // 2
    with jax.named_scope("experts_in"):
        ts_in = per_tensor_scale(xf.astype(jnp.float32), fmt)
        h = _grouped_gemm(w13, xs, ts_in, block_expert, n_used, bm,
                          quant, unit).astype(x.dtype)
        act = (jax.nn.silu(h[:, :f].astype(jnp.float32)).astype(x.dtype)
               * h[:, f:])
        act = jnp.where(row_ok, act, 0)
    with jax.named_scope("experts_out"):
        ts_out = per_tensor_scale(act.astype(jnp.float32), fmt)
        y = _grouped_gemm(params["w2"], act, ts_out, block_expert, n_used,
                          bm, quant, unit)
    with jax.named_scope("expert_combine"):
        yp = jnp.take(y, dest, axis=0).reshape(t, k, d)
        out = jnp.sum(yp * gate[..., None], axis=1)
    return out.astype(x.dtype).reshape(b, s, d), counts
