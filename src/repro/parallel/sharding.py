"""Sharding rules: param/batch/cache PartitionSpecs for the production mesh.

Scheme (DESIGN.md §6) — "FSDP × TP":
  * projection weights:  contraction/d_model dim -> 'data' (storage
    sharding; GSPMD all-gathers per layer inside the scan), output/heads/
    ffn/vocab dim -> 'model' (tensor parallel);
  * batch dims -> ('pod', 'data') (multi-pod) or 'data';
  * 'pod' is pure DP for weights (replicated across pods, grads all-reduced
    over DCN);
  * decode caches: batch-sharded; at batch=1 (long_500k) the KV sequence
    dim shards over 'data' (sequence parallelism — softmax reductions
    become collectives) and recurrent states shard over 'model' heads.

Rules bind to parameter names (the contract stated in models/layers.py).
Every rule checks divisibility and falls back to replication for that dim,
so odd vocabularies (mamba2's 50280) and head counts (deepseek's 56) stay
correct — they just replicate where they do not divide.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["param_pspecs", "batch_pspecs", "cache_pspecs", "serve_pspecs",
           "named", "batch_axes", "make_mesh"]


def make_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto — the mesh the ``with mesh:``
    / NamedSharding / shard_map code of this repo is written for
    (``jax.make_mesh`` itself defaults to Explicit axes)."""
    from jax.sharding import AxisType

    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _expert_axis(mesh: Mesh, names, leaf) -> str | None:
    """'expert' when the mesh carries the axis, the leaf belongs to a MoE
    expert stack (moe/w1|w2|w3 with a leading expert dim) and the expert
    count divides — else None (replicated lead, the existing behavior)."""
    if "expert" not in mesh.axis_names or "moe" not in names:
        return None
    if leaf.ndim < 3:
        return None
    stacked = "units" in names
    e = leaf.shape[1] if stacked else leaf.shape[0]
    return "expert" if e % mesh.shape["expert"] == 0 else None


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    if isinstance(axes, str):
        axes = (axes,)
    size = int(np.prod([mesh.shape[a] for a in axes]))
    return dim % size == 0


def _spec2d(shape, mesh, in_axis="data", out_axis="model"):
    """(d_in, d_out) rule with divisibility fallback."""
    a = in_axis if in_axis and _fits(shape[0], mesh, in_axis) else None
    b = out_axis if out_axis and _fits(shape[1], mesh, out_axis) else None
    return P(a, b)


# weight-name -> (in_axis, out_axis) for trailing 2 dims
_IN_OUT = {
    "wq": ("data", "model"), "wk": ("data", "model"), "wv": ("data", "model"),
    "wo": ("model", "data"),
    "w1": ("data", "model"), "w3": ("data", "model"), "w2": ("model", "data"),
    "w_in": ("data", "model"), "w_gate": ("data", "model"),
    "w_out": ("model", "data"),
    "wa": ("data", "model"), "wx": ("data", "model"),
    "router": ("data", None),
    "lm_head": ("data", "model"),
}
_VEC_MODEL = {"lam", "ba", "bx", "a_log", "dt_bias", "d_skip"}  # width-sharded 1-D


def _param_rule(path, leaf, mesh: Mesh):
    names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    name = names[-1]
    stacked = "units" in names  # leading unit axis from the layer scan
    shape = leaf.shape[1:] if stacked else leaf.shape
    lead = (None,) if stacked else ()

    if name in ("ka", "kscale", "tscale", "bits") and len(names) >= 2 \
            and names[-2] in _IN_OUT:
        # DSBP-packed projection, kernel layout (DESIGN.md §8): ka (..., K',
        # N_out) int8; kscale (..., ng, N); tscale (..., N, 1); bits
        # (..., N, n_g).  N_out -> 'model' (TP), the reduction dims K'/ng ->
        # 'data' (FSDP storage); MoE expert containers additionally shard
        # their leading expert dim over 'expert' when the mesh carries one.
        full = leaf.shape
        spec = [None] * len(full)
        if name in ("ka", "kscale") and len(full) >= 2:
            spec[-2] = "data" if _fits(full[-2], mesh, "data") else None
            spec[-1] = "model" if _fits(full[-1], mesh, "model") else None
        elif name in ("tscale", "bits") and len(full) >= 2:
            # per-output-column metadata: N is dim -2
            spec[-2] = "model" if _fits(full[-2], mesh, "model") else None
        ea = _expert_axis(mesh, names, leaf)
        if ea is not None and len(full) >= 3:
            spec[1 if "units" in names else 0] = ea
        return P(*spec)

    if name == "embed":
        spec = _spec2d(shape, mesh, "model", "data")  # (vocab, d)
    elif name in _IN_OUT:
        ia, oa = _IN_OUT[name]
        if len(shape) == 3:  # MoE experts (E, d_in, d_out)
            a = ia if ia and _fits(shape[1], mesh, ia) else None
            b = oa if oa and _fits(shape[2], mesh, oa) else None
            spec = P(_expert_axis(mesh, names, leaf), a, b)
        else:
            spec = _spec2d(shape, mesh, ia, oa)
    elif name == "conv_w":  # (K, width)
        spec = P(None, "model" if _fits(shape[1], mesh, "model") else None)
    elif name in _VEC_MODEL:
        spec = P("model" if _fits(shape[0], mesh, "model") else None)
    elif name == "scale":  # norms
        spec = P(*([None] * len(shape)))
    else:
        spec = P(*([None] * len(shape)))
    return P(*lead, *spec)


def param_pspecs(params, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _param_rule(p, l, mesh), params
    )


def batch_pspecs(batch, mesh: Mesh):
    ba = batch_axes(mesh)

    def rule(path, leaf):
        b = leaf.shape[0]
        a = ba if b % int(np.prod([mesh.shape[x] for x in ba])) == 0 else (
            "data" if b % mesh.shape["data"] == 0 else None
        )
        return P(a, *([None] * (leaf.ndim - 1)))

    return jax.tree_util.tree_map_with_path(rule, batch)


def cache_pspecs(cache, mesh: Mesh, batch_size: int, shard_kv_model: bool = True,
                 paged: bool = False):
    """KV caches (B,H,S,D) / states.

    Batch dim -> batch axes; additionally (the decode memory-term
    optimization, EXPERIMENTS.md §Perf-2) the KV head dim shards over
    'model' when divisible, else the *sequence* dim does — either way the
    cache stops being replicated across the TP axis.  B=1 (long_500k)
    shards the sequence over 'data' (SP).

    ``paged=True`` reads the tree as a block-pool cache (DESIGN.md §12):
    KV leaves are physical pools ((R,) NB, Hkv, bs, D) with no batch axis —
    the BLOCK axis shards over the batch axes (any lane's table may address
    any block, so GSPMD turns table gathers into cross-shard collectives;
    correctness is GSPMD's, placement is ours) and the head dim keeps the
    'model' rule.  Recurrent per-lane states keep the dense batch rule.
    """
    ba = batch_axes(mesh)
    bsz = int(np.prod([mesh.shape[x] for x in ba]))
    batch_ok = batch_size % bsz == 0
    msz = mesh.shape["model"]

    def rule(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "idx",
                                               getattr(p, "name", p))))
                 for p in path]
        stacked = "units" in names
        shape = leaf.shape[1:] if stacked else leaf.shape
        lead = (None,) if stacked else ()
        name = names[-1]
        # packed KV leaves (repro.kvq.PackedKVBlock) flatten as qm/scale
        # children of the k/v entry; both share the k/v leading axes (the
        # scale's trailing axis is 1 and stays unsharded either way), so
        # they inherit the parent's KV placement rule verbatim
        if name in ("qm", "scale") and len(names) >= 2 and names[-2] in (
                "k", "v"):
            name = names[-2]
        if paged and name in ("k", "v") and len(shape) == 4:
            blk_ax = ba if shape[0] % bsz == 0 else None
            head_ax = "model" if (shard_kv_model and shape[1] % msz == 0) else None
            return P(*lead, blk_ax, head_ax, None, None)
        if name in ("k", "v") and len(shape) == 4:
            b_ax = ba if batch_ok else None
            head_ax = "model" if (shard_kv_model and shape[1] % msz == 0) else None
            seq_axes = []
            if not batch_ok and shape[2] % mesh.shape["data"] == 0:
                seq_axes.append("data")  # B=1: SP over data
            if (shard_kv_model and head_ax is None
                    and shape[2] % (mesh.shape["data"] * msz if seq_axes else msz) == 0):
                seq_axes.append("model")
            spec = (b_ax, head_ax, tuple(seq_axes) if seq_axes else None, None)
        elif name == "h" and len(shape) >= 2:
            ok = shape[1] % msz == 0
            spec = (ba if batch_ok else None, "model" if ok else None)
            spec += (None,) * (len(shape) - 2)
        elif name == "conv":
            ok = shape[-1] % msz == 0
            spec = (ba if batch_ok else None,)
            spec += (None,) * (len(shape) - 2) + ("model" if ok else None,)
        elif batch_ok:
            spec = (ba,) + (None,) * (len(shape) - 1)
        else:
            spec = (None,) * len(shape)
        return P(*lead, *spec)

    return jax.tree_util.tree_map_with_path(rule, cache)


_GROUP = 64  # core.dsbp group size (kept in sync with kernels.dsbp_fused.GROUP)


def serve_pspecs(params, mesh: Mesh):
    """Compute-layout specs for multi-device *serving* (DESIGN.md §11).

    Unlike :func:`param_pspecs` (FSDP storage: reduction dims sharded over
    'data', re-gathered at use), this places every projection exactly as
    its shard_map GEMM consumes it — the Megatron split from
    ``parallel.context.tp_axes_for``: ka/kscale column shards over the
    plan's n_axis (wq/wk/wv/w1/w3...), group-aligned K-row shards over the
    plan's k_axis (wo/w2/w_out), tscale/bits row shards over n_axis — so
    decode moves ZERO weight bytes per call (the only collective left is
    the row-parallel psum).  Per-axis divisibility fallback mirrors
    ``ops.dsbp_matmul_fused_sharded`` exactly (K additionally needs
    group-aligned shards), so storage always equals the compute-time spec.
    MoE expert stacks keep their 'expert' lead-dim rule.  Everything that
    is not a planned projection (embed, norms, router, vectors) replicates.
    """
    from repro.core.packed import key_entry_str
    from repro.parallel.context import tp_axes_for

    def fit(ax, dim, group_aligned=False):
        if not ax or ax not in mesh.axis_names:
            return None
        size = mesh.shape[ax]
        if group_aligned:
            return ax if dim % (_GROUP * size) == 0 else None
        return ax if dim % size == 0 else None

    def rule(path, leaf):
        names = [key_entry_str(p) for p in path]
        name = names[-1]
        full = leaf.shape
        if name in ("ka", "kscale", "tscale", "bits") and len(names) >= 2 \
                and len(full) >= 2:
            ka_ax, n_ax = tp_axes_for(names[-2])
            spec = [None] * len(full)
            if name == "ka":
                spec[-2] = fit(ka_ax, full[-2], group_aligned=True)
                spec[-1] = fit(n_ax, full[-1])
            elif name == "kscale":  # ng rows follow the group-aligned K shards
                spec[-2] = fit(ka_ax, full[-2] * _GROUP, group_aligned=True)
                spec[-1] = fit(n_ax, full[-1])
            else:  # tscale (..., N, 1) / bits (..., N, ng): per-column rows
                spec[-2] = fit(n_ax, full[-2])
            ea = _expert_axis(mesh, names, leaf)
            if ea is not None and len(full) >= 3:
                spec[1 if "units" in names else 0] = ea
            return P(*spec)
        ka_ax, n_ax = tp_axes_for(name)
        if (ka_ax or n_ax) and len(full) >= 2:
            spec = [None] * len(full)
            spec[-2] = fit(ka_ax, full[-2])
            spec[-1] = fit(n_ax, full[-1])
            ea = _expert_axis(mesh, names, leaf)
            if ea is not None and len(full) >= 3:
                spec[1 if "units" in names else 0] = ea
            return P(*spec)
        return P(*([None] * len(full)))

    return jax.tree_util.tree_map_with_path(rule, params)


def named(mesh: Mesh, pspec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))
