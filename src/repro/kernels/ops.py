"""Jit'd public wrappers around the Pallas kernels.

Every ``interpret`` argument defaults to None, which the kernel entry
resolves from the backend (``kernels/backend.py``): compiled by Mosaic on a
TPU, interpreted (the kernel body run op by op) on the CPU backend.

Weight handling mirrors the macro (DESIGN.md §2/§8): ``dsbp_matmul_fused``
is the serving entry point — ONE kernel runs quantize + predict + align +
MAC off a :class:`PackedDSBPWeight`'s stored kernel-layout operands, with
no intermediate tensors and no per-call weight relayout.
``dsbp_matmul_packed`` is the two-kernel variant (separate input-path and
GEMM kernels, aligned ints through HBM) kept as the fused path's
cross-check and the K-tiling fallback; ``dsbp_matmul`` is the
pack-per-call convenience wrapper.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dsbp import DSBPConfig
from repro.core.formats import per_tensor_scale
from repro.core.packed import PackedDSBPWeight
from repro.core.quantized import QuantizedMatmulConfig, pack_weights

from . import dsbp_fused as _df
from . import dsbp_matmul as _dm
from . import fp8_quant_align as _qa
from . import flash_attention as _fa

__all__ = [
    "dsbp_matmul",
    "dsbp_matmul_packed",
    "dsbp_matmul_fused",
    "dsbp_matmul_fused_sharded",
    "dsbp_matmul_ste",
    "dsbp_matmul_fused_ste",
    "fp8_quant_align",
    "flash_attention",
    "packed_flash_attention",
    "count_weight_transposes",
    "count_kv_dequants",
    "quant_sat_stats",
]


def count_weight_transposes(fn, *args, min_size: int) -> int:
    """Transpose primitives over arrays of >= min_size elements anywhere in
    ``fn``'s traced computation (pjit/pallas bodies included).

    This is the checkable form of the no-relayout contract (DESIGN.md §8):
    a packed serving call must never permute a weight-sized array per call
    — the kernel-layout operands come straight from the container.  Used by
    tests/test_fused.py and the CI bench gate
    (``benchmarks.bench_kernels.bench_fused_vs_two_kernel``).
    """
    from jax.extend.core import ClosedJaxpr, Jaxpr

    stack = [jax.make_jaxpr(fn)(*args).jaxpr]
    count = 0

    def push(v):
        if isinstance(v, ClosedJaxpr):
            stack.append(v.jaxpr)
        elif isinstance(v, Jaxpr):
            stack.append(v)
        elif isinstance(v, (list, tuple)):
            for item in v:
                push(item)

    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            if (eqn.primitive.name == "transpose"
                    and eqn.invars[0].aval.size >= min_size):
                count += 1
            for p in eqn.params.values():
                push(p)
    return count


@partial(jax.jit, static_argnames=("cfg", "interpret"))
def fp8_quant_align(x: jax.Array, cfg: DSBPConfig, interpret: bool | None = None):
    """On-the-fly input path: (M,K) f32 -> aligned ints, scales, bits."""
    ts = per_tensor_scale(x, cfg.fmt)
    a, s, b = _qa.fp8_quant_align_kernel_call(x * ts, cfg, interpret=interpret)
    return {"a": a, "scale": s, "bits": b, "tscale": ts}


@partial(jax.jit, static_argnames=("input_cfg", "interpret", "folded"))
def dsbp_matmul_packed(
    x: jax.Array,
    pw: PackedDSBPWeight,
    input_cfg: DSBPConfig | None = None,
    interpret: bool | None = None,
    folded: bool = True,
):
    """Pre-packed DSBP GEMM: x (..., K) @ packed(K, N) -> (..., N) f32.

    The Pallas GEMM takes the stored int8 aligned mantissas + per-group
    scales directly — no per-call weight quantization.  The input path runs
    under ``input_cfg`` (default: the config the weights were packed with).
    K is the container's *logical* reduction width; activations are
    zero-padded here up to the packed (group-aligned) K', exactly mirroring
    the zero lanes the weights were packed with.
    """
    _check_packed_2d(pw, x, "dsbp_matmul_packed")
    batch = x.shape[:-1]
    n = pw.n
    icfg = input_cfg if input_cfg is not None else pw.cfg.input_cfg
    xm = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    if pw.padded_k != pw.k:
        xm = jnp.pad(xm, ((0, 0), (0, pw.padded_k - pw.k)))
    qx = fp8_quant_align(xm, icfg, interpret=interpret)
    # kernel-layout operands straight from the container: no relayout
    y = _dm.dsbp_matmul_kernel_call(
        qx["a"], qx["scale"], pw.ka, pw.kscale, interpret=interpret,
        folded=folded,
    )
    tw = pw.tscale.reshape(1, -1) if jnp.ndim(pw.tscale) else pw.tscale
    return (y / (qx["tscale"] * tw)).reshape(*batch, n)


def _check_packed_2d(pw: PackedDSBPWeight, x: jax.Array, name: str) -> None:
    if pw.ka.ndim != 2:
        raise ValueError(
            f"{name} needs a 2-D logical weight; got leading "
            f"axes {pw.ka.shape[:-2]} (vmap over them instead)"
        )
    if x.shape[-1] != pw.k:
        raise ValueError(
            f"activation K={x.shape[-1]} != packed logical K={pw.k}"
        )


@partial(jax.jit, static_argnames=("input_cfg", "interpret", "bm", "bn", "bk"))
def dsbp_matmul_fused(
    x: jax.Array,
    pw: PackedDSBPWeight,
    input_cfg: DSBPConfig | None = None,
    interpret: bool | None = None,
    bm: int = 128,
    bn: int = 256,
    bk: int | None = None,
):
    """Fused one-pass DSBP GEMM: x (..., K) @ packed(K, N) -> (..., N) f32.

    The serving hot path (DESIGN.md §8): quantize + predict + align + MAC
    run in ONE Pallas kernel, the input path once per row block and K tile
    and reused by every output tile — the aligned-int intermediate
    and its scales never touch HBM, the pow2 tensor scales of both operands
    fold into the group scales inside the kernel (no pre-multiply / final
    division pass), and the weight operands are the container's stored
    kernel-layout arrays (zero per-call relayout).  Bit-exact vs
    ``dsbp_matmul_ref`` under the default RNE path.  M is ragged-friendly
    (decode batches like B=3 auto-pad internally).
    """
    _check_packed_2d(pw, x, "dsbp_matmul_fused")
    batch = x.shape[:-1]
    icfg = input_cfg if input_cfg is not None else pw.cfg.input_cfg
    xm = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    if pw.padded_k != pw.k:  # mirror the zero lanes the weights packed with
        xm = jnp.pad(xm, ((0, 0), (0, pw.padded_k - pw.k)))
    ts = per_tensor_scale(xm, icfg.fmt)
    tsw = jnp.asarray(pw.tscale)
    tw = jnp.broadcast_to(
        tsw.reshape(1, -1) if tsw.ndim else tsw, (1, pw.n)
    ).astype(jnp.float32)
    y = _df.dsbp_fused_kernel_call(
        xm, ts, pw.ka, pw.kscale, tw, icfg,
        bm=bm, bn=bn, bk=bk, interpret=interpret,
    )
    return y.reshape(*batch, pw.n)


def dsbp_matmul_fused_sharded(
    x: jax.Array,
    pw: PackedDSBPWeight,
    mesh,
    input_cfg: DSBPConfig | None = None,
    *,
    batch_axis=None,
    k_axis: str | None = None,
    n_axis: str | None = None,
    interpret: bool | None = None,
    bm: int = 128,
    bn: int = 256,
    bk: int | None = None,
):
    """Fused DSBP GEMM under shard_map: x (..., K) @ packed(K, N) -> (..., N).

    The multi-device serving entry (DESIGN.md §11).  Same numerics contract
    as :func:`dsbp_matmul_fused` — bit-exact vs ``dsbp_matmul_ref`` — on
    ANY mesh, because: the per-tensor input scale is computed globally
    before the shard_map (per-device quantization is then bit-identical to
    the unsharded input path), K shards are group-aligned so group
    boundaries never straddle devices, and the row-parallel ``psum``
    reassociates an exact pow2-granular sum (kernels/dsbp_fused.py).

    Axis arguments name mesh axes (``parallel.context.tp_axes_for`` gives
    the per-projection plan); each is dropped — replicating that dim, the
    same fallback contract as ``parallel/sharding.py`` — when the dim does
    not divide the axis (K' additionally needs group-aligned shards) or the
    mesh lacks the axis.  ``batch_axis`` may be a tuple (('pod','data')).
    ``mesh=None`` falls back to the single-device fused path.
    """
    if mesh is None:
        return dsbp_matmul_fused(
            x, pw, input_cfg=input_cfg, interpret=interpret,
            bm=bm, bn=bn, bk=bk,
        )
    _check_packed_2d(pw, x, "dsbp_matmul_fused_sharded")
    batch = x.shape[:-1]
    icfg = input_cfg if input_cfg is not None else pw.cfg.input_cfg
    xm = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    if pw.padded_k != pw.k:  # mirror the zero lanes the weights packed with
        xm = jnp.pad(xm, ((0, 0), (0, pw.padded_k - pw.k)))
    m = xm.shape[0]

    def axis_size(ax):
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        if any(a not in mesh.axis_names for a in axes):
            return None
        return int(np.prod([mesh.shape[a] for a in axes]))

    bsz = axis_size(batch_axis) if batch_axis else None
    if not bsz or m % bsz:
        batch_axis = None
    nsz = axis_size(n_axis) if n_axis else None
    if not nsz or pw.n % nsz:
        n_axis = None
    ksz = axis_size(k_axis) if k_axis else None
    if not ksz or pw.padded_k % (_df.GROUP * ksz):
        k_axis = None
    if k_axis is not None and k_axis == n_axis:
        n_axis = None  # one axis cannot shard both operand dims
    if batch_axis is not None and k_axis is not None and (
        k_axis == batch_axis
        or (not isinstance(batch_axis, str) and k_axis in batch_axis)
    ):
        batch_axis = None  # x cannot shard M and K over the same axis

    # global pow2 input scale, replicated into every shard's kernel call
    ts = per_tensor_scale(xm, icfg.fmt)
    tsw = jnp.asarray(pw.tscale)
    tw = jnp.broadcast_to(
        tsw.reshape(1, -1) if tsw.ndim else tsw, (1, pw.n)
    ).astype(jnp.float32)
    y = _df.dsbp_fused_sharded_call(
        xm, ts, pw.ka, pw.kscale, tw, icfg, mesh,
        batch_axis=batch_axis, k_axis=k_axis, n_axis=n_axis,
        bm=bm, bn=bn, bk=bk, interpret=interpret,
    )
    return y.reshape(*batch, pw.n)


@partial(jax.jit, static_argnames=("cfg", "interpret", "folded"))
def dsbp_matmul(
    x: jax.Array,
    w: jax.Array,
    cfg: QuantizedMatmulConfig,
    interpret: bool | None = None,
    folded: bool = True,
):
    """Full DSBP GEMM through both kernels: x (..., K) @ w (K, N) -> f32.

    Convenience wrapper that packs the weight per call; the serving engine
    packs once at init (``core.quantized.pack_weights``) and calls
    :func:`dsbp_matmul_packed`, which is where the memory saving and the
    repeated-GEMM speedup land (benchmarks/bench_kernels.py).
    """
    return dsbp_matmul_packed(
        x, pack_weights(w, cfg), interpret=interpret, folded=folded
    )


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def dsbp_matmul_ste(x: jax.Array, w: jax.Array, cfg: QuantizedMatmulConfig):
    """Kernel forward, straight-through (full-precision) backward — the
    Pallas counterpart of ``core.quantized.dsbp_matmul_ste`` so QAT can
    train through the 'dsbp_kernel' method (gradients would otherwise be
    zero through the rounding/clipping ops)."""
    return dsbp_matmul(x, w, cfg)


def _ste_fwd(x, w, cfg):
    return dsbp_matmul(x, w, cfg), (x, w)


def _ste_bwd(cfg, res, g):
    x, w = res
    gx = jnp.einsum("...n,kn->...k", g, w)
    xm = x.reshape(-1, x.shape[-1])
    gm = g.reshape(-1, g.shape[-1])
    gw = jnp.einsum("mk,mn->kn", xm, gm)
    return gx.astype(x.dtype), gw.astype(w.dtype)


dsbp_matmul_ste.defvjp(_ste_fwd, _ste_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def dsbp_matmul_fused_ste(x: jax.Array, w: jax.Array, cfg: QuantizedMatmulConfig):
    """Fused-kernel forward (pack per call), straight-through backward —
    QAT through the 'dsbp_fused' method sees the exact serving numerics
    while keeping full-precision gradients."""
    return dsbp_matmul_fused(x, pack_weights(w, cfg))


def _fused_ste_fwd(x, w, cfg):
    return dsbp_matmul_fused(x, pack_weights(w, cfg)), (x, w)


dsbp_matmul_fused_ste.defvjp(_fused_ste_fwd, _ste_bwd)


def flash_attention(q, k, v, *, causal=True, window=None, interpret=None,
                    bq=128, bkv=128):
    """(B, Hq, Sq, D) x (B, Hkv, S, D) GQA flash attention via vmap."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, sq, d)

    def one(qh, kh, vh):
        return _fa.flash_attention_kernel_call(
            qh, kh, vh, causal=causal, window=window, bq=bq, bkv=bkv,
            interpret=interpret,
        )

    f = jax.vmap(jax.vmap(one, in_axes=(0, None, None)), in_axes=(0, 0, 0))
    out = jax.vmap(f, in_axes=(0, 0, 0))(qg, k, v)
    return out.reshape(b, hq, sq, d)


def packed_flash_attention(q, k, v, *, causal=True, window=None,
                           interpret=None, bq=128, bkv=128):
    """GQA flash attention over a PACKED KV cache (DESIGN.md §14).

    ``q``: (B, Hq, Sq, D); ``k``/``v``: :class:`repro.kvq.PackedKVBlock`
    with qm (B, Hkv, S, D) int8 and scale (B, Hkv, S, 1) f32.  The kernel
    consumes mantissas + scales directly — the int8 widening and the pow2
    scale folds happen in VMEM, so the traced computation contains ZERO
    int8->float converts outside the pallas_call
    (:func:`count_kv_dequants` == 0) and the KV HBM traffic is the packed
    bytes.  Bit-identical to :func:`flash_attention` over
    ``k.dequantize()``/``v.dequantize()`` (tests/test_kvq.py)."""
    b, hq, sq, d = q.shape
    hkv = k.qm.shape[1]
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, sq, d)

    def one(qh, kqm, ks, vqm, vs):
        return _fa.packed_flash_attention_kernel_call(
            qh, kqm, ks, vqm, vs, causal=causal, window=window, bq=bq,
            bkv=bkv, interpret=interpret,
        )

    f = jax.vmap(jax.vmap(one, in_axes=(0, None, None, None, None)),
                 in_axes=(0, 0, 0, 0, 0))
    out = jax.vmap(f, in_axes=(0, 0, 0, 0, 0))(
        qg, k.qm, k.scale, v.qm, v.scale)
    return out.reshape(b, hq, sq, d)


def count_kv_dequants(fn, *args, min_size: int) -> int:
    """int8 -> float ``convert_element_type`` primitives over arrays of
    >= min_size elements in ``fn``'s traced computation, NOT counting the
    bodies of pallas_call kernels.

    This is the checkable form of the dequantize-free KV contract
    (DESIGN.md §14): a packed attention step must never materialize a
    KV-sized float copy of the cache in HBM — the widening belongs INSIDE
    the kernel, on the VMEM block the DMA just landed, which is exactly
    why pallas_call bodies are excluded.  The dequantize-oracle path
    (``PackedKVBlock.dequantize`` then float attention) counts >= 1 here;
    the packed kernel path counts 0 (asserted in tests/test_kvq.py).
    """
    from jax.extend.core import ClosedJaxpr, Jaxpr

    stack = [jax.make_jaxpr(fn)(*args).jaxpr]
    count = 0

    def push(v):
        if isinstance(v, ClosedJaxpr):
            stack.append(v.jaxpr)
        elif isinstance(v, Jaxpr):
            stack.append(v)
        elif isinstance(v, (list, tuple)):
            for item in v:
                push(item)

    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                continue  # in-VMEM widening inside the kernel is the point
            if (eqn.primitive.name == "convert_element_type"
                    and eqn.invars[0].aval.dtype == jnp.int8
                    and jnp.issubdtype(eqn.outvars[0].aval.dtype,
                                       jnp.floating)
                    and eqn.invars[0].aval.size >= min_size):
                count += 1
            for p in eqn.params.values():
                push(p)
    return count


@partial(jax.jit, static_argnames=("fmt",))
def _sat_counts(x, fmt, tscale):
    from repro.core.formats import get_format

    f = get_format(fmt)
    x = x.astype(jnp.float32)
    finite = jnp.isfinite(x)
    xz = jnp.where(finite, x, 0.0)
    ts = jnp.where(tscale > 0, tscale, per_tensor_scale(xz, f))
    ax = jnp.abs(xz) * ts
    overflow = jnp.sum((ax > f.max_value) & finite)
    underflow = jnp.sum((ax > 0) & (ax < 2.0 ** f.emin) & finite)
    return overflow, underflow, jnp.sum(~finite), ts


def quant_sat_stats(x: jax.Array, cfg, tscale: float | None = None) -> dict:
    """Overflow / underflow / non-finite counts of ``x`` against a target
    FP format — the quantize-path health statistic of "FP8 Formats for
    Deep Learning" (PAPERS.md), exported via :mod:`repro.obs.health`.

    ``cfg`` is a :class:`DSBPConfig` (its ``fmt`` is used), an
    :class:`~repro.core.formats.FPFormat`, or a format name.  With
    ``tscale=None`` the per-call :func:`per_tensor_scale` is applied — by
    construction nothing overflows then, so callers tracking distribution
    SHIFT must pass a frozen scale (obs freezes the first sample's);
    overflow = ``|x|*tscale`` above the format max, underflow = non-zero
    magnitudes below the smallest normal ``2**emin``.
    """
    fmt = getattr(cfg, "fmt", None)
    if fmt is None:
        fmt = cfg if isinstance(cfg, str) else getattr(cfg, "name", str(cfg))
    ts = jnp.float32(0.0 if tscale is None else tscale)
    overflow, underflow, nonfinite, used = _sat_counts(jnp.asarray(x), fmt, ts)
    return {"overflow": int(overflow), "underflow": int(underflow),
            "nonfinite": int(nonfinite), "total": int(np.size(x)),
            "tscale": float(used)}
