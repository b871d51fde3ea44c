"""Pack-once DSBP weight representation + the quantized-linear-method
registry (DESIGN.md §2).

The paper computes the weight path **offline** ("For weights, B_g can be
calculated offline and rounded to the nearest valid bitwidth") and only the
input path on-the-fly.  :class:`PackedDSBPWeight` is that offline product as
a first-class, pytree-registered container.  Since layout v2 (DESIGN.md §8)
the arrays are stored in **kernel layout** — exactly the operand shapes the
Pallas GEMM consumes, so the serving path performs zero per-call relayout:

  ka      int8  (..., K', N)      aligned mantissas, reduction axis leading
                                  (sign applied; weights are <= 7 magnitude
                                  bits + sign -> int8); K' = n_g * G is the
                                  group-padded reduction width
  kscale  f32   (..., n_g, N)     per-64-group scales (powers of two)
  tscale  f32                     per-channel (N, 1) or per-tensor () scale
  bits    int8  (..., N, n_g)     predicted aligned widths B_g (stats/energy)

plus static metadata: the **logical** GEMM shape ``(k, n)`` (so K-padding
up to a multiple of the group is explicit, not recovered by slicing), the
group size, the :class:`~repro.core.quantized.QuantizedMatmulConfig`
the weights were packed under (so consumers know which on-the-fly input
path pairs with them), and the layout ``version``.  The legacy v1 layout
(``a (..., N, n_g, G)`` / ``scale (..., N, n_g)``) remains available as the
derived read-only views :attr:`PackedDSBPWeight.a` /
:attr:`PackedDSBPWeight.scale` (a pure, bit-exact permutation) for the
reference numerics path; v1 checkpoints load and upgrade transparently
(``checkpoint/store.py``).

Because the container is a pytree node it flows transparently through
``jax.jit`` / ``lax.scan`` (stacked per-unit params), ``jax.tree`` utils,
sharding constraints, and the checkpoint store.

The **registry** follows the vLLM ``FP8Config``/``FP8LinearMethod``
pattern: a named :class:`QuantMethod` decides how ``models.layers.dense``
executes a projection —

  dense_bf16   plain einsum, no quantization
  dsbp_ref     reference DSBP numerics (jnp grouped int contraction; STE
               backward for QAT on raw weights)
  dsbp_kernel  Pallas TPU kernels (two passes: quant-align, then the
               grouped int GEMM, with the aligned ints through HBM)
  dsbp_fused   single-pass Pallas kernel: quantize + predict + align +
               scale-folded MXU dot in one VMEM-resident body (the serving
               default, DESIGN.md §8)

``models.layers.Quant`` resolves a method once per forward; ``dense()``
dispatches through it instead of isinstance-checking dict layouts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.tree_util import GetAttrKey

__all__ = [
    "PackedDSBPWeight",
    "LAYOUT_VERSION",
    "to_kernel_layout",
    "draft_view",
    "pack_weights_sharded",
    "QuantMethod",
    "register_quant_method",
    "get_quant_method",
    "quant_method_names",
    "key_entry_str",
    "packed_nbytes",
    "tree_is_packed",
]

# Bumped whenever the container's stored array layout changes.  v1 stored
# the macro's per-column (N, n_g, G) mantissas; v2 stores the kernel-layout
# (K', N) operands directly (DESIGN.md §8).  The checkpoint store upgrades
# v1 trees on restore.
LAYOUT_VERSION = 2


def to_kernel_layout(a, scale=None):
    """Relayout the macro's per-column weight fields into kernel operands.

    ``a (..., N, n_g, G)`` aligned mantissas and ``scale (..., N, n_g)``
    group scales become ``ka (..., K', N)`` / ``kscale (..., n_g, N)`` — the
    exact shapes :func:`repro.kernels.dsbp_matmul.dsbp_matmul_kernel_call`
    and the fused kernel take.  A pure permutation (bit-exact), run ONCE at
    pack time (or at v1-checkpoint upgrade, where the fields may arrive one
    at a time — ``scale=None`` returns ``kscale=None``); works on numpy and
    jax arrays.
    """
    lead = a.shape[:-3]
    n, ng, g = a.shape[-3:]
    ka = a.reshape(*lead, n, ng * g).swapaxes(-1, -2)
    return ka, None if scale is None else scale.swapaxes(-1, -2)


@jax.tree_util.register_pytree_with_keys_class
class PackedDSBPWeight:
    """Offline-quantized DSBP weight for a logical ``(k, n)`` GEMM.

    Leading axes (stacked scan units, MoE experts) are carried by the
    array children; ``k``/``n``/``group_size``/``cfg`` are static aux data,
    so ``lax.scan`` can unstack a container along its leading axis and the
    per-slice container keeps the same logical metadata.
    """

    __slots__ = ("ka", "kscale", "tscale", "bits", "k", "n", "group_size",
                 "cfg", "version")

    def __init__(self, ka, kscale, tscale, bits, *, k, n, group_size, cfg,
                 version: int = LAYOUT_VERSION):
        self.ka = ka
        self.kscale = kscale
        self.tscale = tscale
        self.bits = bits
        self.k = k
        self.n = n
        self.group_size = group_size
        self.cfg = cfg
        self.version = version

    # ---- pytree protocol ----

    def tree_flatten_with_keys(self):
        children = [
            (GetAttrKey("ka"), self.ka),
            (GetAttrKey("kscale"), self.kscale),
            (GetAttrKey("tscale"), self.tscale),
            (GetAttrKey("bits"), self.bits),
        ]
        aux = (self.k, self.n, self.group_size, self.cfg, self.version)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, n, group_size, cfg = aux[:4]
        version = aux[4] if len(aux) > 4 else LAYOUT_VERSION
        ka, kscale, tscale, bits = children
        return cls(ka, kscale, tscale, bits, k=k, n=n, group_size=group_size,
                   cfg=cfg, version=version)

    # ---- derived geometry ----

    @property
    def n_groups(self) -> int:
        return self.kscale.shape[-2]

    @property
    def padded_k(self) -> int:
        """K rounded up to a multiple of the group (zero-filled lanes)."""
        return self.ka.shape[-2]

    @property
    def nbytes(self) -> int:
        return packed_nbytes(self)

    # ---- legacy (v1) layout views — the macro's per-column storage ----

    @property
    def a(self) -> jax.Array:
        """Legacy ``(..., N, n_g, G)`` aligned-mantissa view (bit-exact
        permutation of :attr:`ka`); consumed by the reference numerics path
        (``core.quantized.grouped_int_matmul``).  The serving kernels take
        :attr:`ka` directly — never this view."""
        lead = self.ka.shape[:-2]
        kp, n = self.ka.shape[-2:]
        g = self.group_size
        return jnp.swapaxes(self.ka, -1, -2).reshape(*lead, n, kp // g, g)

    @property
    def scale(self) -> jax.Array:
        """Legacy ``(..., N, n_g)`` group-scale view of :attr:`kscale`."""
        return jnp.swapaxes(self.kscale, -1, -2)

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"PackedDSBPWeight(k={self.k}, n={self.n}, "
                f"group={self.group_size}, v{self.version}, "
                f"ka={getattr(self.ka, 'shape', None)})")

    # ---- dequantization (weight-only consumption) ----

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        """Back to a dense ``(..., k, n)`` matrix (weight-only quantization:
        dequantization error only, activations untouched).

        The logical ``k`` is sliced off the padded reduction axis here —
        explicitly, from the container's metadata — instead of trusting the
        caller's activation width.  Kernel layout makes this transpose-free:
        ``ka`` already is ``(..., K', N)``.
        """
        deq = self.ka.astype(dtype) * jnp.repeat(
            self.kscale.astype(dtype), self.group_size, axis=-2
        )
        ts = jnp.asarray(self.tscale).astype(dtype)
        if ts.ndim >= 2:  # per-channel (..., N, 1) -> (..., 1, N)
            ts = jnp.swapaxes(ts, -1, -2)
        if ts.ndim < deq.ndim:  # per-tensor () or leading (L,) -> broadcast
            ts = ts.reshape(*ts.shape, *([1] * (deq.ndim - ts.ndim)))
        return (deq / ts)[..., : self.k, :]


def draft_view(pw: PackedDSBPWeight, draft_bits: int) -> PackedDSBPWeight:
    """MSB-slice view of a packed container: the top ``draft_bits`` magnitude
    bits of every aligned mantissa, as a new :class:`PackedDSBPWeight`
    (DESIGN.md §10).

    The macro's precision-scalable INT MAC array decomposes a B_g-bit
    aligned weight into 2b column slices fused by shift-and-add, so the top
    slices of the stored container already ARE a functional low-bit model.
    This derives that model in software: per group, drop the bottom
    ``s_g = max(B_g - draft_bits, 0)`` bits with an arithmetic right shift
    (the 2's-complement slice semantics: value = top_slices·2^s + remainder,
    0 <= remainder < 2^s) and multiply the group scale by exactly the
    dropped power of two:

        a'·σ' = (a >> s_g) · (σ · 2^s_g)  =  floor(a / 2^s_g)·2^s_g · σ

    The rescale is EXACT — group scales are powers of two and 2^s_g is an
    exact f32 product (the same argument DESIGN.md §8 uses for in-kernel
    scale folding) — so the only approximation is the mantissa truncation
    itself; groups already at B_g <= draft_bits pass through bit-identically
    (draft_bits=7 returns the container's exact numerics).  The result is a
    plain v2 container: it dispatches through ``packed_matmul`` /
    ``dsbp_matmul_packed`` / ``dsbp_matmul_fused`` unchanged, at the
    narrower weight width.  Derived with cheap elementwise int8/f32 ops, so
    callers trace it INSIDE their jitted step — the view lives in
    temporaries, never as a second weight tree in HBM.
    """
    if not 1 <= int(draft_bits) <= 7:
        raise ValueError(f"draft_bits must be in [1, 7], got {draft_bits}")
    from .formats import exp2i  # local import: packed.py stays dependency-light

    shift = jnp.maximum(pw.bits.astype(jnp.int32) - draft_bits, 0)
    # bits is stored per-column (..., N, n_g); the kernel-layout operands
    # need it per-group-row: (..., n_g, N) for kscale, (..., K', N) for ka
    shift_k = jnp.swapaxes(shift, -1, -2)
    ka = jnp.right_shift(  # arithmetic for signed ints: floor(a / 2^s)
        pw.ka, jnp.repeat(shift_k, pw.group_size, axis=-2).astype(jnp.int8)
    )
    kscale = pw.kscale * exp2i(shift_k)
    return PackedDSBPWeight(
        ka=ka,
        kscale=kscale,
        tscale=pw.tscale,
        bits=jnp.minimum(pw.bits, jnp.int8(draft_bits)),
        k=pw.k,
        n=pw.n,
        group_size=pw.group_size,
        cfg=pw.cfg,
        version=pw.version,
    )


def pack_weights_sharded(w, cfg, mesh, *, n_axis: str = "model"):
    """Offline pack directly into per-shard kernel layouts (DESIGN.md §11).

    Each device of ``mesh`` quantizes only its own N/s output columns of
    ``w (..., K, N)`` under ``shard_map``, so the full-size quantized
    container is never materialized on one device — the returned
    :class:`PackedDSBPWeight` holds globally-shaped arrays whose shards
    live where they will be consumed (``ka``/``kscale`` column shards,
    ``tscale``/``bits`` row shards over the same ``n_axis``).

    Bit-identical to pack-then-shard: with per-row weight scale
    granularity (every PRESETS entry packs weights with
    ``scale_granularity='row'``) the whole weight path — per-tensor scale,
    group scales, bitwidth prediction, mantissa alignment — is independent
    per output column, so packing a column shard equals slicing the global
    pack (asserted in tests/test_sharded_serving.py).  Per-tensor weight
    granularity couples the columns through the global max; that case (and
    an indivisible N or a mesh without ``n_axis``) falls back to the
    global :func:`~repro.core.quantized.pack_weights`.
    """
    from jax.sharding import PartitionSpec as P

    from . import quantized as Q  # local import: packed stays dependency-light

    if isinstance(cfg, str):
        cfg = Q.PRESETS[cfg]
    n = w.shape[-1]
    nsz = mesh.shape[n_axis] if n_axis in mesh.axis_names else 0
    if (not nsz or n % nsz
            or cfg.weight_cfg.scale_granularity != "row"):
        return Q.pack_weights(w, cfg)
    lead = (None,) * (w.ndim - 2)

    def local(wl):
        pw = Q.pack_weights(wl, cfg)
        return pw.ka, pw.kscale, pw.tscale, pw.bits

    ka, kscale, tscale, bits = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(*lead, None, n_axis),),
        out_specs=(
            P(*lead, None, n_axis),   # ka     (..., K', N)
            P(*lead, None, n_axis),   # kscale (..., n_g, N)
            P(*lead, n_axis, None),   # tscale (..., N, 1) per-channel
            P(*lead, n_axis, None),   # bits   (..., N, n_g)
        ),
    )(jnp.asarray(w))
    return PackedDSBPWeight(
        ka=ka, kscale=kscale, tscale=tscale, bits=bits,
        k=w.shape[-2], n=n, group_size=cfg.weight_cfg.group_size, cfg=cfg,
    )


def key_entry_str(entry) -> str:
    """Stable string for one pytree key-path entry: dict key (DictKey),
    sequence index (SequenceKey), or attribute name (GetAttrKey — the
    fields of a PackedDSBPWeight flatten with attribute paths).  Shared by
    the checkpoint store and the sharding constraints so both name the same
    leaf identically."""
    for attr in ("key", "idx", "name"):
        v = getattr(entry, attr, None)
        if v is not None:
            return str(v)
    return str(entry)


def packed_nbytes(tree) -> int:
    """Total bytes of every array leaf (packed containers included)."""
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


def tree_is_packed(tree) -> bool:
    """True if any leaf of ``tree`` is a :class:`PackedDSBPWeight`."""
    is_pw = lambda x: isinstance(x, PackedDSBPWeight)
    return any(is_pw(l) for l in jax.tree.leaves(tree, is_leaf=is_pw))


# ---------------------------------------------------------------------------
# Quantized-linear-method registry
# ---------------------------------------------------------------------------

class QuantMethod:
    """How a projection executes: pack its weight, and apply x @ w.

    ``apply(w, x, cfg, name=None)`` computes the logical
    ``x (..., K) @ w (K, N)``; ``w`` is either a raw array or a
    :class:`PackedDSBPWeight`, and ``cfg`` is the active
    :class:`QuantizedMatmulConfig` (None = no activation quantization,
    i.e. weight-only consumption of packed weights).  ``name`` is the
    projection's parameter name ('wq', 'wo', ...) when the call site knows
    it — the sharded method keys the tensor-parallel plan
    (``parallel.context.tp_axes_for``) off it; every other method ignores
    it.

    The base class owns the common dispatch — packed weights without a cfg
    dequantize (weight-only), raw weights without a cfg run the plain
    einsum — and subclasses implement only their two quantized paths.
    """

    name: str = "?"

    def pack(self, w, cfg):
        """Offline weight representation for this method (default: raw)."""
        del cfg
        return w

    def apply(self, w, x, cfg, name=None):
        if isinstance(w, PackedDSBPWeight):
            if cfg is None:
                return _einsum(w.dequantize(x.dtype), x)
            return self._apply_packed(w, x, cfg, name=name)
        if cfg is None:
            return _einsum(w, x)
        return self._apply_raw(w, x, cfg, name=name)

    def _apply_packed(self, pw, x, cfg, name=None):
        raise NotImplementedError

    def _apply_raw(self, w, x, cfg, name=None):
        raise NotImplementedError


_REGISTRY: dict[str, QuantMethod] = {}


def register_quant_method(cls):
    """Class decorator: instantiate and register under ``cls.name``."""
    _REGISTRY[cls.name] = cls()
    return cls


def get_quant_method(name: str) -> QuantMethod:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown quant method {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def quant_method_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _einsum(w, x):
    return jnp.einsum("...k,kn->...n", x, w)


@register_quant_method
class DenseBF16Method(QuantMethod):
    """No quantization: the bf16/f32 einsum baseline."""

    name = "dense_bf16"

    def apply(self, w, x, cfg, name=None):
        del cfg, name
        if isinstance(w, PackedDSBPWeight):
            w = w.dequantize(x.dtype)
        return _einsum(w, x)


@register_quant_method
class DSBPRefMethod(QuantMethod):
    """Reference DSBP numerics (core.quantized, bit-exact macro oracle).

    * packed weight + cfg  -> true integer path: on-the-fly input
      quantization + grouped int contraction off the packed form (no weight
      re-quantization, bit-exact vs ``dsbp_matmul_ref``);
    * raw weight + cfg     -> ``dsbp_matmul_ste`` (QAT: quantized forward,
      straight-through backward);
    * no cfg (base class)  -> weight-only dequantization / plain einsum.
    """

    name = "dsbp_ref"

    def pack(self, w, cfg):
        from . import quantized as Q

        return Q.pack_weights(w, cfg)

    def _apply_packed(self, pw, x, cfg, name=None):
        from . import quantized as Q

        return Q.packed_matmul(x, pw, input_cfg=cfg.input_cfg).astype(x.dtype)

    def _apply_raw(self, w, x, cfg, name=None):
        from . import quantized as Q

        return Q.dsbp_matmul_ste(x, w, cfg).astype(x.dtype)


@register_quant_method
class DSBPKernelMethod(QuantMethod):
    """Pallas TPU kernels: fused quant-align (VPU) + grouped int GEMM (MXU).

    Packed weights skip per-call quantization entirely — the int8 aligned
    mantissas feed the GEMM kernel directly (``ops.dsbp_matmul_packed``),
    with the *active* config's input path (so a preset override behaves
    like dsbp_ref).  Raw weights keep STE gradients (``ops``' STE wrapper)
    so QAT trains through the kernel forward too.
    """

    name = "dsbp_kernel"

    def pack(self, w, cfg):
        from . import quantized as Q

        return Q.pack_weights(w, cfg)

    def _apply_packed(self, pw, x, cfg, name=None):
        from repro.kernels import ops as kops  # local import: optional dep

        return kops.dsbp_matmul_packed(
            x, pw, input_cfg=cfg.input_cfg
        ).astype(x.dtype)

    def _apply_raw(self, w, x, cfg, name=None):
        from repro.kernels import ops as kops

        return kops.dsbp_matmul_ste(x, w, cfg).astype(x.dtype)


@register_quant_method
class DSBPFusedMethod(QuantMethod):
    """One-pass Pallas kernel: FP8 quantize + DSBP predict + align + MAC
    fused into a single GEMM body (DESIGN.md §8).

    The aligned-mantissa intermediate, its group scales and the bits map
    never leave VMEM, and the power-of-two tensor scales of both operands
    are folded into the group scales inside the kernel — no pre-multiply or
    final division pass.  Packed weights feed the kernel their stored
    kernel-layout ``(K', N)`` mantissas with zero per-call relayout; raw
    weights pack per call with STE gradients (QAT trains through the fused
    forward).  Bit-exact vs ``dsbp_matmul_ref`` under the default RNE path
    (tests/test_fused.py), so swapping methods can never change served
    tokens.
    """

    name = "dsbp_fused"

    def pack(self, w, cfg):
        from . import quantized as Q

        return Q.pack_weights(w, cfg)

    def _apply_packed(self, pw, x, cfg, name=None):
        from repro.kernels import ops as kops  # local import: optional dep

        return kops.dsbp_matmul_fused(
            x, pw, input_cfg=cfg.input_cfg
        ).astype(x.dtype)

    def _apply_raw(self, w, x, cfg, name=None):
        from repro.kernels import ops as kops

        return kops.dsbp_matmul_fused_ste(x, w, cfg).astype(x.dtype)


@register_quant_method
class DSBPFusedShardedMethod(DSBPFusedMethod):
    """The fused one-pass kernel under ``shard_map`` (DESIGN.md §11).

    When a sharding context is active (``parallel.context.sharding_ctx`` —
    the multi-device Engine traces prefill/decode inside one), each packed
    projection runs :func:`repro.kernels.ops.dsbp_matmul_fused_sharded`
    with the Megatron split from ``tp_axes_for(name)``: wq/wk/wv/w1/w3-
    style projections column-parallel over their N shards (no collective),
    wo/w2/w_out-style row-parallel over group-aligned K shards with ONE
    ``psum`` folded after the in-kernel scale division — bit-exact vs the
    single-device path, so a mesh can never change served tokens.  Token
    rows additionally shard over the context's batch axes (data
    parallelism).  Without a context (or for an unnamed projection on a
    1-axis mesh) this degrades exactly to 'dsbp_fused'.
    """

    name = "dsbp_fused_sharded"

    def _apply_packed(self, pw, x, cfg, name=None):
        from repro.parallel import context as PC  # local: avoid import cycle

        ctx = PC.active_ctx()
        if ctx is None or getattr(pw.ka, "ndim", 2) != 2:
            return super()._apply_packed(pw, x, cfg, name=name)
        from repro.kernels import ops as kops

        k_axis, n_axis = PC.tp_axes_for(name)
        return kops.dsbp_matmul_fused_sharded(
            x, pw, ctx["mesh"], input_cfg=cfg.input_cfg,
            batch_axis=ctx["batch_axes"], k_axis=k_axis, n_axis=n_axis,
        ).astype(x.dtype)
