"""DSBP-quantized matmul — the paper's technique as a composable JAX op.

``dsbp_matmul`` is the software equivalent of the macro's datapath:

  weights  --offline-->  FP8(fmt_w) -> group fields -> Algorithm-1 B_w
                          -> aligned ints A_w + group scales σ_w
  inputs   --on-the-fly-> FP8(fmt_i) -> group fields -> MPU B_i (Eq. 1)
                          -> aligned ints A_i + group scales σ_i
  MAC      per 64-group:  Σ_g  (A_i_g · A_w_g) · σ_i[m,g] · σ_w[n,g]

The integer dots are exact in f32 (|A_i|<2**11, |A_w|<2**7, 64-deep sums
< 2**24), so this *is* the INT MAC array result, bit-for-bit — verified
against :mod:`repro.core.mac_array` in tests.

For training, :func:`dsbp_matmul_ste` wraps the quantized forward in a
straight-through estimator so QAT "sees" the macro's numerics.

The Pallas TPU kernel in ``repro.kernels.dsbp_matmul`` implements the same
contraction with VMEM tiling; :func:`dsbp_matmul_ref` is its oracle.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import dsbp, energy
from .dsbp import DSBPConfig
from .packed import PackedDSBPWeight, to_kernel_layout

__all__ = [
    "QuantizedMatmulConfig",
    "PRESETS",
    "quantize_weights",
    "quantize_inputs",
    "grouped_int_matmul",
    "pack_weights",
    "packed_matmul",
    "dsbp_matmul_ref",
    "dsbp_matmul",
    "dsbp_matmul_ste",
    "matmul_stats",
]


@dataclasses.dataclass(frozen=True)
class QuantizedMatmulConfig:
    """Hyperparameters of one DSBP-quantized GEMM (both operand paths)."""

    input_cfg: DSBPConfig = DSBPConfig(fmt="e4m3", side="input", k=1.0, b_fix=6)
    weight_cfg: DSBPConfig = DSBPConfig(fmt="e2m5", side="weight", k=1.0,
                                        b_fix=5, scale_granularity="row")

    @property
    def mode(self) -> str:
        return "fp_dsbp" if self.input_cfg.mode == "dsbp" else "fp_fixed"


def _preset(name, k, b_in, b_w, mode="dsbp", fmt_i="e4m3", fmt_w="e2m5"):
    return QuantizedMatmulConfig(
        input_cfg=DSBPConfig(fmt=fmt_i, side="input", k=k, b_fix=b_in, mode=mode),
        weight_cfg=DSBPConfig(fmt=fmt_w, side="weight", k=k, b_fix=b_w, mode=mode,
                              scale_granularity="row"),
    )


# Table I design points. Paper quantizes Llama-7b per [10]: inputs E4M3/E5M2,
# weights E2M5.
PRESETS: dict[str, QuantizedMatmulConfig] = {
    "e5m3_fixed": _preset("e5m3_fixed", 0.0, 3, 3, mode="fixed"),
    "e5m7_fixed": _preset("e5m7_fixed", 0.0, 7, 7, mode="fixed"),
    "precise": _preset("precise", 1.0, 6, 5),
    "efficient": _preset("efficient", 2.0, 4, 4),
}


def quantize_weights(w: jax.Array, cfg: DSBPConfig):
    """Offline weight path: w is (K, N); groups along K per output column.

    Returns dict with a:(N, n_g, G) int32, scale:(N, n_g), bits:(N, n_g),
    tscale scalar — transposed so the reduction axis is last, matching the
    macro's per-column storage.
    """
    return dsbp.dsbp_quantize(w.T, cfg)


def quantize_inputs(x: jax.Array, cfg: DSBPConfig):
    """On-the-fly input path: x is (..., K); groups along K per row."""
    return dsbp.dsbp_quantize(x, cfg)


def grouped_int_matmul(qx: dict, qw: dict) -> jax.Array:
    """The INT MAC array contraction with per-group scale fusion.

    qx["a"]: (M, n_g, G) int32;  qw["a"]: (N, n_g, G) int32.
    Returns f32 (M, N) = Σ_g σx[m,g] σw[n,g] Σ_i A_x[m,g,i] A_w[n,g,i],
    descaled by the per-tensor scales.
    """
    ax = qx["a"].astype(jnp.float32)
    aw = qw["a"].astype(jnp.float32)
    # exact: products < 2**18, 64-sums < 2**24 -> f32 integer-exact, at
    # full precision (a TPU's default would round |a_x| < 2**11 to bfloat16)
    partial_ = jnp.einsum("mgi,ngi->mng", ax, aw,
                          precision=jax.lax.Precision.HIGHEST)
    scaled = partial_ * (qx["scale"][:, None, :] * qw["scale"][None, :, :])
    y = jnp.sum(scaled, axis=-1)
    tx = qx["tscale"].reshape(-1, 1) if jnp.ndim(qx["tscale"]) else qx["tscale"]
    tw = qw["tscale"].reshape(1, -1) if jnp.ndim(qw["tscale"]) else qw["tscale"]
    return y / (tx * tw)


def pack_weights(w: jax.Array, cfg: QuantizedMatmulConfig | str) -> PackedDSBPWeight:
    """Offline weight path, run ONCE: w (..., K, N) -> PackedDSBPWeight.

    ``cfg`` is a :data:`PRESETS` key or a full config; the container embeds
    it so consumers know which on-the-fly input path pairs with the packed
    weights.  Aligned mantissas are stored as int8 (weight widths are <= 7
    magnitude bits + sign) in **kernel layout** — ``ka (K', N)`` with the
    reduction axis leading, ``kscale (n_g, N)`` — so the Pallas GEMMs take
    the stored arrays with zero per-call relayout (DESIGN.md §8).  The
    logical (K, N) shape is recorded so the group padding of K is explicit,
    and leading axes (stacked scan units, MoE experts) are preserved.
    Bit-exact vs :func:`quantize_weights`: the int8 narrowing is lossless
    for every valid weight width and the relayout is a pure permutation.
    """
    if isinstance(cfg, str):
        cfg = PRESETS[cfg]
    wcfg = cfg.weight_cfg
    k, n = w.shape[-2:]
    lead = w.shape[:-2]
    wf = w.astype(jnp.float32)
    if lead:
        q = jax.vmap(lambda m: quantize_weights(m, wcfg))(wf.reshape(-1, k, n))
        q = {key: q[key].reshape(*lead, *q[key].shape[1:])
             for key in ("a", "scale", "tscale", "bits")}
    else:
        q = quantize_weights(wf, wcfg)
    ka, kscale = to_kernel_layout(q["a"].astype(jnp.int8), q["scale"])
    return PackedDSBPWeight(
        ka=ka,
        kscale=kscale,
        tscale=q["tscale"],
        bits=q["bits"].astype(jnp.int8),
        k=k,
        n=n,
        group_size=wcfg.group_size,
        cfg=cfg,
    )


@partial(jax.jit, static_argnames=("input_cfg",))
def packed_matmul(x: jax.Array, pw: PackedDSBPWeight,
                  input_cfg: DSBPConfig | None = None) -> jax.Array:
    """Grouped int contraction consuming the packed form directly.

    x (..., K) @ packed(K, N) -> (..., N) f32, with K the container's
    *logical* reduction width.  The input path runs on the fly under
    ``input_cfg`` (default: the config the weights were packed with), the
    weight path is the stored int8 mantissas — nothing is re-quantized.
    Bit-exact vs ``dsbp_matmul_ref(x, w, pw.cfg)`` when
    ``pw = pack_weights(w, pw.cfg)``.
    """
    if x.shape[-1] != pw.k:
        raise ValueError(
            f"activation K={x.shape[-1]} != packed logical K={pw.k}"
        )
    if pw.ka.ndim != 2:
        raise ValueError(
            f"packed_matmul needs a 2-D logical weight; got leading axes "
            f"{pw.ka.shape[:-2]} (vmap over them instead)"
        )
    icfg = input_cfg if input_cfg is not None else pw.cfg.input_cfg
    batch_shape = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    qx = quantize_inputs(xm, icfg)
    qw = {"a": pw.a, "scale": pw.scale, "tscale": pw.tscale}
    y = grouped_int_matmul(qx, qw)
    return y.reshape(*batch_shape, pw.n)


@partial(jax.jit, static_argnames=("cfg",))
def dsbp_matmul_ref(x: jax.Array, w: jax.Array, cfg: QuantizedMatmulConfig):
    """Reference DSBP GEMM: x (..., K) @ w (K, N) -> (..., N) f32."""
    batch_shape = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    qx = quantize_inputs(xm, cfg.input_cfg)
    qw = quantize_weights(w, cfg.weight_cfg)
    y = grouped_int_matmul(qx, qw)
    return y.reshape(*batch_shape, w.shape[-1])


def dsbp_matmul(x: jax.Array, w: jax.Array, cfg: QuantizedMatmulConfig,
                use_kernel: bool = False):
    """DSBP GEMM; ``use_kernel=True`` routes to the Pallas TPU kernel."""
    if use_kernel:
        from repro.kernels import ops as kops  # local import: optional dep

        return kops.dsbp_matmul(x, w, cfg)
    return dsbp_matmul_ref(x, w, cfg)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def dsbp_matmul_ste(x: jax.Array, w: jax.Array, cfg: QuantizedMatmulConfig):
    """Quantized forward, straight-through (full-precision) backward."""
    return dsbp_matmul_ref(x, w, cfg)


def _ste_fwd(x, w, cfg):
    return dsbp_matmul_ref(x, w, cfg), (x, w)


def _ste_bwd(cfg, res, g):
    x, w = res
    gx = jnp.einsum("...n,kn->...k", g, w)
    xm = x.reshape(-1, x.shape[-1])
    gm = g.reshape(-1, g.shape[-1])
    gw = jnp.einsum("mk,mn->kn", xm, gm)
    return gx.astype(x.dtype), gw.astype(w.dtype)


dsbp_matmul_ste.defvjp(_ste_fwd, _ste_bwd)


@partial(jax.jit, static_argnames=("cfg",))
def matmul_stats(x: jax.Array, w: jax.Array, cfg: QuantizedMatmulConfig):
    """Average aligned I/W widths (incl. sign) + modeled macro efficiency.

    This is how Table I's "Avg. I/W" column and the Fig. 7 efficiency axis
    are produced for a given layer's data.
    """
    xm = x.reshape(-1, x.shape[-1])
    qx = quantize_inputs(xm, cfg.input_cfg)
    qw = quantize_weights(w, cfg.weight_cfg)
    return {
        "avg_i_bits": dsbp.avg_total_bits(qx["bits"]),
        "avg_w_bits": dsbp.avg_total_bits(qw["bits"]),
    }


def modeled_efficiency(avg_i: float, avg_w: float, mode: str) -> dict:
    """Macro throughput/efficiency at measured average widths."""
    return {
        "tput_ops": energy.throughput_ops(avg_i, avg_w),
        "power_w": energy.power_w(avg_i, avg_w, mode),
        "eff_tops_w": energy.efficiency_tops_per_w(avg_i, avg_w, mode),
    }
