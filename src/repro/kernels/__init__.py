"""Pallas TPU kernels for the macro's perf-critical datapaths.

  dsbp_matmul     — group-aligned INT GEMM with per-64-group scales (MXU)
  fp8_quant_align — fused FP8 quantize + DSBP predict + align (VPU)
  dsbp_fused      — one-pass quantize-align-MAC GEMM (VPU input path feeds
                    the scale-folded MXU dot in VMEM; the serving default)
  flash_attention — blockwise online-softmax attention for serving

Each kernel: <name>.py (pl.pallas_call + BlockSpec) with its jnp oracle in
ref.py and the jit'd public wrapper in ops.py.  backend.py decides how they
run: interpreted on the CPU backend (where the tests validate them),
compiled by Mosaic everywhere else.
"""
from . import ops, ref  # noqa: F401
