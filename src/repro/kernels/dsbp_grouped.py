"""Pallas TPU kernel: the one-pass DSBP GEMM over row blocks of many experts.

A mixture-of-experts layer multiplies each expert's rows by that expert's
weights.  The caller sorts the (token, expert) rows by expert and pads each
expert's group to a multiple of the row block ``bm``; every row block then
belongs to one expert, named by a block -> expert table.  The table, the
count of used blocks and the layer index are scalar-prefetched
(``PrefetchScalarGridSpec``), so the weight operands' ``index_map`` picks
the block's expert of that layer from the stacked ``(U, E, K', N)``
kernel-layout containers, and the grid stays static while the routing
changes every step.  A layer loop hands the kernel the whole stack and its
index: no layer's experts are sliced out of it.

Each grid step is the body of ``kernels/dsbp_fused.py`` with the whole
reduction in one tile: the on-the-fly input path (FP8 quantize + DSBP
predict + align) runs once per row block, at its first output tile, and is
kept scale-folded in a VMEM scratch for the block's other output tiles;
the weight tile's power-of-two scales fold in VMEM and the MXU dot runs at
``Precision.HIGHEST``.  So a block's rows get, bit for bit, what
``dsbp_matmul_ref`` gives them against their expert's weights under the
same tensor scale (tests/test_moe_grouped.py).

Blocks at or past the used count do no work: their index maps repeat the
last used block's operand tiles, so no new tile is fetched, and their
output tiles go to one spare block past the end, which is dropped.  Rows of
unused blocks in the returned array are left unwritten; callers read only
the rows they routed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dsbp import DSBPConfig

from . import backend
from .fp8_quant_align import quant_align_tile

GROUP = 64
# a 128-row block's whole reduction at K = 2304 and its input path's
# temporaries outgrow the default scoped VMEM (16 MiB of a v5e core's 128)
_VMEM_LIMIT = 64 * 2**20

__all__ = ["dsbp_grouped_kernel_call", "GROUP"]


def _kernel(be_ref, nu_ref, unit_ref, x_ref, ts_ref, aw_ref, sw_ref, tw_ref,
            o_ref, ae_ref, *, cfg: DSBPConfig):
    del be_ref, unit_ref  # read by the index maps only
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < nu_ref[0])
    def _block():
        # on-the-fly input path once per row block, kept in VMEM
        @pl.when(j == 0)
        def _input_path():
            ts = ts_ref[0, 0]
            a, s, _bits = quant_align_tile(
                x_ref[...].astype(jnp.float32) * ts, cfg)
            ae_ref[...] = a * (s / ts)

        k, bn = aw_ref.shape
        we = (
            aw_ref[...].astype(jnp.float32).reshape(k // GROUP, GROUP, bn)
            * (sw_ref[...] / tw_ref[...])[:, None, :]
        ).reshape(k, bn)
        o_ref[...] = jnp.dot(ae_ref[...], we,
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("cfg", "bm", "bn", "interpret"))
def dsbp_grouped_kernel_call(
    x: jax.Array,
    ts: jax.Array,
    aw: jax.Array,
    sw: jax.Array,
    tw: jax.Array,
    block_expert: jax.Array,
    n_used: jax.Array,
    unit: jax.Array,
    cfg: DSBPConfig,
    *,
    bm: int,
    bn: int = 256,
    interpret: bool | None = None,
):
    """Grouped one-pass DSBP GEMM.

    x            (R, K')        f32 rows sorted by expert, each expert's
                                group padded with zero rows to a multiple
                                of ``bm`` (R a multiple of ``bm``)
    ts           ()/(1,1)       f32 power-of-two input tensor scale, shared
                                by every row
    aw           (U, E, K', N)  int8 kernel-layout mantissas (``.ka``) of
                                U stacked layers' experts
    sw           (U, E, ng, N)  f32 group scales (``.kscale``)
    tw           (U, E, 1, N)   f32 power-of-two per-channel weight scales
    block_expert (R/bm,)        int32 expert of each row block
    n_used       (1,)           int32 row blocks that hold routed rows; the
                                blocks past it are skipped
    unit         (1,)           int32 layer of the stack to use
    -> (R, N) f32; rows of skipped blocks are unwritten.

    The reduction runs whole in each grid step (one K tile), so a row's
    result is the very reduction ``dsbp_matmul_ref`` computes, and the
    group scales' block spans every group (a legal block for any count of
    groups).  The stack is read in place: a layer loop passes the whole
    stack and its index, and no layer's weights are sliced out.
    """
    if interpret is None:
        interpret = backend.interpret_default()
    r, k = x.shape
    n_unit, n_exp, _, n = aw.shape
    ng = k // GROUP
    assert r % bm == 0 and k % GROUP == 0, (x.shape, bm)
    assert aw.shape[2] == k and sw.shape == (n_unit, n_exp, ng, n), (
        aw.shape, sw.shape)
    assert tw.shape == (n_unit, n_exp, 1, n), tw.shape
    bn = min(bn, n)
    assert n % bn == 0
    nb, nj = r // bm, n // bn
    ts = jnp.asarray(ts, jnp.float32).reshape(1, 1)

    def blk(i, nu):
        """(block's live flag, the block whose operands it reads)."""
        live = i < nu[0]
        return live, jnp.where(live, i, jnp.maximum(nu[0] - 1, 0))

    def x_map(i, j, be, nu, u):
        return blk(i, nu)[1], 0

    def w_map(i, j, be, nu, u):
        live, ib = blk(i, nu)
        return u[0], be[ib], 0, jnp.where(live, j, nj - 1)

    def o_map(i, j, be, nu, u):
        live, _ = blk(i, nu)
        return jnp.where(live, i, nb), jnp.where(live, j, nj - 1)

    y = pl.pallas_call(
        functools.partial(_kernel, cfg=cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nb, nj),
            in_specs=[
                pl.BlockSpec((bm, k), x_map),
                pl.BlockSpec((1, 1), lambda i, j, be, nu, u: (0, 0)),
                pl.BlockSpec((None, None, k, bn), w_map),
                pl.BlockSpec((None, None, ng, bn), w_map),
                pl.BlockSpec((None, None, 1, bn), w_map),
            ],
            out_specs=pl.BlockSpec((bm, bn), o_map),
            # the row block's scale-folded aligned input
            scratch_shapes=[pltpu.VMEM((bm, k), jnp.float32)],
        ),
        # one spare row block past the end takes the skipped blocks' tiles
        out_shape=jax.ShapeDtypeStruct((r + bm, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(block_expert.astype(jnp.int32), n_used.astype(jnp.int32).reshape(1),
      jnp.asarray(unit, jnp.int32).reshape(1), x, ts, aw, sw, tw)
    return y[:r]
