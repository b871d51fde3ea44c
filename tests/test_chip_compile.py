"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: lane splits Mosaic
cannot lay out, blocks that break the (8, 128) rule, kernels that overflow
VMEM.  These tests compile the serving GEMM at yi-9b's widths, and one
jitted yi-9b decode layer, for one chip of a described ``v5e:2x2``
topology.  Nothing runs, so they say nothing about results or time.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.quantized import PRESETS
from repro.kernels import backend
from repro.kernels.dsbp_fused import dsbp_fused_kernel_call
from repro.models import blocks
from repro.models.layers import Quant
from repro.serve.engine import pack_tree

# yi-9b's projection (K, N): wq/wo, wk/wv, w1/w3, w2, then the serving
# path's fused qkv and mlp_in (w1 | w3), whose 20 and 86 output tiles
# reuse the aligned input of their row block
YI_KN = [(4096, 4096), (4096, 512), (4096, 11008), (11008, 4096),
         (4096, 5120), (4096, 22016)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile_fused_gemm(sharding, m, k, n):
    cfg = PRESETS["precise"].input_cfg
    args = _abstract([
        jax.ShapeDtypeStruct((m, k), jnp.float32),   # x
        jax.ShapeDtypeStruct((1, 1), jnp.float32),   # ts
        jax.ShapeDtypeStruct((k, n), jnp.int8),      # ka
        jax.ShapeDtypeStruct((k // 64, n), jnp.float32),  # kscale
        jax.ShapeDtypeStruct((1, n), jnp.float32),   # tscale
    ], sharding)
    return jax.jit(lambda *a: dsbp_fused_kernel_call(
        *a, cfg, interpret=False)).lower(*args).compile()


@pytest.mark.parametrize("m", [8, 256, 128])  # decode, two blocks, verify
@pytest.mark.parametrize("k,n", YI_KN)
def test_fused_gemm_compiles_for_v5e(one_chip, m, k, n):
    compiled = _compile_fused_gemm(one_chip, m, k, n)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_gemm_long_reduction_compiles_for_v5e(one_chip):
    """grok-1's mlp_out reduction (K = 32768) at a verify row block: the
    row block's aligned input alone (16 MiB of f32) fills the default
    scoped VMEM, so the kernel raises its limit by that much."""
    compiled = _compile_fused_gemm(one_chip, 128, 32768, 6144)
    assert "tpu_custom_call" in compiled.as_text()


def test_yi9b_decode_layer_compiles_for_v5e(one_chip, monkeypatch):
    """One packed yi-9b layer, decode step at batch 4: the serving path's
    kernels (selected from the backend, here steered to compiled) and the
    XLA ops around them compile for the chip."""
    monkeypatch.setattr(backend, "interpret_default", lambda: False)
    cfg = get_config("yi-9b").replace(quant="precise",
                                      quant_method="dsbp_fused")
    b, max_len = 4, 528
    params = jax.eval_shape(
        lambda key: pack_tree(blocks.init_layer(key, cfg, "attn_full",
                                                jnp.float32), cfg.quant),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: blocks.init_layer_cache(
        cfg, "attn_full", b, max_len, jnp.float32))
    args = _abstract([
        params,
        jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.float32),
        cache,
        jax.ShapeDtypeStruct((b,), jnp.int32),
    ], one_chip)
    quant = Quant(cfg.quant, cfg.quant_method)
    compiled = jax.jit(lambda p, x, c, pos: blocks.layer_decode(
        p, x, cfg, "attn_full", c, pos, quant)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# Mellum2's expert GEMMs (K, N): gate and up side by side, then down, at
# a decode pass's rows (32 tokens x top-8, row block 8) and a chunk
# pass's (512 x 8, row block 128), over a 4-layer stack read in place
MELLUM_KN = [(2304, 1792), (896, 2304)]


@pytest.mark.parametrize("rows,bm", [(704, 8), (12288, 128)])
@pytest.mark.parametrize("k,n", MELLUM_KN)
def test_grouped_gemm_compiles_for_v5e(one_chip, rows, bm, k, n):
    from repro.kernels.dsbp_grouped import dsbp_grouped_kernel_call

    cfg = PRESETS["precise"].input_cfg
    args = _abstract([
        jax.ShapeDtypeStruct((rows, k), jnp.float32),            # x
        jax.ShapeDtypeStruct((1, 1), jnp.float32),               # ts
        jax.ShapeDtypeStruct((4, 64, k, n), jnp.int8),           # ka
        jax.ShapeDtypeStruct((4, 64, k // 64, n), jnp.float32),  # kscale
        jax.ShapeDtypeStruct((4, 64, 1, n), jnp.float32),        # tscale
        jax.ShapeDtypeStruct((rows // bm,), jnp.int32),          # table
        jax.ShapeDtypeStruct((1,), jnp.int32),                   # used
        jax.ShapeDtypeStruct((1,), jnp.int32),                   # layer
    ], one_chip)
    compiled = jax.jit(lambda *a: dsbp_grouped_kernel_call(
        *a, cfg, bm=bm, interpret=False)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "dsbp_grouped_kernel_call" in text
