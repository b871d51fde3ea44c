"""The one-pass quantize-align-MAC kernel (DESIGN.md §8): bit-exactness vs
the reference GEMM across presets/formats/modes/roundings, ragged M and
padded K, zero per-call weight relayout, the kernel-layout container views,
and the v1 -> v2 checkpoint layout upgrade."""
import dataclasses
import os

import numpy as np
import jax
import jax.numpy as jnp
import msgpack
import pytest

from repro.core import quantized as Q
from repro.core.packed import (
    LAYOUT_VERSION,
    PackedDSBPWeight,
    get_quant_method,
    quant_method_names,
    to_kernel_layout,
)
from repro.kernels import ops
from repro.models.layers import Quant, dense


def _data(shape, seed=0, spread=4):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal(shape) * np.exp2(rng.integers(-spread, spread, shape))
    ).astype(np.float32)


def _cfg(preset="precise", **input_kw):
    cfg = Q.PRESETS[preset]
    if input_kw:
        cfg = dataclasses.replace(
            cfg, input_cfg=dataclasses.replace(cfg.input_cfg, **input_kw)
        )
    return cfg


# ---------------- bit-exactness vs dsbp_matmul_ref ----------------

@pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
@pytest.mark.parametrize("preset", ["precise", "efficient"])
def test_fused_bit_exact_vs_ref(preset, fmt):
    """Fused kernel == reference GEMM, bitwise, under the default RNE path
    (the ISSUE's acceptance bar: max relative error == 0)."""
    cfg = _cfg(preset, fmt=fmt)
    x = jnp.asarray(_data((16, 256), seed=1))
    w = jnp.asarray(_data((256, 96), seed=2, spread=2))
    pw = Q.pack_weights(w, cfg)
    ref = np.asarray(Q.dsbp_matmul_ref(x, w, cfg))
    got = np.asarray(ops.dsbp_matmul_fused(x, pw))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("mode,b_fix", [("fixed", 7), ("fixed", 3), ("dsbp", 4)])
def test_fused_bit_exact_modes(mode, b_fix):
    cfg = _cfg("precise", mode=mode, b_fix=b_fix, k=0.0 if mode == "fixed" else 2.0)
    x = jnp.asarray(_data((8, 192), seed=3, spread=8))
    w = jnp.asarray(_data((192, 64), seed=4, spread=2))
    pw = Q.pack_weights(w, cfg)
    np.testing.assert_array_equal(
        np.asarray(ops.dsbp_matmul_fused(x, pw)),
        np.asarray(Q.dsbp_matmul_ref(x, w, cfg)),
    )


def test_fused_bit_exact_trunc_rounding():
    """FIAU serial-read truncation: still integer-exact alignment, so the
    fused path stays bitwise equal to the reference."""
    cfg = _cfg("efficient", mantissa_rounding="trunc")
    x = jnp.asarray(_data((8, 256), seed=5))
    w = jnp.asarray(_data((256, 64), seed=6, spread=2))
    pw = Q.pack_weights(w, cfg)
    np.testing.assert_array_equal(
        np.asarray(ops.dsbp_matmul_fused(x, pw)),
        np.asarray(Q.dsbp_matmul_ref(x, w, cfg)),
    )


@pytest.mark.parametrize("k", [100, 250])  # K % 64 != 0
def test_fused_k_padding(k):
    """The activation pads up to the container's K' with the same zero
    lanes the weights packed with — bit-exact at odd K, loud error on a
    mismatched activation width."""
    cfg = _cfg("precise")
    x = jnp.asarray(_data((4, k), seed=7))
    w = jnp.asarray(_data((k, 48), seed=8, spread=2))
    pw = Q.pack_weights(w, cfg)
    assert pw.padded_k != k and pw.k == k
    np.testing.assert_array_equal(
        np.asarray(ops.dsbp_matmul_fused(x, pw)),
        np.asarray(Q.dsbp_matmul_ref(x, w, cfg)),
    )
    with pytest.raises(ValueError):
        ops.dsbp_matmul_fused(jnp.asarray(_data((4, k + 1))), pw)
    with pytest.raises(ValueError):  # stacked containers need a vmap
        stacked = jax.tree.map(lambda l: jnp.stack([l, l]), pw)
        ops.dsbp_matmul_fused(x, stacked)


@pytest.mark.parametrize("m", [1, 3, 5, 130])
def test_fused_ragged_m(m):
    """Decode batches (B=1/3/5, or any M not dividing the row block) need
    no caller-side padding."""
    cfg = _cfg("efficient")
    x = jnp.asarray(_data((m, 128), seed=m))
    w = jnp.asarray(_data((128, 64), seed=9, spread=2))
    pw = Q.pack_weights(w, cfg)
    np.testing.assert_array_equal(
        np.asarray(ops.dsbp_matmul_fused(x, pw)),
        np.asarray(Q.dsbp_matmul_ref(x, w, cfg)),
    )


def test_fused_batched_and_vs_two_kernel():
    """(B, S, K) batch shapes reshape through; the fused one-pass result
    agrees with the two-kernel packed path (whose own tolerance vs ref is
    pinned in test_kernels.py)."""
    cfg = _cfg("precise")
    x = jnp.asarray(_data((2, 5, 256), seed=10))
    w = jnp.asarray(_data((256, 128), seed=11, spread=2))
    pw = Q.pack_weights(w, cfg)
    y_f = np.asarray(ops.dsbp_matmul_fused(x, pw))
    assert y_f.shape == (2, 5, 128)
    np.testing.assert_array_equal(y_f, np.asarray(Q.dsbp_matmul_ref(x, w, cfg)))
    y_2 = np.asarray(ops.dsbp_matmul_packed(x, pw))
    tol = 3e-5 * np.abs(y_f).max()
    np.testing.assert_allclose(y_2, y_f, atol=tol)


def test_fused_k_tiling_close():
    """Explicit bk tiles the reduction across grid steps: still an exact
    integer dot per tile, only the cross-tile f32 accumulation order may
    differ from the reference."""
    cfg = _cfg("precise")
    x = jnp.asarray(_data((8, 512), seed=12))
    w = jnp.asarray(_data((512, 64), seed=13, spread=2))
    pw = Q.pack_weights(w, cfg)
    ref = np.asarray(Q.dsbp_matmul_ref(x, w, cfg))
    got = np.asarray(ops.dsbp_matmul_fused(x, pw, bk=128))
    np.testing.assert_allclose(got, ref, atol=3e-5 * np.abs(ref).max())


def _fused_sharded_one_device(x, pw, **blocks):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    return ops.dsbp_matmul_fused_sharded(
        x, pw, mesh, batch_axis=("data",), k_axis=None, n_axis="model",
        **blocks)


@pytest.mark.parametrize("entry", ["fused", "sharded"])
@pytest.mark.parametrize("bk", [None, 128], ids=["one_k_block", "k_tiles"])
@pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
@pytest.mark.parametrize("preset", ["precise", "efficient"])
def test_fused_output_tiles_reuse_aligned_input(preset, fmt, bk, entry):
    """Three row blocks (the last one ragged) by four output tiles: the
    first output tile of a row block aligns its input once per K tile, and
    the other three read that back.  One K block stays bit-exact; K tiles
    keep the tolerance of test_fused_k_tiling_close."""
    cfg = _cfg(preset, fmt=fmt)
    x = jnp.asarray(_data((20, 256), seed=24))
    w = jnp.asarray(_data((256, 512), seed=25, spread=2))
    pw = Q.pack_weights(w, cfg)
    ref = np.asarray(Q.dsbp_matmul_ref(x, w, cfg))
    call = ops.dsbp_matmul_fused if entry == "fused" else _fused_sharded_one_device
    got = np.asarray(call(x, pw, bm=8, bn=128, bk=bk))
    if bk is None:
        np.testing.assert_array_equal(got, ref)
    else:
        np.testing.assert_allclose(got, ref, atol=3e-5 * np.abs(ref).max())


# ---------------- no per-call weight relayout ----------------

def test_fused_and_packed_make_zero_weight_relayouts():
    """The kernel-layout operands come straight from the container: neither
    serving entry point transposes (or otherwise relayouts) a weight-sized
    array per call."""
    cfg = _cfg("precise")
    x = jnp.asarray(_data((4, 256), seed=14))
    w = jnp.asarray(_data((256, 128), seed=15, spread=2))
    pw = Q.pack_weights(w, cfg)
    wsize = pw.ka.size
    assert ops.count_weight_transposes(
        lambda xx, p: ops.dsbp_matmul_fused(xx, p), x, pw, min_size=wsize) == 0
    assert ops.count_weight_transposes(
        lambda xx, p: ops.dsbp_matmul_packed(xx, p), x, pw, min_size=wsize) == 0
    # sanity: the counter does see the legacy view's permutation
    assert ops.count_weight_transposes(lambda p: p.a, pw, min_size=wsize) >= 1


# ---------------- registry + QAT ----------------

def test_fused_method_registered():
    assert "dsbp_fused" in quant_method_names()
    assert Quant("precise", "dsbp_fused").method.name == "dsbp_fused"


def test_fused_method_packed_and_raw_agree():
    """dense() through 'dsbp_fused': packed container == raw weight (packed
    per call), bitwise — and both equal the reference method's numerics."""
    x = jnp.asarray(_data((2, 5, 128), seed=16))
    w = jnp.asarray(_data((128, 64), seed=17, spread=2))
    pw = Q.pack_weights(w, Q.PRESETS["efficient"])
    quant = Quant("efficient", "dsbp_fused")
    y_pk = np.asarray(dense(pw, x, quant))
    np.testing.assert_array_equal(y_pk, np.asarray(dense(w, x, quant)))
    y_ref = np.asarray(dense(pw, x, Quant("efficient", "dsbp_ref")))
    np.testing.assert_array_equal(y_pk, y_ref)


def test_fused_method_qat_gradients_are_ste():
    x = jnp.asarray(_data((8, 128), seed=18))
    w = jnp.asarray(_data((128, 32), seed=19, spread=2))

    def loss(wv, method):
        return jnp.sum(dense(wv, x, Quant("efficient", method)) ** 2)

    g_ref = jax.grad(lambda wv: loss(wv, "dsbp_ref"))(w)
    g_fus = jax.grad(lambda wv: loss(wv, "dsbp_fused"))(w)
    assert float(jnp.abs(g_fus).max()) > 0
    np.testing.assert_allclose(np.asarray(g_fus), np.asarray(g_ref), rtol=1e-5)


# ---------------- container layout v2 ----------------

def test_container_kernel_layout_and_legacy_views():
    cfg = _cfg("precise")
    w = jnp.asarray(_data((250, 48), seed=20, spread=2))
    pw = Q.pack_weights(w, cfg)
    assert pw.version == LAYOUT_VERSION == 2
    assert pw.ka.shape == (256, 48) and pw.ka.dtype == jnp.int8
    assert pw.kscale.shape == (4, 48)
    # the legacy views are the exact inverse permutation
    ka2, ks2 = to_kernel_layout(pw.a, pw.scale)
    np.testing.assert_array_equal(np.asarray(ka2), np.asarray(pw.ka))
    np.testing.assert_array_equal(np.asarray(ks2), np.asarray(pw.kscale))
    # dequantize is transpose-free off the kernel layout and still logical
    assert pw.dequantize().shape == (250, 48)


def _forge_v1_checkpoint(dirpath, step, pw):
    """Write a layout-v1 checkpoint (fields a/scale/tscale/bits in the
    macro's per-column shapes) the way the pre-v2 store did."""
    flat = {
        "w2/a": np.asarray(pw.a),
        "w2/scale": np.asarray(pw.scale),
        "w2/tscale": np.asarray(pw.tscale),
        "w2/bits": np.asarray(pw.bits),
    }
    d = os.path.join(dirpath, f"step_{step:08d}")
    os.makedirs(d)
    np.savez(os.path.join(d, "host0.npz"), **flat)
    manifest = {
        "step": step,
        "keys": sorted(flat),
        "shapes": {k: list(v.shape) for k, v in flat.items()},
        "dtypes": {k: str(v.dtype) for k, v in flat.items()},
    }
    with open(os.path.join(d, "manifest.msgpack"), "wb") as f:
        f.write(msgpack.packb(manifest))


def test_checkpoint_v1_layout_loads_and_upgrades(tmp_path):
    """An old-layout checkpoint restores into a v2 container bit-exactly
    (the upgrade is a pure permutation) and serves through the fused
    kernel; a genuinely missing field still raises."""
    from repro.checkpoint import store

    cfg = _cfg("efficient")
    x = jnp.asarray(_data((4, 130), seed=21))
    w = jnp.asarray(_data((130, 64), seed=22, spread=2))
    pw = Q.pack_weights(w, cfg)
    _forge_v1_checkpoint(str(tmp_path), 5, pw)
    restored, step = store.restore(str(tmp_path), {"w2": pw})
    assert step == 5
    rp = restored["w2"]
    assert isinstance(rp, PackedDSBPWeight) and rp.version == 2
    for name in ("ka", "kscale", "tscale", "bits"):
        a, b = np.asarray(getattr(rp, name)), np.asarray(getattr(pw, name))
        assert a.dtype == b.dtype, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(ops.dsbp_matmul_fused(x, rp)),
        np.asarray(Q.dsbp_matmul_ref(x, w, cfg)),
    )
    with pytest.raises(KeyError):  # 'bits' is not derivable -> still loud
        store.restore(str(tmp_path), {"w2": pw, "extra": jnp.zeros(3)})


def test_checkpoint_v2_roundtrip_current_layout(tmp_path):
    from repro.checkpoint import store

    cfg = _cfg("precise")
    pw = Q.pack_weights(jnp.asarray(_data((128, 64), seed=23, spread=2)), cfg)
    store.save(str(tmp_path), 1, {"w": pw})
    restored, _ = store.restore(str(tmp_path), {"w": pw})
    np.testing.assert_array_equal(np.asarray(restored["w"].ka), np.asarray(pw.ka))
    np.testing.assert_array_equal(
        np.asarray(restored["w"].kscale), np.asarray(pw.kscale))
