"""Pack-once DSBP weights end-to-end: bit-exactness vs the reference GEMM,
checkpoint round-trip, quant-method registry, and packed serving parity."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import quantized as Q
from repro.core.packed import (
    PackedDSBPWeight,
    get_quant_method,
    packed_nbytes,
    quant_method_names,
    tree_is_packed,
)
from repro.models import model as M
from repro.models.layers import Quant, dense
from repro.serve.engine import Engine, ServeConfig, pack_weights_int8


def _data(shape, seed=0, spread=4):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal(shape) * np.exp2(rng.integers(-spread, spread, shape))
    ).astype(np.float32)


# ---------------- packed container + packed_matmul ----------------

@pytest.mark.parametrize("preset", sorted(Q.PRESETS))
def test_packed_matmul_bit_exact_vs_ref(preset):
    """packed_matmul off the int8 container == dsbp_matmul_ref, bitwise."""
    cfg = Q.PRESETS[preset]
    x = jnp.asarray(_data((8, 256), seed=1))
    w = jnp.asarray(_data((256, 96), seed=2, spread=2))
    pw = Q.pack_weights(w, cfg)
    assert pw.a.dtype == jnp.int8 and (pw.k, pw.n) == (256, 96)
    ref = np.asarray(Q.dsbp_matmul_ref(x, w, cfg))
    got = np.asarray(Q.packed_matmul(x, pw))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("k", [100, 130])  # K not a multiple of 64
def test_packed_k_padding_regression(k):
    """The logical K lives in the container, not in a trailing slice: packing
    pads K up to the group, and both the integer path and dequantization
    strip the pad explicitly."""
    cfg = Q.PRESETS["precise"]
    x = jnp.asarray(_data((4, k), seed=3))
    w = jnp.asarray(_data((k, 48), seed=4, spread=2))
    pw = Q.pack_weights(w, cfg)
    assert pw.k == k and pw.padded_k == -(-k // 64) * 64 and pw.padded_k != k
    # integer path: bit-exact vs the unpacked reference at this odd K
    np.testing.assert_array_equal(
        np.asarray(Q.packed_matmul(x, pw)),
        np.asarray(Q.dsbp_matmul_ref(x, w, cfg)),
    )
    # weight-only path: dequantized matrix has the logical shape and is
    # close to the original (quantization error only, no pad garbage)
    wd = pw.dequantize()
    assert wd.shape == (k, 48)
    assert float(jnp.max(jnp.abs(wd - w)) / jnp.max(jnp.abs(w))) < 0.05
    # mismatched activation width is a loud error, not a silent slice
    with pytest.raises(ValueError):
        Q.packed_matmul(jnp.asarray(_data((4, k + 1))), pw)


def test_pack_weights_preserves_leading_axes():
    """Stacked scan-unit / MoE-expert weights pack along their lead axes and
    slice back out as containers (what lax.scan does per unit)."""
    cfg = Q.PRESETS["efficient"]
    w = jnp.asarray(_data((3, 128, 64), seed=5, spread=2))
    pw = Q.pack_weights(w, cfg)
    assert pw.a.shape[:2] == (3, 64) and (pw.k, pw.n) == (128, 64)
    unit = jax.tree.map(lambda l: l[1], pw)
    assert isinstance(unit, PackedDSBPWeight) and (unit.k, unit.n) == (128, 64)
    np.testing.assert_array_equal(
        np.asarray(unit.a), np.asarray(Q.pack_weights(w[1], cfg).a)
    )


def test_dense_dispatch_packed_vs_raw_bit_exact():
    """dense() through the registry: packed + quant context == raw + quant
    context (the STE forward), bitwise."""
    cfg_key = "efficient"
    x = jnp.asarray(_data((2, 5, 128), seed=6))
    w = jnp.asarray(_data((128, 64), seed=7, spread=2))
    pw = Q.pack_weights(w, Q.PRESETS[cfg_key])
    quant = Quant(cfg_key)
    np.testing.assert_array_equal(
        np.asarray(dense(pw, x, quant)), np.asarray(dense(w, x, quant))
    )
    # no quant context -> weight-only dequantization, close to the einsum
    y_wo = np.asarray(dense(pw, x))
    y_fp = np.asarray(jnp.einsum("...k,kn->...n", x, w))
    assert np.abs(y_wo - y_fp).max() / (np.abs(y_fp).max() + 1e-9) < 0.1


def test_quant_method_registry():
    assert set(quant_method_names()) >= {"dense_bf16", "dsbp_ref", "dsbp_kernel"}
    with pytest.raises(KeyError):
        get_quant_method("nope")
    assert Quant(None).method.name == "dense_bf16"
    assert Quant("precise").method.name == "dsbp_ref"
    assert Quant("precise", "dsbp_kernel").method.name == "dsbp_kernel"


def test_kernel_method_matches_ref_method():
    """dsbp_kernel consumes the same packed container as dsbp_ref — also
    when the active preset overrides the one the weights were packed with
    (both methods must quantize inputs under the *active* config)."""
    x = jnp.asarray(_data((16, 128), seed=8))
    w = jnp.asarray(_data((128, 64), seed=9, spread=2))
    pw = Q.pack_weights(w, Q.PRESETS["efficient"])
    for active in ("efficient", "precise"):
        cfg = Q.PRESETS[active]
        y_ref = np.asarray(get_quant_method("dsbp_ref").apply(pw, x, cfg))
        y_ker = np.asarray(get_quant_method("dsbp_kernel").apply(pw, x, cfg))
        rel = np.abs(y_ker - y_ref).max() / (np.abs(y_ref).max() + 1e-9)
        assert rel < 1e-6, active


def test_kernel_method_qat_gradients_are_ste():
    """QAT through the dsbp_kernel method must see straight-through weight
    gradients (a plain kernel forward would give grad(w) == 0 through the
    rounding/clipping ops)."""
    x = jnp.asarray(_data((8, 128), seed=10))
    w = jnp.asarray(_data((128, 32), seed=11, spread=2))

    def loss(wv, method):
        return jnp.sum(dense(wv, x, Quant("efficient", method)) ** 2)

    g_ref = jax.grad(lambda wv: loss(wv, "dsbp_ref"))(w)
    g_ker = jax.grad(lambda wv: loss(wv, "dsbp_kernel"))(w)
    assert float(jnp.abs(g_ker).max()) > 0
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref), rtol=1e-5)


# ---------------- checkpoint round-trip ----------------

def test_checkpoint_roundtrip_packed_tree(tmp_path):
    from repro.checkpoint import store

    cfg = _tiny_cfg(quant="precise")
    params = M.init(jax.random.PRNGKey(0), cfg)
    packed, _ = pack_weights_int8(params, "precise")
    assert tree_is_packed(packed)
    store.save(str(tmp_path), 3, packed)
    restored, step = store.restore(str(tmp_path), packed)
    assert step == 3
    flat_a, _ = jax.tree_util.tree_flatten(packed)
    flat_b, _ = jax.tree_util.tree_flatten(restored)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert tree_is_packed(restored)


# ---------------- packed serving ----------------

def _tiny_cfg(**kw):
    base = dict(d_model=128, n_layers=2, n_heads=4, n_kv_heads=2, d_head=32,
                d_ff=256, vocab_size=256, remat=False, quant=None)
    base.update(kw)
    return get_config("llama-7b-paper").replace(**base)


def test_engine_packed_generations_match_unpacked_dsbp():
    """Engine prefill+decode off the int8 packed tree == serving raw weights
    through the same DSBP preset, token-for-token at temperature 0."""
    cfg = _tiny_cfg(quant="precise")
    params = M.init(jax.random.PRNGKey(0), cfg)
    prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 12))
    eng_packed = Engine(params, cfg, ServeConfig(max_len=64))
    eng_raw = Engine(params, cfg, ServeConfig(max_len=64, pack=False))
    assert tree_is_packed(eng_packed.params)
    assert not tree_is_packed(eng_raw.params)
    out_p = eng_packed.generate(prompts, 8)
    out_r = eng_raw.generate(prompts, 8)
    np.testing.assert_array_equal(out_p, out_r)
    # and the engine reports the HBM saving of the packed representation
    rep = eng_packed.pack_report
    assert rep is not None and rep["packed_nbytes"] < 0.55 * rep["raw_nbytes"]
    assert rep["packed_nbytes"] == packed_nbytes(eng_packed.params)


def test_engine_packs_once_not_per_generate():
    cfg = _tiny_cfg(quant="efficient")
    params = M.init(jax.random.PRNGKey(1), cfg)
    eng = Engine(params, cfg, ServeConfig(max_len=64))
    tree_before = eng.params
    prompts = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 8))
    eng.generate(prompts, 3)
    assert eng.params is tree_before  # same packed tree object, no repack
    # an already-packed tree passed in is served as-is
    eng2 = Engine(eng.params, cfg, ServeConfig(max_len=64))
    assert eng2.pack_report is None and eng2.params is eng.params


@pytest.mark.parametrize("arch", ["yi-9b", "recurrentgemma-2b",
                                  "mixtral-8x7b"])
def test_init_packed_equals_packing_the_float_model(arch):
    """The layer-by-layer build (each layer packed as soon as it exists, so
    the float model never does) gives bit-identical containers to packing
    what M.init returns — stacked units, unrolled tail layers and MoE
    expert stacks alike — and the same bit statistics."""
    from repro.configs import smoke_config
    from repro.serve.engine import init_packed

    cfg = smoke_config(arch).replace(quant="precise")
    key = jax.random.PRNGKey(3)
    want, want_stats = pack_weights_int8(M.init(key, cfg), "precise")
    got, got_stats = init_packed(key, cfg, "precise")
    is_pw = lambda x: isinstance(x, PackedDSBPWeight)
    assert (jax.tree.structure(got, is_leaf=is_pw)
            == jax.tree.structure(want, is_leaf=is_pw))
    assert tree_is_packed(got)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert got_stats == want_stats
