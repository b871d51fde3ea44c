"""The grouped DSBP expert GEMM, the serving MoE path built on it, YaRN
rotary, and the Mellum2 configuration (CPU, kernels in interpret mode)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.formats import per_tensor_scale
from repro.core.packed import PackedDSBPWeight
from repro.core.quantized import (PRESETS, dsbp_matmul_ref,
                                  grouped_packed_matmul_ref, pack_weights)
from repro.kernels import ops
from repro.models import model as M
from repro.models import moe as MOE
from repro.models.layers import Quant, Yarn, rope, rope_scaling, yarn_inv_freq
from repro.serve.engine import Engine, ServeConfig, pack_tree

CFG = PRESETS["precise"]


def _grouped_inputs(counts, k, bm, rng, amax=3.0, spare_blocks=2):
    """Rows of each expert (every row's largest entry is ``amax``, so any
    subset of rows has the whole tensor's scale), sorted and padded per
    expert to ``bm``, with the block table and used-block count."""
    rows, x, table, off = [], [], [], 0
    for e, c in enumerate(counts):
        r = rng.standard_normal((c, k)).astype(np.float32)
        if c:
            r[:, 0] = amax * np.sign(r[:, 0])
        rows.append(r)
        pad = -(-c // bm) * bm
        x.append(np.concatenate([r, np.zeros((pad - c, k), np.float32)]))
        table += [e] * (pad // bm)
    n_used = len(table)
    table += [table[-1]] * spare_blocks
    x.append(np.zeros((spare_blocks * bm, k), np.float32))
    return rows, np.concatenate(x), np.asarray(table, np.int32), n_used


@pytest.mark.parametrize("counts", [
    [5, 0, 17, 3],     # ragged, with an expert that takes no row
    [0, 24, 0, 0],     # one expert takes every row
    [1, 1, 1, 1],      # one row each: every block mostly padding
])
def test_grouped_kernel_is_bit_exact_per_expert(counts):
    """Each expert's rows through the grouped kernel equal
    ``dsbp_matmul_ref`` of those rows against that expert's weights, bit
    for bit; the padding rows come out zero."""
    rng = np.random.default_rng(sum(counts))
    n_exp, k, n, bm = len(counts), 128, 256, 8
    w = jnp.asarray(rng.standard_normal((n_exp, k, n)).astype(np.float32)
                    * k ** -0.5)
    pw = pack_weights(w, CFG)
    rows, x, table, n_used = _grouped_inputs(counts, k, bm, rng)
    ts = per_tensor_scale(jnp.asarray(x), CFG.input_cfg.fmt)
    y = np.asarray(ops.dsbp_matmul_grouped(
        jnp.asarray(x), pw, ts, jnp.asarray(table), jnp.asarray([n_used]),
        bm))
    y_ref = np.asarray(grouped_packed_matmul_ref(
        jnp.asarray(x), ts, pw, jnp.asarray(table), bm))
    off = 0
    for e, c in enumerate(counts):
        pad = -(-c // bm) * bm
        if c:
            want = np.asarray(dsbp_matmul_ref(jnp.asarray(rows[e]), w[e], CFG))
            np.testing.assert_array_equal(y[off:off + c], want)
            np.testing.assert_array_equal(y_ref[off:off + c], want)
        assert not np.any(y[off + c:off + pad])  # padding does not leak
        off += pad


def test_grouped_kernel_reads_a_layer_of_a_stack_in_place():
    """With a unit index the kernel reads that layer of a (U, E, K, N)
    stack: the same result as the layer's own (E, K, N) container."""
    rng = np.random.default_rng(7)
    k, n, bm = 128, 128, 8
    w = jnp.asarray(rng.standard_normal((3, 2, k, n)).astype(np.float32))
    stack = pack_weights(w, CFG)
    layer = pack_weights(w[1], CFG)
    _, x, table, n_used = _grouped_inputs([9, 4], k, bm, rng)
    ts = per_tensor_scale(jnp.asarray(x), CFG.input_cfg.fmt)
    args = (jnp.asarray(x), ts)
    got = ops.dsbp_matmul_grouped(*args[:1], stack, ts, jnp.asarray(table),
                                  jnp.asarray([n_used]), bm, unit=1)
    want = ops.dsbp_matmul_grouped(*args[:1], layer, ts, jnp.asarray(table),
                                   jnp.asarray([n_used]), bm)
    rows = n_used * bm
    np.testing.assert_array_equal(np.asarray(got)[:rows],
                                  np.asarray(want)[:rows])
    ref = grouped_packed_matmul_ref(*args, stack, jnp.asarray(table), bm,
                                    unit=1)
    np.testing.assert_array_equal(np.asarray(ref)[:rows],
                                  np.asarray(want)[:rows])


def _tiny_moe_cfg(**kw):
    return get_config("mellum2-12b").replace(
        n_layers=8, d_model=128, n_heads=4, n_kv_heads=2, d_head=32, d_ff=64,
        vocab_size=512, window=32, n_experts=16, top_k=4, remat=False,
        yarn_original_max_len=64, **kw)


def _dense_float_moe(p, x, cfg):
    """Every expert on every token, weighted by the renormalized top-k
    softmax gates (zero for the experts a token does not choose)."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    top = top / top.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(idx, cfg.n_experts) * top[..., None]).sum(-2)
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w1"])) * jnp.einsum(
        "bsd,edf->bsef", x, p["w3"])
    return jnp.einsum("bse,bsef,efd->bsd", gates, h, p["w2"])


@pytest.mark.parametrize("method", ["dsbp_fused", "dsbp_ref"])
def test_serving_moe_matches_a_dense_masked_float_moe(method):
    """The grouped serving path (packed experts, FP8 inputs aligned by
    DSBP) against every expert evaluated in float and masked by the gates.
    The tolerance is the DSBP rounding: E4M3 inputs keep 3 mantissa bits
    and the aligned widths drop further low bits of each 64-group's small
    members, so one such GEMM at this size lands up to 4% of its largest
    output from float, and the expert path chains two with the product
    silu(gate) * up between them (4-7% over seeds).  12% of the largest
    output holds that with room, where routing each token to one expert
    fewer moves the output by 20% and more of it (23-45% over seeds)."""
    cfg = _tiny_moe_cfg()
    p = MOE.init_moe(jax.random.PRNGKey(1), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, cfg.d_model))
    packed = pack_tree({"moe": p}, "precise")["moe"]
    assert set(packed) == {"router", "w13", "w2"}  # gate and up fused
    got = MOE.moe_ffn(packed, x, cfg, Quant("precise", method), no_drop=True)
    want = _dense_float_moe(p, x, cfg)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 0.12 * scale
    one_short = want - _dense_float_moe(p, x, cfg.replace(top_k=cfg.top_k - 1))
    assert float(jnp.abs(one_short).max()) > 0.2 * scale


def test_fused_gate_up_equals_packing_both_together():
    cfg = _tiny_moe_cfg()
    p = MOE.init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    fused = MOE.fuse_gate_up(pack_weights(p["w1"], CFG),
                             pack_weights(p["w3"], CFG))
    whole = pack_weights(jnp.concatenate([p["w1"], p["w3"]], -1), CFG)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (fused.k, fused.n) == (whole.k, whole.n)


def test_serving_step_dequantizes_no_expert_container(monkeypatch):
    """The paged decode step of a packed MoE runs its experts through the
    grouped kernel alone: no container's ``dequantize``, and no int8 ->
    float conversion of an expert-sized array outside the kernel."""
    cfg = _tiny_moe_cfg(quant="precise", quant_method="dsbp_fused")
    params = pack_tree(M.init(jax.random.PRNGKey(0), cfg), "precise")
    b, max_len, bs = 2, 64, 16
    cache = M.init_paged_cache(cfg, b, b * max_len // bs + 1, bs)
    table = jnp.arange(b * max_len // bs, dtype=jnp.int32).reshape(b, -1) + 1
    args = (params, {"tokens": jnp.zeros((b, 1), jnp.int32)}, cache, table,
            jnp.asarray([3, 40], jnp.int32), jnp.ones((b,), jnp.int32))

    def step(*a):
        return M.decode_step_paged(*a, cfg, max_len, expert_rows=True)

    expert = params["units"][0]["moe"]["w2"].ka[0].size
    monkeypatch.setattr(PackedDSBPWeight, "dequantize", lambda *a, **k: (
        pytest.fail("an expert container was dequantized")))
    assert ops.count_kv_dequants(step, *args, min_size=expert) == 0
    # the weight-only path the grouped one replaced does convert them
    monkeypatch.undo()
    wo = cfg.replace(quant=None)
    assert ops.count_kv_dequants(
        lambda *a: M.decode_step_paged(*a, wo, max_len), *args,
        min_size=expert) >= 1


def test_engine_counts_the_rows_it_routes():
    """Every token of a paged pass routes ``top_k`` rows in every layer;
    the engine's counters add up to exactly that, with padding to each
    pass's row block on top."""
    cfg = _tiny_moe_cfg(quant="precise")
    params = M.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, cfg.vocab_size, (n,)) for n in (70, 40)]
    eng = Engine(params, cfg, ServeConfig(
        batch_size=2, max_len=128, paged=True, kv_block_size=16,
        prefill_bucket=16, chunk_prefill_tokens=32))
    eng.serve(reqs, max_new_tokens=6)
    st = eng.last_stats
    per_token = cfg.top_k * cfg.n_layers
    routed = per_token * (st["decode_steps"] * 2 + st["chunk_steps"] * 2 * 16)
    assert st["expert_passes"] == st["decode_steps"] + st["chunk_steps"]
    assert st["expert_rows_real"] == routed
    assert st["expert_rows_padded"] > 0
    assert 0 < st["experts_touched"] <= (st["expert_passes"] * cfg.n_layers
                                         * cfg.n_experts)
    assert 0 < st["expert_rows_max"] <= 2 * 16 * cfg.top_k


def test_block_rows_and_static_rows_cover_any_routing():
    assert MOE.block_rows(32, 1, 8) == 8
    assert MOE.block_rows(512, 16, 8) == 128
    assert MOE.block_rows(4, 2, 2) == 8
    for t, k, e, bm in [(32, 8, 64, 8), (512, 8, 64, 128), (3, 2, 16, 8)]:
        rows = MOE.grouped_rows(t, k, e, bm)
        assert rows % bm == 0
        # worst case: every expert holding rows pads by bm - 1
        worst = t * k + min(e, t * k) * (bm - 1)
        assert worst <= rows < worst + bm


def _yarn_numpy(d, theta, factor, orig, beta_fast, beta_slow):
    half = d // 2
    extrap = 1.0 / theta ** (np.arange(0, d, 2) / d)

    def dim(beta):
        return d * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), d - 1)
    ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
    return extrap / factor * ramp + extrap * (1 - ramp)


def test_yarn_inverse_frequencies_cos_and_sin_match_the_formulas():
    cfg = get_config("mellum2-12b")
    yarn = rope_scaling(cfg, "attn_full")
    assert rope_scaling(cfg, "attn_local") is None
    assert yarn == Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    want = _yarn_numpy(128, 5e5, 16.0, 8192, 32.0, 1.0)
    got = np.asarray(yarn_inv_freq(128, 5e5, yarn))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # below the ramp (fast-turning dims) the frequency is kept, above it
    # (slow dims) divided by the factor
    np.testing.assert_allclose(got[:18], want[:18], rtol=2e-6)
    np.testing.assert_allclose(got[40:] * 16.0, _yarn_numpy(
        128, 5e5, 1.0, 8192, 32.0, 1.0)[40:], rtol=2e-6)
    pos = np.asarray([0, 1, 1000, 4095], np.int32)
    x = np.random.default_rng(0).standard_normal((1, 2, 4, 128)).astype(
        np.float32)
    ang = pos[:, None] * want[None]
    c, s = np.cos(ang) * yarn.attention_factor, np.sin(ang) * yarn.attention_factor
    x1, x2 = x[..., :64], x[..., 64:]
    ref = np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)
    out = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), 5e5, yarn))
    np.testing.assert_allclose(out, ref, atol=2e-3 * 1.28)


def test_default_rope_is_unchanged():
    """Layers without YaRN (every layer of yi-9b) run the rotary as it
    was, bit for bit."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 4, 9, 64)).astype(np.float32))
    pos = jnp.arange(9)
    half = 32
    freqs = 1e4 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang)[None, None], jnp.sin(ang)[None, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    want = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    np.testing.assert_array_equal(np.asarray(rope(x, pos, 1e4)),
                                  np.asarray(want))
    assert rope_scaling(get_config("yi-9b"), "attn_full") is None


def test_mellum2_config_is_the_published_geometry():
    cfg = get_config("mellum2-12b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_head, cfg.vocab_size) == (28, 2304, 32, 4, 128, 98304)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff) == (64, 8, 896)
    assert cfg.pattern == ("attn_local",) * 3 + ("attn_full",)
    assert cfg.window == 1024 and cfg.rope_theta == 5e5
    assert abs(cfg.param_count() - 12.15e9) / 12.15e9 < 0.01
    assert abs(cfg.active_param_count() - 2.5e9) / 2.5e9 < 0.12
