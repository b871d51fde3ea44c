"""The mixture-of-experts cell on the CPU: its configuration, work counts,
readers, and a tiny run of the whole cell against its plain reference."""
from __future__ import annotations

import json
import math
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import program, program_moe, reference_moe, work_moe
from bench import weights_moe as WM

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CONF = "mellum2-12b-a2.5b"

TINY_MOE = {
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "moe_intermediate_size": 64, "num_experts": 16,
    "num_experts_per_tok": 4, "num_hidden_layers": 8, "vocab_size": 512,
    "sliding_window": 32,
}
TINY_MIX = {
    "clients": 3, "warm_requests": 3, "queue": 4000, "epoch": 4,
    "prompt": {"median": 80, "sigma": 0.2, "min": 66, "max": 100},
    "output": {"median": 12, "sigma": 0.3, "min": 8, "max": 24},
    "serve": {"paged": True, "batch_size": 3, "max_len": 128,
              "kv_block_size": 16},
    "trace_start_s": 0.5, "trace_seconds": 0.5,
    "check": {"requests": 3, "block": 3},
}
TINY_LIMIT = 1.0


def _conf(name=CONF):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def make_tiny_moe(dst: Path) -> tuple[str, str]:
    """A checkout-like root whose mellum2-chat cell runs a tiny
    configuration of the same shape: 2 periods, window 32, 16 experts,
    top-4, and a tiny mix of prompts past the window."""
    bench = dst / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*.py", "conftest.py", "testdata"))
    conf = _conf()
    conf.update(TINY_MOE)
    conf["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 64
    (bench / "configs" / f"{CONF}.json").write_text(json.dumps(conf))
    path = bench / "mixes" / "chat-mixed-len.json"
    mix = json.loads(path.read_text())
    mix.update(TINY_MIX)
    path.write_text(json.dumps(mix))
    (bench / "cells" / "mellum2-chat.json").write_text(
        json.dumps({"limits": {"max_gap": TINY_LIMIT}}))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return str(dst), str(bench)


@pytest.fixture
def tiny_moe(tmp_path, monkeypatch):
    import jax

    import repro.launch.compile_cache as cc
    from bench.conftest import Tiny

    monkeypatch.setattr(cc, "setup_compile_cache", lambda: None)
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield Tiny(*make_tiny_moe(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def test_bench_config_runs_the_program_config_at_16_layers():
    """The configuration file, read as the harness reads it, gives the
    program's own Mellum2 config (``configs/mellum2_12b.py``) at the file's
    depth: the window, the experts and each layer kind's rotary."""
    from repro.configs import get_config

    conf = _conf()
    cfg = program_moe.moe_config(program.arch_config(conf), conf)
    want = get_config("mellum2-12b").replace(
        n_layers=16, quant="precise", dtype="float32")
    fields = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
              "vocab_size", "pattern", "window", "n_experts", "top_k",
              "rope_theta", "norm_eps", "yarn_factor",
              "yarn_original_max_len", "yarn_beta_fast", "yarn_beta_slow",
              "yarn_attention_factor", "quant", "dtype", "tie_embeddings")
    assert {f: getattr(cfg, f) for f in fields} == \
        {f: getattr(want, f) for f in fields}
    # the published dense width stays in the file, used by no layer
    assert conf["intermediate_size"] == 7168 and cfg.d_ff == 896
    assert set(conf["mlp_layer_types"]) == {"sparse"}
    assert conf["reduced"] == {"num_hidden_layers": 28}


def test_the_mix_fits_its_cache_and_wraps_the_window():
    from bench import traffic

    mix = json.loads((BENCH / "mixes" / "chat-mixed-len.json").read_text())
    prompts = traffic.quantile_lengths(mix["epoch"], **mix["prompt"])
    outputs = traffic.quantile_lengths(mix["epoch"], **mix["output"])
    assert prompts.max() + outputs.max() <= mix["serve"]["max_len"]
    assert prompts.max() > _conf()["sliding_window"] > prompts[-2]
    assert prompts.min() > 4 * 16  # every admission is chunked


MELLUM = WM.Dims(n_layers=16, d_model=2304, n_heads=32, n_kv_heads=4,
                 d_head=128, d_ff=896, n_experts=64, top_k=8, vocab=98304,
                 rope_theta=5e5, norm_eps=1e-6,
                 pattern=("attn_local",) * 3 + ("attn_full",), window=1024,
                 yarn=(16.0, 8192, 32.0, 1.0, 1.2772588722239782))


def test_work_counts_at_mellum2_widths():
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert work_moe.active_elements(MELLUM) == attn + 2304 * 64 + 8 * 3 * 2304 * 896
    assert work_moe.expert_pair_ops(MELLUM) == 6 * 2304 * 896
    # 64 experts of 16 layers at an 8-bit width: 6.34e9 elements, under
    # the packed experts' bytes on the chip
    every = 16 * 64
    assert work_moe.expert_bytes(every, MELLUM, 8.0) == pytest.approx(
        every * 3 * 2304 * 896 * (1 + 1 / 64))
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    # a decode pass of 32 tokens is bound by the bytes of what it touches
    t = work_moe.expert_least_seconds([(32, every)], MELLUM, 6.0, peaks)
    assert t == pytest.approx(work_moe.expert_bytes(every, MELLUM, 6.0) / 819e9)
    # touched experts are capped by what the tokens can route to
    one = work_moe.expert_least_seconds([(1, every)], MELLUM, 6.0, peaks)
    assert one == pytest.approx(work_moe.expert_bytes(16 * 8, MELLUM, 6.0)
                                / 819e9)
    assert work_moe.expert_least_seconds([(0, 5)], MELLUM, 6.0, peaks) == 0


@pytest.mark.parametrize("segments", [[(0, 16)], [(1000, 48)], [(2000, 1)],
                                      [(1020, 1), (0, 1100)]])
def test_model_flops_count_each_kind_of_layer_reach(segments):
    per_pos = 4 * 32 * 128
    full = local = tokens = 0
    for start, n in segments:
        for p in range(start, start + n):
            tokens += 1
            full += 4 * (p + 1)
            local += 12 * min(p + 1, 1024)
    want = (2.0 * work_moe.active_elements(MELLUM) * 16 * tokens
            + per_pos * (full + local) + 2.0 * 2304 * 98304 * 3)
    assert work_moe.model_flops(MELLUM, segments, 3) == pytest.approx(want)


def _reader(name):
    from bench import harness

    return harness.Bench(str(ROOT)).reader(name)


def _moe_counts(**kw):
    return {"moe_dims": MELLUM._asdict(), "expert_avg_w_bits": 6.0,
            "segments": [(0, 100), (100, 1)], "logit_tokens": 1,
            "window_s": 2.0, "expert_rows_real": 400,
            "expert_rows_padded": 100, "trace_expert_passes": [(32, 1024)],
            **kw}


PEAKS = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def test_readers_of_the_moe_cell_on_a_synthetic_run():
    trace = {"op_s": {"%dsbp_grouped_kernel_call.3 = f32[..] custom-call(..)":
                      0.02, "%fusion.1 = f32[..] fusion(..)": 0.5},
             "op_info": {}, "module_calls": {"jit__decode_paged_fn(1)": 4},
             "module_s": {"jit__decode_paged_fn(1)": 0.2},
             "expert_scope_s": {"_decode_paged_fn/experts_in": 0.06,
                                "_decode_paged_fn/experts_out": 0.03,
                                "_decode_paged_fn/router": 0.01,
                                "_verify_paged_fn/experts_in": 9.0}}
    run = {"trace": trace, "counts": _moe_counts(), "peaks": PEAKS}
    assert _reader("expert_pad_share.mellum2").read(run) == 25.0
    assert _reader("expert_ms.mellum2").read(run) == pytest.approx(25.0)
    least = work_moe.expert_least_seconds([(32, 1024)], MELLUM, 6.0, PEAKS)
    assert _reader("expert_gemm_roofline.mellum2").read(run) == \
        pytest.approx(100 * least / 0.02)
    mfu = _reader("moe_mfu.mellum2").read(run)
    assert mfu == pytest.approx(100 * work_moe.model_flops(
        MELLUM, [(0, 100), (100, 1)], 1) / (2.0 * 393e12))
    assert 0 < mfu < 100


@pytest.mark.parametrize("metric", ["expert_pad_share.mellum2",
                                    "expert_ms.mellum2",
                                    "expert_gemm_roofline.mellum2",
                                    "moe_mfu.mellum2"])
def test_moe_readers_find_nothing_in_a_run_without_experts(metric):
    """A run of a program without the grouped kernel, the expert scopes or
    the expert counters (as the parent commit's) gives no reading, and no
    error."""
    from bench import program_trace as P

    spans = P.read_space(str(BENCH / "testdata" / "serve_spans_v5e.xplane.pb"))
    from bench import expert_trace

    assert expert_trace.expert_scope_s(spans) == {}
    trace = {"op_s": {"%dsbp_fused_kernel_call.1 = f32[..]": 1.0},
             "op_info": {}, "module_calls": {"jit__decode_paged_fn(1)": 4},
             "expert_scope_s": {}}
    plain = {"segments": [(0, 4)], "logit_tokens": 1, "window_s": 1.0}
    for run in ({"trace": trace, "counts": plain, "peaks": PEAKS},
                {"trace": None, "counts": {}, "peaks": None}):
        assert _reader(metric).read(run) is None


def test_a_tiny_run_of_the_moe_cell(tiny_moe):
    """The whole cell at a tiny size: chunked prefill past the sliding
    window, decode through the grouped experts, the engine's counters, and
    the served tokens checked against the plain reference."""
    res = tiny_moe.run("mellum2-chat", seconds=3.0)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["window_compiles"] == 0
    assert res["checks"]["max_gap"]["value"] < TINY_LIMIT
    traced = tiny_moe.run("mellum2-chat", seconds=3.0, trace=True)
    m = traced["metrics"]
    assert m["expert_pad_share.mellum2"]["value"] > 0
    assert 0 < m["moe_mfu.mellum2"]["value"] < 100


def test_the_tiny_control_fails_the_tiny_limit(tiny_moe):
    """Fixed 3-bit mantissas in place of DSBP's predicted widths: the
    served tokens fall behind the reference's best by more than the
    limit the sound runs stay under."""
    res = tiny_moe.run("mellum2-chat", seconds=3.0, preset="e5m3_fixed")
    assert res["checks"]["max_gap"]["value"] > TINY_LIMIT
    assert res["correct"] is False


def _tiny_cfg(preset=None):
    conf = _conf()
    conf.update(TINY_MOE)
    conf["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 64
    return program_moe.moe_config(program.arch_config(conf, preset), conf)


def _float_params(seed, dims):
    """The seeded weights of ``bench/weights_moe.py`` in the program's
    layout, unpacked: the serving engine then runs them in float."""
    import jax
    import jax.numpy as jnp

    key, period = WM.seed_key(seed), len(dims.pattern)

    def layer(i):
        w = WM.layer(key, i, dims)
        e = WM.experts(key, i, dims, 0, dims.n_experts)
        return {"norm1": {"scale": w["attn_norm"]},
                "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                "norm2": {"scale": w["mlp_norm"]},
                "moe": {"router": w["router"], "w1": e["w_gate"],
                        "w3": e["w_up"], "w2": e["w_down"]}}

    units = [jax.tree.map(lambda *a: jnp.stack(a),
                          *[layer(u * period + li)
                            for u in range(dims.n_layers // period)])
             for li in range(period)]
    return {"embed": WM.embed(key, dims),
            "final_norm": {"scale": WM.final_norm(key, dims)},
            "lm_head": WM.head(key, dims), "units": units, "tail": []}


def _served_logits(params, cfg, prompt, new_tokens):
    """Greedy ``Engine.serve(paged=True)`` of one request, and the logits
    each position of prompt + served tokens got on the way: the chunked
    prefill's passes over the prompt, then one decode step per token."""
    from repro.serve.engine import Engine, Request, ServeConfig

    eng = Engine(params, cfg, ServeConfig(batch_size=1, max_len=128,
                                          paged=True, kv_block_size=16))
    seen = {}
    verify, decode = eng._verify_paged, eng._decode_paged

    def verify_rec(p, tok, cache, table, pos):
        out = verify(p, tok, cache, table, pos)
        for t, row in enumerate(np.asarray(out[0][0])):
            seen[int(pos[0]) + t] = row
        return out

    def decode_rec(p, tok, cache, table, pos, live):
        out = decode(p, tok, cache, table, pos, live)
        seen[int(pos[0])] = np.asarray(out[0][0, -1])
        return out

    eng._verify_paged, eng._decode_paged = verify_rec, decode_rec
    served = eng.serve([Request(uid=0, tokens=prompt,
                                max_new_tokens=new_tokens)])[0]
    assert eng.last_stats["chunk_steps"] > 1  # a chunked admission
    seq = np.concatenate([prompt, served]).astype(np.int32)
    return seq, np.stack([seen[p] for p in range(len(seq) - 1)])


@pytest.mark.parametrize("weights", ["float", "dsbp"])
def test_paged_serving_logits_match_the_reference(weights):
    """Teacher-forced: the logits the paged engine computed at every
    position of a served sequence (an 80-token prompt through chunked
    prefill, then 40 decode steps: past the 32-token window on the sliding
    layers and through YaRN on the full ones) against
    ``bench/reference_moe.py``'s logits of that sequence under the same
    seeded weights.

    Float weights (dense expert dispatch) match to float round-off, so the
    cache, the rings, the masks and both rotaries are the reference's.
    Packed DSBP weights through the grouped experts: the mean relative
    error over positions read 0.14-0.22 on four seeds (0.17 on this one),
    from E4M3 inputs and aligned widths rounding every projection of 8
    layers at a width of 128; fixed 3-bit mantissas (``e5m3_fixed``) read
    0.55-0.78 on the same seeds (0.61 on this one), and 0.3 lies
    between."""
    seed = 3
    cfg = _tiny_cfg()
    dims = program_moe.dims_of(cfg)
    if weights == "float":
        cfg = cfg.replace(quant=None)
        params = _float_params(seed, dims)
    else:
        params = program_moe.build_params(seed, cfg)
    prompt = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, 80).astype(np.int32)
    seq, got = _served_logits(params, cfg, prompt, 40)
    want = np.asarray(reference_moe.logits(WM.seed_key(seed), seq[None, :-1],
                                           dims))[0]
    err = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    assert len(err) == 119 > 3 * cfg.window
    if weights == "float":
        assert err.max() < 1e-4
    else:
        assert err.mean() < 0.3


def test_reference_yarn_frequencies_are_the_programs():
    from repro.configs import get_config
    from repro.models.layers import rope_scaling, yarn_inv_freq

    cfg = get_config("mellum2-12b")
    ours = np.asarray(yarn_inv_freq(128, 5e5, rope_scaling(cfg, "attn_full")))
    np.testing.assert_allclose(
        ours, reference_moe.inv_freq(128, 5e5, MELLUM.yarn), rtol=2e-6)
    assert math.isclose(reference_moe.inv_freq(128, 5e5)[1],
                        5e5 ** (-2 / 128))
