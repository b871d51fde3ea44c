"""Operation and byte counts of bench/work.py against the widths."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import work
from bench.weights import Dims

BENCH = Path(__file__).resolve().parent

# deepseek-coder-33b's published widths (arXiv:2401.14196) at 16 of its 62
# layers: widths the benchmark has no configuration file for
WIDTHS = {"deepseek-coder-33b-s16": {
    "num_hidden_layers": 16, "hidden_size": 7168, "num_attention_heads": 56,
    "num_key_value_heads": 8, "intermediate_size": 19200,
    "vocab_size": 32256, "rope_theta": 100000.0, "rms_norm_eps": 1e-6}}


def _dims(name):
    c = WIDTHS.get(name) or json.loads(
        (BENCH / "configs" / f"{name}.json").read_text())
    return Dims(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"],
                d_head=c["hidden_size"] // c["num_attention_heads"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"])


@pytest.mark.parametrize("name,layers,per_layer", [
    ("yi-9b", 48, 173_015_040),
    ("deepseek-coder-33b-s16", 16, 530_317_312),
])
def test_projection_elements_match_the_widths(name, layers, per_layer):
    assert work.projection_elements(_dims(name)) == layers * per_layer


def test_least_bytes_stay_under_the_packed_bytes_on_the_chip():
    # yi-9b's packed projections take 8,960,311,296 bytes on a v5e; even a
    # full 8-bit width leaves the least-bytes count below them
    e = work.projection_elements(_dims("yi-9b"))
    assert work.least_pass_bytes(e, 8.0) < 8_960_311_296
    assert work.least_pass_bytes(e, 6.0) == e * 6 / 8 + e / 64


def test_gemm_least_time_takes_the_larger_bound():
    d = _dims("yi-9b")
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    e = work.projection_elements(d)
    mem = work.least_pass_bytes(e, 6.0) / 819e9
    assert work.gemm_least_seconds([16], d, 6.0, peaks) == pytest.approx(mem)
    big = 2.0 * 4096 * e / 393e12
    assert big > mem
    assert work.gemm_least_seconds([4096, 0], d, 6.0, peaks) == \
        pytest.approx(big)


def test_model_flops_counts_attention_per_position():
    d = Dims(n_layers=1, d_model=8, n_heads=2, n_kv_heads=1, d_head=4,
             d_ff=16, vocab=10, rope_theta=1e4, norm_eps=1e-6)
    e = work.projection_elements(d)
    # one sequence of 3 tokens = positions 0, 1, 2 -> 1 + 2 + 3 keys
    got = work.model_flops(d, [(0, 3)], logit_tokens=1)
    assert got == 2 * e * 3 + 4 * 6 * 2 * 4 + 2 * 8 * 10
    # the same tokens in two segments count the same
    assert work.model_flops(d, [(0, 2), (2, 1)], 1) == got


def test_element_count_matches_the_packed_containers_at_smoke_size():
    import jax

    from bench import program
    from repro.configs import smoke_config
    from repro.serve.engine import init_packed

    cfg = smoke_config("yi-9b").replace(quant="precise", vocab_size=512)
    want = work.projection_elements(program.dims_of(cfg))
    ours = program.packed_stats(program.build_params(3, cfg))
    theirs = program.packed_stats(
        init_packed(jax.random.PRNGKey(3), cfg, "precise")[0])
    assert ours["elements"] == theirs["elements"] == want
    assert 1.0 < ours["avg_w_bits"] <= 8.0
