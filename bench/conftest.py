"""Fixtures for the benchmark's own CPU tests: a copy of the benchmark
with a tiny configuration and tiny mixes under the real cell names."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

TINY_CONF = {
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
}
TINY_MIXES = {
    "chat": {
        "clients": 4, "warm_requests": 4, "queue": 4000, "epoch": 8,
        "prompt": {"median": 70, "sigma": 0.1, "min": 66, "max": 80},
        "output": {"median": 24, "sigma": 0.3, "min": 16, "max": 40},
        "serve": {"paged": True, "batch_size": 4, "max_len": 128,
                  "kv_block_size": 16},
        "trace_start_s": 0.5, "trace_seconds": 0.5,
        "check": {"requests": 3, "block": 3},
    },
    "mc-score": {
        "context": {"median": 40, "sigma": 0.5, "min": 20, "max": 90},
        "items_per_batch": 2, "batches_per_epoch": 3,
        "check": {"sequences": 16, "block": 8, "width": 128},
    },
}
# limits for the tiny cells, set between the tiny program's readings and
# the tiny control's (bench/test_bench_control.py)
TINY_LIMITS = {"yi9b-chat": {"max_gap": 1.2},
               "yi9b-score": {"max_logp_err": 0.55}}


def make_tiny(dst: Path) -> tuple[str, str]:
    """A checkout-like root at ``dst``: BENCHMARK.json whose cells all run
    one tiny configuration, and a copy of the benchmark directory with
    tiny mixes, cell limits and a 'cpu' row in the peaks table."""
    bench = dst / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*.py", "conftest.py", "testdata"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((BENCH / "configs" / "yi-9b.json").read_text())
    conf.update(TINY_CONF, name="tiny")
    (bench / "configs" / "tiny.json").write_text(json.dumps(conf))
    spec["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                        "file": "bench/configs/tiny.json", "why": "test"}]
    for w in spec["workloads"]:
        w["config"] = "tiny"
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    for name, over in TINY_MIXES.items():
        path = bench / "mixes" / f"{name}.json"
        mix = json.loads(path.read_text())
        mix.update(over)
        path.write_text(json.dumps(mix))
    for cell, limits in TINY_LIMITS.items():
        (bench / "cells" / f"{cell}.json").write_text(
            json.dumps({"limits": limits}))
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return str(dst), str(bench)


class Tiny:
    """A tiny benchmark root and harness runs on it, on the CPU."""

    def __init__(self, root: str, bench_dir: str):
        self.root, self.dir = root, bench_dir

    def run(self, cell, seed=5, seconds=2.0, trace=False, preset=None,
            dtype=None):
        """One harness run of a tiny cell (no chip check)."""
        from bench import harness, run

        return run.run_cell(harness.Bench(self.root, self.dir), cell, seed,
                            seconds, trace, t_start=0.0, require_chip=False,
                            preset=preset, dtype=dtype)


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """The tiny benchmark, with the persistent compilation cache left as
    the test process has it (the harness turns it on for real runs)."""
    import jax

    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "setup_compile_cache", lambda: None)
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield Tiny(*make_tiny(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)
