"""bench/trace_reduce.py on a small trace recorded on a TPU v5e: eight
runs of the fused DSBP GEMM (16 x 256 x 256, compiled Pallas kernel), 4 ms
apart, inside the benchmark's window span."""
from __future__ import annotations

import shutil
from pathlib import Path

import pytest

from bench import trace_reduce as T
from bench.harness import Bench

TRACE = Path(__file__).resolve().parent / "testdata" / "dsbp_fused_v5e.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return T.summarize(T.load(str(TRACE)))


def test_window_and_busy_time(summary):
    assert summary["devices"] == 1
    assert summary["window_s"] > 0.03
    # a few microseconds of work per 4 ms: the device is idle nearly always
    assert 0 < summary["busy_s"] < 0.01 * summary["window_s"]


def test_kernel_and_program_names(summary):
    reader = Bench(str(TRACE.parents[2])).reader("gemm_roofline.score")
    gemm = [k for k in summary["op_s"]
            if reader.is_gemm(k, summary["op_info"].get(k, {}))]
    assert gemm and all("dsbp_fused_kernel_call" in k for k in gemm)
    assert any(k.startswith("jit_") for k in summary["module_s"])
    assert sum(summary["module_calls"].values()) >= 1


def test_breakdown_lists(summary):
    ops = summary["device_ops"]
    assert 0 < len(ops) <= 10
    assert ops[0][0] == "dsbp_fused_kernel_call"
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    gaps = summary["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    # busy time and the idle gaps tile the window
    total = summary["busy_s"] + sum(s for _, s in gaps)
    assert total <= summary["window_s"] * (1 + 1e-9)


def test_union_clips_and_merges():
    ns = 1e9
    iv = [(0, 2 * ns), (1 * ns, 3 * ns), (5 * ns, 6 * ns), (9 * ns, 12 * ns)]
    assert T.union_s(iv, 0, 10 * ns) == pytest.approx(3 + 1 + 1)
    assert T.union_s(iv, 2.5 * ns, 5.5 * ns) == pytest.approx(1.0)
    assert T.union_s([], 0, ns) == 0.0


def test_op_kind():
    assert T.op_kind("%fusion.12 = f32[] fusion(..)") == "fusion"
    assert T.op_kind("%dsbp_fused_kernel_call.3 = f32[8] custom-call()") \
        == "dsbp_fused_kernel_call"
    assert T.op_kind("%copy-start = (f32[]) copy-start()") == "copy-start"


def test_find_xplane(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    shutil.copy(TRACE, d / "host.xplane.pb")
    assert T.find_xplane(str(tmp_path)).endswith("host.xplane.pb")
    with pytest.raises(FileNotFoundError):
        T.find_xplane(str(tmp_path / "plugins" / "none"))
