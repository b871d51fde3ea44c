"""bench/program_trace.py and the readers built on it, on a trace recorded
on a TPU v5e (``bench/testdata/record_serve_spans.py``): paged decode and
chunked-prefill iterations of a 2-layer model at yi-9b's widths, then one
scoring call, with the engine's spans and the model's named scopes."""
from __future__ import annotations

import math
import shutil
from pathlib import Path

import pytest

from bench import harness
from bench import program_trace as P
from bench import trace_reduce as T

TESTDATA = Path(__file__).resolve().parent / "testdata"
SPANS = TESTDATA / "serve_spans_v5e.xplane.pb"
GEMM = TESTDATA / "dsbp_fused_v5e.xplane.pb"
ROOT = Path(__file__).resolve().parents[1]
LANES, MAX_LEN, LAYERS = 4, 320, 2      # what the recording served
ITER_CHILDREN = ("serve.control", "serve.admit", "serve.cow", "serve.decode",
                 "serve.wait", "serve.tokens", "serve.chunk")
NEW_METRICS = ("host_self_ms.chat", "host_self_ms.score", "attn_ms.chat",
               "kv_gather_ms.chat", "kv_live_share.chat")


def _summary(path):
    out = T.summarize(T.load(str(path)))
    out.update(P.reduce(P.read_space(str(path))))
    return out


@pytest.fixture(scope="module")
def spans():
    return _summary(SPANS)


def _run(trace, only=None):
    """A harness run around a summary; ``only`` keeps the spans whose
    name starts with it."""
    if only is not None:
        trace = dict(trace, spans={k: v for k, v in trace["spans"].items()
                                   if k.startswith(only)})
    return {"trace": trace, "counts": {}, "peaks": None}


def _reader(metric):
    return harness.Bench(str(ROOT)).reader(metric)


def test_spans_of_an_iteration_add_up(spans):
    sp = spans["spans"]
    it = sp["serve.iter"]
    assert it["count"] >= 5
    assert (sp["serve.decode"]["count"] == sp["serve.wait"]["count"]
            == sp["serve.tokens"]["count"] > 0)
    assert 0 < sp["serve.chunk"]["count"] <= it["count"]
    inside = sum(sp[n]["total_s"] for n in ITER_CHILDREN if n in sp)
    assert it["self_s"] == pytest.approx(it["total_s"] - inside)
    waits = sum(v["total_s"] for k, v in sp.items()
                if k.startswith("serve.") and k.endswith("wait"))
    assert it["wait_s"] == pytest.approx(waits)
    assert sp["serve.chunk"]["wait_s"] == \
        pytest.approx(sp["serve.chunk_wait"]["total_s"])
    call = sp["score.call"]
    assert call["count"] == 1
    assert call["wait_s"] == pytest.approx(sp["score.wait"]["total_s"])


def test_decode_spans_carry_the_kv_rows(spans):
    dec = spans["spans"]["serve.decode"]
    args = dec["args"]
    assert args["kv_rows_gathered"] == \
        dec["count"] * LANES * MAX_LEN * LAYERS
    assert 0 < args["kv_rows_live"] < args["kv_rows_gathered"]


def test_idle_gaps_are_the_same_gaps_named_by_program_spans(spans):
    plain = T.summarize(T.load(str(SPANS)))["idle_gaps"]
    assert [s for _, s in spans["idle_gaps"]] == [s for _, s in plain]
    assert all(n.startswith(P.PROGRAM_SPANS) for n, _ in spans["idle_gaps"])
    idle = spans["window_s"] - spans["busy_s"]
    assert spans["span_idle_s"] == pytest.approx(
        sum(v["idle_s"] for v in spans["spans"].values()))
    assert 0 < spans["span_idle_s"] <= idle * (1 + 1e-9)


def test_scopes_split_the_programs(spans):
    scope = spans["scope_s"]
    # self-times per scope are the ops' self-times, regrouped
    assert sum(scope.values()) == pytest.approx(sum(spans["op_s"].values()))
    dec = {k.split("/", 1)[1]: v for k, v in scope.items()
           if k.startswith("_decode_paged_fn/")}
    assert {"qkv", "attn_out", "mlp_in", "mlp_out", "attention",
            "kv_gather", "kv_write", "lm_head", "layer_stack"} <= set(dec)
    assert dec.get("other", 0.0) <= 0.05 * sum(dec.values())
    assert {"_verify_paged_fn/attention", "_commit_paged_fn/kv_write",
            "_score/lm_head", "_score/mlp_in"} <= set(scope)


def test_names_of_programs_and_scopes():
    assert P._program("jit(_decode_paged_fn)/while/body/qkv/dot") == \
        "_decode_paged_fn"
    assert P._module_program("jit__decode_paged_fn(123)") == \
        "_decode_paged_fn"
    assert P._scope("jit(f)/layer_stack/while/body/closed_call/qkv/"
                    "jit(dsbp_matmul_fused)/pallas_call") == "qkv"
    assert P._scope("jit(f)/layer_stack/while/body/squeeze") == \
        "layer_stack"
    assert P._scope("jit(_commit_paged_fn)/kv_write/vmap(kv_write)/"
                    "scatter") == "kv_write"
    assert P._scope("jit(f)/vmap(jit(_where))/select_n") == "other"
    assert P._scope("jit(decode_attention)/reduce_max") == "other"
    assert P._scope("") == "other"


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_read_the_recorded_trace(spans, metric):
    only = "score." if metric.endswith(".score") else "serve."
    value = _reader(metric).read(_run(spans, only))
    assert value is not None and math.isfinite(value) and value > 0
    if metric.startswith(("attn_ms", "kv_gather_ms")):
        scope = "attention" if metric.startswith("attn") else "kv_gather"
        calls = sum(v for k, v in spans["module_calls"].items()
                    if "_decode_paged_fn" in k)
        assert value == pytest.approx(
            1e3 * spans["scope_s"][f"_decode_paged_fn/{scope}"] / calls)
    if metric.startswith("kv_live_share"):
        assert value < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_find_nothing_without_the_programs_marks(metric):
    """A trace of a program that opens no spans and names no scopes (as
    the parent commit's) gives no reading, and no error."""
    gemm = _summary(GEMM)
    assert gemm["spans"] == {} and gemm["span_idle_s"] == 0
    assert all(k.endswith("/other") for k in gemm["scope_s"])
    assert _reader(metric).read(_run(gemm)) is None
    assert _reader(metric).read({"trace": None, "counts": {}}) is None


def test_attach_adds_keys_once_and_keeps_the_old_ones(tmp_path, monkeypatch):
    monkeypatch.setattr(harness.Tracer, "summary", harness.Tracer.summary)
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    shutil.copy(GEMM, d / "host.xplane.pb")
    tracer = harness.Tracer.__new__(harness.Tracer)
    tracer.dir = str(tmp_path)
    plain = harness.Tracer.summary(tracer)
    P.attach()
    once = harness.Tracer.summary
    P.attach()
    assert harness.Tracer.summary is once
    out = harness.Tracer.summary(tracer)
    assert {k: out[k] for k in plain} == plain
    assert {"scope_s", "spans", "span_idle_s"} <= set(out)
