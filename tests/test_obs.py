"""Serving observability (DESIGN.md §15).

Core contracts: the recorder is deterministic under a seeded
:class:`~repro.serve.faults.FaultPlan` (same plan => same event sequence
modulo timestamps), histogram bucket math follows Prometheus ``le``
semantics, the snapshot is JSON-able, ``Engine.last_stats`` stays
backwards-compatible with ``observe=True``, the schedulers' profiler spans
nest as DESIGN.md §15 lists them, the model's programs carry their named
scopes, and :func:`~repro.policy.reprice_from_telemetry` widens exactly the
layers the guard telemetry implicates.
"""
import dataclasses
import glob
import json
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.core.quantized import PRESETS
from repro.kernels.ops import quant_sat_stats
from repro.models import model as M
from repro.obs import (Histogram, MetricsRegistry, QuantHealth,
                       ServeRecorder, TraceRecorder, shift_drift)
from repro.policy import (DSBPPolicy, WIDEN_LADDER, reprice_from_telemetry,
                          widen_config)
from repro.serve import faults as FA
from repro.serve.engine import Engine, Request, ServeConfig


def _cfg(arch="yi-9b", **kw):
    return smoke_config(arch).replace(remat=False, **kw)


@pytest.fixture(scope="module")
def fparams():
    return M.init(jax.random.PRNGKey(0), _cfg())


def _reqs(cfg, lens, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(uid=f"r{i}", tokens=rng.integers(0, cfg.vocab_size, (l,)),
                    max_new_tokens=8, **kw)
            for i, l in enumerate(lens)]


def _paged_scfg(**kw):
    base = dict(max_len=32, batch_size=4, paged=True, kv_block_size=4)
    base.update(kw)
    return ServeConfig(**base)


def _fake_cache(poison=False):
    """A minimal cache pytree in the engine's entry layout."""
    k = jnp.ones((2, 4, 8), jnp.float32)
    v = jnp.ones((2, 4, 8), jnp.float32)
    if poison:
        k = k.at[0, 0, 0].set(jnp.nan)
    return {"units": [{"k": k, "v": v},
                      {"k": jnp.ones_like(k), "v": jnp.ones_like(v)}],
            "tail": []}


# ---------------------------------------------------------------------------
# metrics registry: bucket math and export round-trips
# ---------------------------------------------------------------------------

def test_histogram_le_bucket_math():
    h = Histogram(buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 4.0, 5.0):
        h.observe(v)
    # le semantics: value <= bound lands in that bucket
    assert h.counts == [2, 1, 1, 1]  # [<=1, <=2, <=4, +Inf]
    assert h.count == 5 and h.sum == pytest.approx(12.0)
    cum = h.cumulative()
    assert cum == [(1.0, 2), (2.0, 3), (4.0, 4), ("+Inf", 5)]


def test_histogram_rejects_non_ascending_buckets():
    with pytest.raises(ValueError, match="ascending"):
        Histogram(buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError, match="ascending"):
        Histogram(buckets=())


def test_counter_rejects_negative_and_kind_conflict():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match=">= 0"):
        reg.counter("c_total").inc(-1)
    reg.counter("c_total").inc(3)
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("c_total")
    assert reg.value("c_total") == 3


def test_registry_snapshot_roundtrip_and_prometheus():
    """The snapshot (the registry's one export) holds every family with
    its kind, help and labelled series, and survives a JSON round trip."""
    reg = MetricsRegistry()
    reg.counter("serve_requests_total", status="ok").inc(2)
    reg.counter("serve_requests_total", status="cancelled").inc()
    reg.gauge("serve_decode_tps").set(12.5)
    h = reg.histogram("serve_ttft_seconds", buckets=(0.1, 1.0), help="ttft")
    h.observe(0.05)
    h.observe(2.0)
    snap = reg.snapshot()
    assert json.loads(json.dumps(snap)) == snap
    fams = snap["families"]
    assert list(fams) == sorted(fams)
    req = fams["serve_requests_total"]
    assert req["kind"] == "counter"
    assert req["series"] == [{"labels": {"status": "cancelled"}, "value": 1},
                             {"labels": {"status": "ok"}, "value": 2}]
    assert fams["serve_decode_tps"]["series"] == [{"labels": {},
                                                   "value": 12.5}]
    ttft = fams["serve_ttft_seconds"]
    assert ttft["kind"] == "histogram" and ttft["help"] == "ttft"
    assert ttft["series"] == [{"labels": {}, "buckets": [0.1, 1.0],
                               "counts": [1, 0, 1], "sum": 2.05,
                               "count": 2}]


# ---------------------------------------------------------------------------
# trace recorder: span model, drops, chrome export
# ---------------------------------------------------------------------------

def test_trace_nesting_and_terminal_status():
    tr = TraceRecorder()
    tr.begin("a", "request", 0, prompt_len=4)
    tr.begin("a", "queued", 0)
    tr.begin("a", "prefill", 1)
    # ending "queued" must first auto-close the dangling inner "prefill"
    tr.end("a", "queued", 1)
    assert tr.open_spans("a") == ("request",)
    tr.end("a", "request", 2, status="ok")
    assert tr.complete("a")
    assert tr.terminal_status("a") == "ok"
    tree = tr.span_tree("a")
    assert tree["phase"] == "request" and tree["end_step"] == 2
    # open span has no terminal status
    tr2 = TraceRecorder()
    tr2.begin("b", "request", 0)
    assert tr2.terminal_status("b") is None
    tr2.end("b", "nonexistent", 1)  # no-op, nothing closed
    assert tr2.open_spans("b") == ("request",)


def test_trace_caps_and_counts_drops():
    tr = TraceRecorder(max_events=3)
    for i in range(5):
        tr.instant("a", "tick", i)
    assert len(tr.events) == 3 and tr.dropped == 2


def test_trace_chrome_export_structure():
    tr = TraceRecorder()
    tr.begin("a", "request", 0)
    tr.instant(None, "decode-step", 1, lanes=2)
    tr.end("a", "request", 2, status="ok")
    rows = tr.to_chrome()
    meta = [r for r in rows if r["ph"] == "M"]
    names = {r["args"]["name"] for r in meta}
    assert "repro.serve" in names and "scheduler" in names and "req a" in names
    inst = next(r for r in rows if r["ph"] == "i")
    assert inst["tid"] == 0 and inst["s"] == "t"  # scheduler pseudo-thread
    be = [r for r in rows if r["ph"] in ("B", "E")]
    assert all(r["tid"] == 1 for r in be)  # first uid -> tid 1
    assert be[-1]["args"]["status"] == "ok"


# ---------------------------------------------------------------------------
# engine integration: back-compat, determinism, guard telemetry
# ---------------------------------------------------------------------------

def test_last_stats_backcompat_and_token_parity(fparams):
    """observe=True must not change served tokens or the last_stats keys —
    the recorder is additive, never a rewrite of the snapshot view."""
    cfg = _cfg()
    prompts = _reqs(cfg, [5, 9])
    off = Engine(fparams, cfg, ServeConfig(max_len=32, batch_size=2))
    out_off = off.serve([dataclasses.replace(r) for r in prompts])
    on = Engine(fparams, cfg, ServeConfig(max_len=32, batch_size=2,
                                          observe=True))
    out_on = on.serve([dataclasses.replace(r) for r in prompts])
    assert set(off.last_stats) == set(on.last_stats)
    for u in out_off:
        assert np.array_equal(out_off[u], out_on[u])
    assert on.obs.complete_spans(on.last_stats["request_status"])
    assert off.obs.enabled is False and not off.obs.trace.events
    summ = on.obs.request_summary()
    assert set(summ) == set(out_on)
    for s in summ.values():
        assert s["status"] == "ok" and s["ttft_s"] >= 0 and s["tokens"] == 8


def test_recorder_determinism_under_seeded_plan(fparams):
    """Same seeded FaultPlan => identical event sequence modulo timestamps
    (durations live only in histograms, never in trace-event args)."""
    cfg = _cfg()
    reqs = _reqs(cfg, [5, 9, 7, 6], seed=3)
    uids = [r.uid for r in reqs]
    scfg = _paged_scfg(kv_blocks=13, max_active=4,
                       numeric_guard="quarantine", observe=True)

    def run():
        eng = Engine(fparams, cfg, scfg)
        plan = FA.FaultPlan.seeded(5, uids=uids, n_alloc=2, n_cow=1, n_nan=1,
                                   n_cancel=1, decode_calls=12,
                                   alloc_calls=10, steps=8, lanes=4)
        eng.serve([dataclasses.replace(r) for r in reqs], faults=plan)
        return eng, plan

    (a, pa), (b, pb) = run(), run()
    assert a.obs.trace.signature() == b.obs.trace.signature()
    assert a.obs.trace.dropped == 0
    assert a.last_stats["request_status"] == b.last_stats["request_status"]
    assert a.obs.complete_spans(a.last_stats["request_status"])
    # the fault observer saw exactly the plan's own injection tally
    assert dict(pa.injected) == dict(pb.injected)
    assert sum(pa.injected.values()) > 0
    for kind, n in pa.injected.items():
        got = a.obs.metrics.value("serve_faults_injected_total", kind=kind)
        assert (got or 0) == n, kind


def test_guard_trip_telemetry_under_nan_injection(fparams):
    cfg = _cfg()
    eng = Engine(fparams, cfg, _paged_scfg(numeric_guard="quarantine",
                                           observe=True))
    plan = FA.FaultPlan(nan_steps={1: "all"})
    eng.serve(_reqs(cfg, [5, 9]), faults=plan)
    obs = eng.obs
    assert obs.health.total_trips >= 2  # both lanes tripped
    # host-buffer injection never reaches the cache: unattributed, and no
    # innocent layer gets blamed
    assert obs.health.unattributed_trips == obs.health.total_trips
    assert obs.health.trips() == {}
    assert obs.metrics.value("serve_guard_trips_total") == \
        obs.health.total_trips
    trips = [e for e in obs.trace.events if e.phase == "guard-trip"]
    assert trips and all(e.args["entries"] == "unattributed" for e in trips)
    assert obs.complete_spans(eng.last_stats["request_status"])


# ---------------------------------------------------------------------------
# profiler spans and named scopes (the device trace's clock)
# ---------------------------------------------------------------------------

# program span -> the program spans that open directly inside it
_SPAN_CHILDREN = {
    None: {"serve.iter", "score.call"},
    "serve.iter": {"serve.control", "serve.admit", "serve.cow",
                   "serve.decode", "serve.wait", "serve.tokens",
                   "serve.chunk"},
    "serve.admit": {"serve.admit_wait", "serve.guard_wait"},
    "serve.decode": {"serve.guard_wait"},
    "serve.chunk": {"serve.chunk_wait", "serve.guard_wait"},
    "score.call": {"score.prepare", "score.run", "score.wait"},
}


def _traced_spans(log_dir, run):
    """(name, stats, parent name) of every program span ``run`` opens
    under a profiler session, in start order."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(log_dir)):
        run()
    path = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = sorted(((ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                          for ev in line.events
                          if ev.name.startswith(("serve.", "score."))),
                         key=lambda e: (e[0], -e[1]))
            stack = []
            for s, e, name, stats in evs:
                while stack and stack[-1][0] <= s:
                    stack.pop()
                out.append((name, stats, stack[-1][1] if stack else None))
                stack.append((e, name))
    return out


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_serve_spans_nest_one_iter_per_step(fparams, tmp_path, paged):
    cfg = _cfg()
    scfg = (_paged_scfg(chunk_prefill_tokens=8, prefill_bucket=4,
                        numeric_guard="quarantine") if paged
            else ServeConfig(max_len=32, batch_size=2,
                             numeric_guard="quarantine"))
    eng = Engine(fparams, cfg, scfg)
    reqs = _reqs(cfg, [5, 12, 9])
    eng.serve([dataclasses.replace(r) for r in reqs])  # compile outside
    spans = _traced_spans(
        tmp_path, lambda: eng.serve([dataclasses.replace(r) for r in reqs]))
    for name, _, parent in spans:
        assert name in _SPAN_CHILDREN[parent], (parent, name)
    steps = [st["step"] for name, st, _ in spans if name == "serve.iter"]
    assert steps == list(range(len(steps))) and steps
    seen = {name for name, _, _ in spans}
    assert {"serve.control", "serve.admit", "serve.admit_wait",
            "serve.decode", "serve.guard_wait", "serve.wait",
            "serve.tokens"} <= seen
    if paged:
        assert {"serve.cow", "serve.chunk", "serve.chunk_wait"} <= seen
        # the decode span carries the step's KV read counts
        rows = [st for name, st, _ in spans if name == "serve.decode"]
        assert sum(st["kv_rows_gathered"] for st in rows) == \
            eng.last_stats["kv_rows_gathered"] > 0
        assert sum(st["kv_rows_live"] for st in rows) == \
            eng.last_stats["kv_rows_live"]


def test_score_spans_nest(fparams, tmp_path):
    cfg = _cfg()
    eng = Engine(fparams, cfg, ServeConfig(max_len=32, batch_size=2))
    seqs, plens = [np.arange(9), np.arange(5)], [4, 2]
    eng.score_continuations(seqs, plens)
    spans = _traced_spans(tmp_path,
                          lambda: eng.score_continuations(seqs, plens))
    assert [(n, p) for n, _, p in spans] == [
        ("score.call", None), ("score.prepare", "score.call"),
        ("score.run", "score.call"), ("score.wait", "score.call")]


def test_kv_read_counts_paged_decode_rows(fparams):
    """Rows gathered: every lane's whole table per KV layer; rows live:
    position + 1 per decoding lane and KV layer.  The recorder's counters
    and last_stats agree."""
    cfg = _cfg()
    eng = Engine(fparams, cfg, _paged_scfg(observe=True))
    eng.serve(_reqs(cfg, [5, 9]))
    st = eng.last_stats
    per_step = eng.lanes * 32 * cfg.n_layers   # max_len 32, full attention
    assert st["kv_rows_gathered"] == st["decode_steps"] * per_step
    # two lanes admitted at 5 and 9 decode 7 steps each (8 tokens, the
    # first from prefill): positions 5..11 and 9..15
    assert st["kv_rows_live"] == cfg.n_layers * (sum(range(6, 13))
                                                 + sum(range(10, 17)))
    m = eng.obs.metrics
    assert m.value("serve_kv_rows_gathered_total") == st["kv_rows_gathered"]
    assert m.value("serve_kv_rows_live_total") == st["kv_rows_live"]
    assert m.value("serve_kv_bytes_gathered_total") > 0


def test_engine_serves_under_a_bare_hook_object(fparams):
    """The benchmark swaps ``engine.obs`` for an object whose hooks are
    no-op lambdas; the spans never go through ``engine.obs``."""
    class Bare:
        def __getattr__(self, name):
            return lambda *a, **k: None

    cfg = _cfg()
    reqs = _reqs(cfg, [5, 9])
    ref = Engine(fparams, cfg, _paged_scfg()).serve(
        [dataclasses.replace(r) for r in reqs])
    eng = Engine(fparams, cfg, _paged_scfg())
    eng.obs = Bare()
    out = eng.serve([dataclasses.replace(r) for r in reqs])
    assert all(np.array_equal(out[u], ref[u]) for u in ref)


def test_decode_program_carries_named_scopes(fparams):
    cfg = _cfg()
    eng = Engine(fparams, cfg, _paged_scfg())
    b = eng.lanes
    cache = M.init_paged_cache(cfg, b, eng.kv_blocks, 4, kv=eng.kv_spec)
    text = eng._decode_paged.lower(
        eng.params, {"tokens": jnp.zeros((b, 1), jnp.int32)}, cache,
        jnp.zeros((b, eng._table_width), jnp.int32),
        jnp.zeros(b, jnp.int32), jnp.ones(b, jnp.int32)).compile().as_text()
    segments = {seg for path in re.findall(r'op_name="([^"]*)"', text)
                for seg in path.split("/")}
    assert {"attention", "kv_gather", "qkv", "lm_head"} <= segments


# ---------------------------------------------------------------------------
# quant health: attribution, frozen-scale saturation, shift drift
# ---------------------------------------------------------------------------

def test_attribute_trip_blames_poisoned_entry_only():
    qh = QuantHealth()
    assert qh.attribute_trip(_fake_cache(poison=True)) == ["units.0"]
    assert qh.trips() == {"units.0": 1}
    assert qh.unattributed_trips == 0
    assert qh.attribute_trip(_fake_cache(poison=False)) == []
    assert qh.unattributed_trips == 1
    assert qh.total_trips == 2


def test_quant_sat_stats_frozen_scale():
    x = np.linspace(-4.0, 4.0, 64, dtype=np.float32)
    clean = quant_sat_stats(x, "e5m7")  # per-call scale: nothing saturates
    assert clean["overflow"] == 0 and clean["total"] == 64
    assert clean["tscale"] > 0
    # the SAME values under a scale frozen on a 1e6x smaller distribution
    frozen = quant_sat_stats(x, "e5m7", tscale=clean["tscale"] * 1e6)
    assert frozen["overflow"] > 0
    nanful = quant_sat_stats(np.array([1.0, np.nan, np.inf]), "e5m7")
    assert nanful["nonfinite"] == 2


def test_sample_cache_freezes_scale_and_fills_shift_hist():
    qh = QuantHealth()
    qh.sample_cache(_fake_cache())
    ts0 = qh.entries["units.0"].tscale
    assert ts0 is not None and qh.entries["units.0"].shift_hist.sum() > 0
    qh.sample_cache(_fake_cache())
    assert qh.entries["units.0"].tscale == ts0  # frozen, not re-derived
    assert qh.entries["units.0"].samples == 2
    snap = qh.snapshot()
    json.dumps(snap)
    assert snap["entries"]["units.0"]["total"] > 0


def test_shift_drift_bounds():
    a = np.array([10, 0, 0])
    assert shift_drift(a, a) == 0.0
    assert shift_drift(a, np.array([0, 0, 10])) == pytest.approx(1.0)
    # length mismatch pads with zeros instead of raising
    assert shift_drift(np.array([1.0]), np.array([1.0, 0.0, 0.0])) == 0.0


# ---------------------------------------------------------------------------
# telemetry -> policy repricing
# ---------------------------------------------------------------------------

_KEYS = ("units/0/attn/wq", "units/0/ff/w1", "units/1/attn/wq")


def test_reprice_widens_exactly_the_tripping_layer():
    pol = DSBPPolicy.uniform("efficient", _KEYS)
    new = reprice_from_telemetry(pol, {"units.0": 2})
    assert new.layers["units/0/attn/wq"] == PRESETS["precise"]
    assert new.layers["units/0/ff/w1"] == PRESETS["precise"]
    assert new.layers["units/1/attn/wq"] == PRESETS["efficient"]  # untouched
    assert new.default == pol.default
    assert pol.layers["units/0/attn/wq"] == PRESETS["efficient"]  # no mutation
    rp = new.meta["reprice"]
    assert rp["flagged"] == {"units.0": "guard_trips=2"}
    assert set(rp["widened"]) == {"units/0/attn/wq", "units/0/ff/w1"}
    assert rp["unmatched"] == []


def test_reprice_min_trips_and_unmatched():
    pol = DSBPPolicy.uniform("efficient", _KEYS)
    same = reprice_from_telemetry(pol, {"units.0": 1}, min_trips=3)
    assert same.layers == pol.layers  # below threshold: nothing flagged
    missed = reprice_from_telemetry(pol, {"units.7": 5})
    assert missed.layers == pol.layers
    assert missed.meta["reprice"]["unmatched"] == ["units.7"]


def test_reprice_accepts_health_object_and_kv_spec():
    qh = QuantHealth()
    qh.record_trip("units.0", 2)
    pol = DSBPPolicy.uniform("efficient", _KEYS).with_kv(
        {"units.0": "kv4", "units.1": "kv4"})
    new = reprice_from_telemetry(pol, qh)
    assert new.layers["units/0/attn/wq"] == PRESETS["precise"]
    assert new.kv_spec_for("units.0").bits == 6  # kv4 -> kv6
    assert new.kv_spec_for("units.1").bits == 4  # untouched
    assert new.meta["reprice"]["kv_widened"] == {"units.0": 6}


def test_reprice_direct_layer_key_and_ladder_top():
    pol = DSBPPolicy.uniform("efficient", _KEYS)
    new = reprice_from_telemetry(pol, {"units/1/attn/wq": 1})
    assert new.layers["units/1/attn/wq"] == PRESETS["precise"]
    assert new.layers["units/0/attn/wq"] == PRESETS["efficient"]
    # the widest rung is a fixed point: flagged but not widened, not lost
    top = DSBPPolicy.uniform("e5m7_fixed", _KEYS)
    again = reprice_from_telemetry(top, {"units.0": 9})
    assert again.layers == top.layers
    assert again.meta["reprice"]["widened"] == {}
    assert again.meta["reprice"]["unmatched"] == []


def test_reprice_drift_flag_with_calibration():
    qh = QuantHealth()
    e = qh.entry("units.0")
    e.shift_hist[0] = 100  # all mass at shift 0
    baseline = {"units.0": np.array([0, 0, 0, 100])}  # all mass at shift 3
    pol = DSBPPolicy.uniform("efficient", _KEYS)
    new = reprice_from_telemetry(pol, qh, calibration=baseline,
                                 drift_threshold=0.5)
    assert new.layers["units/0/attn/wq"] == PRESETS["precise"]
    assert "shift_drift" in new.meta["reprice"]["flagged"]["units.0"]


def test_widen_config_ladder_order():
    widths = [PRESETS[n].input_cfg.b_fix + PRESETS[n].weight_cfg.b_fix
              for n in WIDEN_LADDER]
    assert widths == sorted(widths)
    assert widen_config(None) is None
    assert widen_config(PRESETS["efficient"]) == PRESETS["precise"]
    assert widen_config(PRESETS["e5m7_fixed"]) == PRESETS["e5m7_fixed"]


def test_repriced_policy_loads_through_checkpoint_path(tmp_path):
    pol = DSBPPolicy.uniform("efficient", _KEYS).with_kv({"units.0": "kv4"})
    new = reprice_from_telemetry(pol, {"units.0": 1})
    path = new.save(str(tmp_path), step=3)
    back = DSBPPolicy.load(str(tmp_path))
    assert back.layers["units/0/attn/wq"] == PRESETS["precise"]
    assert back.kv_spec_for("units.0").bits == 6
    assert back.meta["reprice"]["flagged"] == {"units.0": "guard_trips=1"}
    assert path


# ---------------------------------------------------------------------------
# recorder-level unit behaviour (no engine)
# ---------------------------------------------------------------------------

def test_recorder_full_lifecycle_and_preempt_cycle():
    rec = ServeRecorder(enabled=True)
    rec.serve_start("paged", [("a", 4)])
    rec.admitted("a", 0, prompt_len=4)
    rec.first_token("a", 1)
    rec.decode_step(1, 1, 0.001)
    rec.preempted("a", 2)
    rec.admitted("a", 3, resumed=True)
    rec.first_token("a", 3)
    rec.terminal("a", "ok", 5, tokens=4)
    rec.serve_end({"decode_tokens": 4, "decode_tps": 100.0,
                   "prefix_lookups": 2, "prefix_hit_blocks": 3})
    assert rec.complete_spans({"a": "ok"})
    assert not rec.complete_spans({"a": "cancelled"})  # status must match
    tree = rec.trace.span_tree("a")
    phases = [c["phase"] for c in tree["children"]]
    assert phases == ["queued", "prefill", "decode", "queued", "prefill",
                      "decode"]  # preempt-resume re-opens the cycle
    assert rec.metrics.value("serve_preemptions_total") == 1
    assert rec.metrics.value("serve_resumed_total") == 1
    assert rec.metrics.value("serve_decode_tokens_total") == 4
    assert rec.metrics.value("serve_prefix_hit_rate") == pytest.approx(1.5)
    summ = rec.request_summary()["a"]
    assert summ["tok_s"] > 0 and summ["total_s"] >= summ["ttft_s"]


def test_recorder_disabled_is_inert():
    rec = ServeRecorder(enabled=False)
    rec.serve_start("dense", [("a", 4)])
    rec.admitted("a", 0)
    rec.guard_trip(["a"], 1, cache=_fake_cache(poison=True))
    rec.terminal("a", "ok", 2)
    rec.serve_end({"decode_tokens": 4})
    assert not rec.trace.events and not rec.requests
    assert rec.health.total_trips == 0
    assert rec.metrics.snapshot()["families"] == {}
