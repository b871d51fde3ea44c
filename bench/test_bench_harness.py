"""The harness's guards and its look-up by name, on the CPU."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import harness, run

ROOT = str(Path(__file__).resolve().parents[1])


def test_cpu_backend_fails_with_no_result_line(capsys):
    rc = run.main(["--workload", "yi9b-score", "--seed", "1", "--seconds",
                   "1", "--trace", "0"], root=ROOT)
    out, err = capsys.readouterr()
    assert rc != 0
    assert out.strip() == ""
    assert "no TPU" in err


def test_unknown_device_kind_fails_with_no_result_line(tiny, capsys):
    root, bench_dir = tiny.root, tiny.dir
    table = Path(bench_dir) / "peaks.json"
    peaks = json.loads(table.read_text())
    del peaks["devices"]["cpu"]
    table.write_text(json.dumps(peaks))
    rc = run.main(["--workload", "yi9b-score", "--seed", "1", "--seconds",
                   "1", "--trace", "1"], root=root, bench_dir=bench_dir,
                  require_chip=False)
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "not in peaks.json" in err
    with pytest.raises(harness.BenchError):
        harness.Bench(ROOT).peaks("TPU v9")


@pytest.mark.parametrize("look", [
    lambda b: b.cell("no-such-cell"),
    lambda b: b.config("no-such-config"),
    lambda b: b.mix("no-such-mix"),
    lambda b: b.cell_file("no-such-cell"),
    lambda b: b.reader("no_such_metric.chat"),
    lambda b: b.driver({"driver": "no_such_driver"}),
])
def test_unknown_names_are_errors(look):
    with pytest.raises(harness.BenchError):
        look(harness.Bench(ROOT))


def test_every_named_part_of_the_benchmark_is_found():
    b = harness.Bench(ROOT)
    for w in b.spec["workloads"]:
        b.cell_file(w["name"])
        b.config(w["config"])
        b.driver(b.mix(w["traffic"]))
        for m in b.metrics_for(w, trace=True):
            assert hasattr(b.reader(m["name"]), "read")
        assert {m["name"] for m in b.metrics_for(w, trace=False)} >= {"setup_s"}


def test_dropped_in_files_are_found_by_name(tiny):
    root, bench_dir = tiny.root, tiny.dir
    bench = Path(bench_dir)
    (bench / "configs" / "other.json").write_text(
        (bench / "configs" / "tiny.json").read_text())
    (bench / "mixes" / "other-mix.json").write_text(
        (bench / "mixes" / "mc-score.json").read_text())
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec = json.loads((Path(root) / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "other", "source": "test",
                            "file": "bench/configs/other.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "other-cell", "config": "other",
                              "traffic": "other-mix", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "score_tok_s":
            m["workloads"].append("other-cell")
    spec["per_layer"].append({"name": "new_metric.score", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "score_tok_s"})
    (Path(root) / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "cells" / "other-cell.json").write_text(
        json.dumps({"limits": {"max_logp_err": 0.5}}))
    b = harness.Bench(root, bench_dir)
    cell = b.cell("other-cell")
    assert b.config(cell["config"])["hidden_size"] == 128
    assert b.mix(cell["traffic"])["driver"] == "score"
    names = [m["name"] for m in b.metrics_for(cell, trace=True)]
    assert "new_metric.score" in names
    assert b.reader("new_metric.score").read({}) == 42.0
    res = tiny.run("other-cell", trace=True)
    assert res["metrics"]["new_metric.score"]["value"] == 42.0


@pytest.mark.parametrize("cell,metric", [("yi9b-chat", "out_tok_s"),
                                         ("yi9b-score", "score_tok_s")])
def test_a_tiny_run_prints_the_contract_fields(tiny, cell, metric):
    res = tiny.run(cell)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"][metric]["value"] > 0
    assert res["metrics"]["setup_s"]["unit"] == "s"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert res["window_compiles"] == 0
