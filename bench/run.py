#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's configuration, traffic mix,
limits and metric readers are found by name (``bench/harness.py``).  The
run builds the cell's weights from ``--seed``, warms up every shape its
traffic uses (set-up), measures for ``--seconds`` seconds, frees the
program and checks what the window produced against the plain reference
(``bench/check.py``).  ``--trace 1`` profiles part of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each compared number with its
limit); the numbers compared are also the last lines of standard error.
Without a TPU, with fewer chips than the cell asks for, or on a device
kind that ``bench/peaks.json`` does not list, it exits nonzero and prints
no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):  # the benchmark, the program
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402

__all__ = ["run_cell", "main"]


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _devices(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise harness.BenchError(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise harness.BenchError(f"the cell needs {chips} chips, JAX found "
                                 f"{len(devs)}")
    return devs[:chips]


def _peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(bench: harness.Bench, name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_chip: bool = True,
             preset: str | None = None, dtype: str | None = None) -> dict:
    """One run of cell ``name``; returns the result object.  ``preset``
    and ``dtype`` replace the configuration's DSBP preset and activation
    dtype (the controls)."""
    cell = bench.cell(name)
    conf = bench.config(cell["config"])
    mix = bench.mix(cell["traffic"])
    cell_file = bench.cell_file(name)
    metrics = bench.metrics_for(cell, trace)
    readers = ({m["name"]: bench.reader(m["name"]) for m in metrics}
               if trace else {})
    drive = bench.driver(mix)
    seed = int(seed) % 2 ** 64

    import jax

    from repro.launch.compile_cache import setup_compile_cache

    devs = _devices(cell["chips"], require_chip)
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = devs[0].device_kind
    peaks = bench.peaks(kind) if require_chip or trace else None
    counter = harness.CompileCounter()

    from bench import program

    cfg = program.arch_config(conf, preset, dtype)
    driver = drive.Driver(cfg, conf, mix, cell_file, seed)
    driver.setup()
    tracer = harness.Tracer() if trace else None
    counter.open = True
    try:
        meas = driver.window(seconds, tracer)
    finally:
        counter.open = False
    setup_s = meas["t0"] - t_start
    memory_peak = _peak_bytes(devs)
    summary = None
    if tracer is not None:
        summary = tracer.summary() if tracer.started else None
        tracer.cleanup()
    driver.release()
    gc.collect()
    numbers, over = driver.check()
    from bench.check import verdict

    correct, shown = verdict(numbers, cell_file.get("limits", {}))
    correct = correct and meas["failed"] == 0

    run = {"cfg": cfg, "dims": program.dims_of(cfg), "peaks": peaks,
           "counts": meas["counts"], "trace": summary,
           "window_compiles": counter.count, "device_kind": kind}
    out_metrics = {}
    for m in metrics:
        if m["name"] == "setup_s":
            value = setup_s
        elif m["name"] in meas["e2e"]:
            value = meas["e2e"][m["name"]]
        else:
            value = readers[m["name"]].read(run)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": meas["attempted"],
              "failed": meas["failed"], "metrics": out_metrics,
              "device": device, "samples": meas["samples"],
              "checked": over, "window_compiles": counter.count}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = shown
    return result


def main(argv=None, *, root: str = ROOT, bench_dir: str = harness.BENCH_DIR,
         require_chip: bool = True) -> int:
    t_start = harness.process_start()
    args = _parse(argv)
    try:
        bench = harness.Bench(root, bench_dir)
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start,
                          require_chip=require_chip)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
