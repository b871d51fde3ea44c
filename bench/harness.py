"""Finding a cell's parts by name, and the pieces of a run around them.

Everything a cell needs sits in a file of its own under the benchmark's
directory and is found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — the configuration as it is run;
* ``mixes/<traffic>.json`` — the traffic mix's parameters, naming the
  driver module (``drive_<kind>.py``) that runs them;
* ``cells/<cell>.json`` — the cell's limits and its own serving knobs;
* ``metrics/<metric>.py`` (or ``metrics/<metric before the first dot>.py``)
  — the reader of a per-layer metric: ``read(run) -> float | None``.

A later cell, mix, configuration or metric is new files and new entries
only.
"""
from __future__ import annotations

import importlib.util
import json
import os
import tempfile
import time

__all__ = ["BenchError", "Bench", "Tracer", "CompileCounter",
           "process_start"]

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class BenchError(Exception):
    """A cell, name or device the benchmark cannot run."""


def _read_json(path: str, what: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"no {what} file {path}") from None


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files under the benchmark's directory."""

    def __init__(self, root: str, bench_dir: str = BENCH_DIR):
        self.root = root
        self.dir = bench_dir
        self.spec = _read_json(os.path.join(root, "BENCHMARK.json"),
                               "benchmark")

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"unknown cell {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _read_json(os.path.join(self.root, c["file"]),
                                  f"configuration {name!r}")
        raise BenchError(f"unknown configuration {name!r}")

    def mix(self, name: str) -> dict:
        return _read_json(os.path.join(self.dir, "mixes", f"{name}.json"),
                          f"traffic mix {name!r}")

    def cell_file(self, name: str) -> dict:
        return _read_json(os.path.join(self.dir, "cells", f"{name}.json"),
                          f"cell {name!r}")

    def driver(self, mix: dict):
        kind = mix.get("driver", "")
        path = os.path.join(self.dir, f"drive_{kind}.py")
        if not os.path.isfile(path):
            raise BenchError(f"no traffic driver {kind!r} ({path})")
        return _load(path, f"bench_drive_{kind}")

    def metrics_for(self, cell: dict, trace: bool) -> list[dict]:
        """The metric entries a cell reports: end-to-end ones with
        ``--trace 0``, per-layer ones with ``--trace 1``."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
        if not trace:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if cell["name"] in m.get("workloads", [cell["name"]])
                and m["moves"] in names]

    def reader(self, metric: str):
        """The reader module of a per-layer metric, by its full name or
        by the part before its first dot."""
        for stem in (metric, metric.split(".")[0]):
            path = os.path.join(self.dir, "metrics", f"{stem}.py")
            if os.path.isfile(path):
                return _load(path, f"bench_metric_{stem.replace('.', '_')}")
        raise BenchError(f"no reader for metric {metric!r} under "
                         f"{os.path.join(self.dir, 'metrics')}")

    def peaks(self, device_kind: str) -> dict:
        table = _read_json(os.path.join(self.dir, "peaks.json"), "peaks")
        try:
            return table["devices"][device_kind]
        except KeyError:
            raise BenchError(f"device kind {device_kind!r} is not in "
                             f"peaks.json") from None


class Tracer:
    """The profiler around part of a window, in a temporary directory;
    the window span names the traced interval in the trace itself."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None
        self.started = False

    def start(self):
        import jax

        from bench import trace_reduce

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()
        self.started = True

    def stop(self):
        import jax

        if self._span is None:
            return
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()

    def summary(self) -> dict:
        from bench import trace_reduce

        return trace_reduce.summarize(
            trace_reduce.load(trace_reduce.find_xplane(self.dir)))

    def cleanup(self):
        import shutil

        shutil.rmtree(self.dir, ignore_errors=True)


class CompileCounter:
    """Programs compiled or loaded from the persistent cache while open,
    counted from ``jax.monitoring``'s backend-compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.open = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.open and event == self.EVENT:
            self.count += 1


def process_start() -> float:
    """``time.perf_counter()`` at the moment this process started (Linux:
    from ``/proc``), else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()
