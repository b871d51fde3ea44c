"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are those
named ``/device:<KIND>:<n>``; on each, the ``XLA Ops`` line holds one event
per device operation and ``XLA Modules`` one per run of a compiled
program.  The traced window is the host span the benchmark opens around
it (``WINDOW_SPAN``), so busy time, idle gaps and op times are all taken
inside the same interval, on the trace's own clock.

``summarize`` gives, averaged over the device planes that ran anything:

* ``window_s`` and ``busy_s`` (union of op intervals inside the window);
* ``op_s`` and ``module_s``: device seconds per op name (self time: a
  ``while`` op less the ops of its body) and per program name, and
  ``module_calls`` per program name;
* ``op_info``: each op name's string stats (from its first event), for
  readers that match a kernel by what the trace shows of it;
* ``device_ops``: the ten kinds of op that took most time (an op's HLO
  name with its numeric suffix dropped, ``%fusion.12`` -> ``fusion``);
* ``idle_gaps``: the ten longest gaps with no op running, each named by the
  innermost host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

__all__ = ["WINDOW_SPAN", "find_xplane", "load", "summarize", "union_s",
           "op_kind"]

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _is_device(plane) -> bool:
    name = plane.name
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def _window(pd):
    """(start_ns, end_ns) of the benchmark's window span on a host plane."""
    for plane in pd.planes:
        if _is_device(plane):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    return ev.start_ns, ev.end_ns
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


def union_s(intervals, lo: float, hi: float) -> float:
    """Seconds covered by the union of ``(start_ns, end_ns)`` intervals,
    clipped to ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def _gaps(intervals, lo, hi):
    """Idle (start_ns, end_ns) stretches of ``[lo, hi]`` between ops."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _self_times(evs):
    """(name, seconds) per op event less the ops nested inside it (a
    ``while`` op spans its loop body's ops on the same line)."""
    out, stack = [], []   # stack of [end, name, self_ns]
    for s, e, name in sorted(evs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            out.append((n, own / 1e9))
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    out += [(n, own / 1e9) for _, n, own in stack]
    return out


def _host_spans(pd, lo, hi):
    spans = []
    for plane in pd.planes:
        if _is_device(plane):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.end_ns > lo and ev.start_ns < hi and ev.name != WINDOW_SPAN:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    return spans


def _name_gap(spans, s, e) -> str:
    mid = (s + e) / 2
    cover = [sp for sp in spans if sp[0] <= mid <= sp[1]]
    if not cover:
        return "no host span"
    return max(cover, key=lambda sp: sp[0])[2]


def op_kind(name: str) -> str:
    """``%dsbp_fused_kernel_call.3 = f32[..] custom-call(..)`` ->
    ``dsbp_fused_kernel_call``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


def _str_stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        if isinstance(v, str):
            out[k] = v
    return out


def summarize(pd, top: int = 10) -> dict:
    lo, hi = _window(pd)
    window_s = (hi - lo) / 1e9
    busy, op_s, module_s, calls = [], defaultdict(float), defaultdict(float), \
        defaultdict(int)
    op_info, gaps = {}, []
    n_dev = 0
    for plane in pd.planes:
        if not _is_device(plane):
            continue
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs = []
                for ev in line.events:
                    if ev.end_ns <= lo or ev.start_ns >= hi:
                        continue
                    s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
                    ops.append((s, e))
                    evs.append((s, e, ev.name))
                    if ev.name not in op_info:
                        op_info[ev.name] = _str_stats(ev)
                for name, secs in _self_times(evs):
                    op_s[name] += secs
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    if ev.end_ns <= lo or ev.start_ns >= hi:
                        continue
                    module_s[ev.name] += (min(ev.end_ns, hi)
                                          - max(ev.start_ns, lo)) / 1e9
                    calls[ev.name] += 1
        if not ops:
            continue
        n_dev += 1
        busy.append(union_s(ops, lo, hi))
        gaps += _gaps(ops, lo, hi)
    if not n_dev:
        return {"window_s": window_s, "busy_s": 0.0, "op_s": {},
                "module_s": {}, "module_calls": {}, "op_info": {},
                "device_ops": [], "idle_gaps": [], "devices": 0}
    kinds = defaultdict(float)
    for k, v in op_s.items():
        kinds[op_kind(k)] += v
    spans = _host_spans(pd, lo, hi)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "op_s": {k: v / n_dev for k, v in op_s.items()},
        "module_s": {k: v / n_dev for k, v in module_s.items()},
        "module_calls": dict(calls),
        "op_info": op_info,
        "device_ops": sorted(([k, v / n_dev] for k, v in kinds.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_name_gap(spans, s, e), (e - s) / 1e9]
                      for s, e in longest],
        "devices": n_dev,
    }
