"""Device time of the mixture-of-experts layer's named parts in a trace.

The serving MoE names its parts with ``jax.named_scope`` (``router``,
``expert_dispatch``, ``experts_in``, ``experts_out``, ``expert_combine``;
``models/moe.py``).  ``bench/program_trace.py`` attributes an op to the
innermost of its own scopes, which lie outside these, so this module
reads the same ``.xplane.pb`` again with its parser and gives
``expert_scope_s``: device self-seconds per ``"<program>/<scope>"``
inside the benchmark's window, for the ops under one of these scopes.

:func:`attach` makes the harness add that key to every traced run's
summary; each reader of a metric built on it calls it when loaded.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

from bench import program_trace as P
from bench import trace_reduce as TR

__all__ = ["SCOPES", "expert_scope_s", "attach"]

SCOPES = ("router", "expert_dispatch", "experts_in", "experts_out",
          "expert_combine")


def _scope(tf_op: str) -> str | None:
    for seg in reversed(tf_op.split("/")):
        if seg in SCOPES:
            return seg
    return None


def expert_scope_s(space) -> dict:
    """Device self-seconds per ``"<program>/<scope>"`` of the expert
    scopes inside the window, averaged over the devices that ran ops."""
    lo, hi = P._window(space)
    out, n_dev = defaultdict(float), 0
    for plane in space.planes:
        if not TR._is_device(plane):
            continue
        stat = P._stat_names(plane)
        modules = []
        for line in plane.lines:
            if line.name == TR.MODULES_LINE:
                modules = sorted((s, e, P._module_program(name))
                                 for s, e, name, _, _ in P._events(plane, line))
        starts = [m[0] for m in modules]
        ops = []
        for line in plane.lines:
            if line.name != TR.OPS_LINE:
                continue
            for s, e, _, md, _ in P._events(plane, line):
                if e <= lo or s >= hi:
                    continue
                tf_op = next((st.str_value for st in md.stats
                              if stat.get(st.metadata_id) == "tf_op"), "")
                i = bisect.bisect_right(starts, s) - 1
                prog = (modules[i][2] if i >= 0 and s < modules[i][1]
                        else P._program(tf_op) or "unknown")
                ops.append((max(s, lo), min(e, hi),
                            f"{prog}/{_scope(tf_op)}"))
        if not ops:
            continue
        n_dev += 1
        for key, secs in TR._self_times(ops):
            if not key.endswith("/None"):
                out[key] += secs
    return {k: v / n_dev for k, v in out.items()} if n_dev else {}


def attach():
    """Make ``harness.Tracer.summary`` add ``expert_scope_s``.  Idempotent."""
    from bench import harness

    plain = harness.Tracer.summary
    if getattr(plain, "expert_trace", False):
        return

    def summary(self):
        out = plain(self)
        out["expert_scope_s"] = expert_scope_s(
            P.read_space(TR.find_xplane(self.dir)))
        return out

    summary.expert_trace = True
    summary.program_trace = getattr(plain, "program_trace", False)
    harness.Tracer.summary = summary
