"""What the program writes into a profiler trace: its spans and scopes.

The serving engine opens a profiler span around each phase of a scheduler
iteration (``serve.*``) and of a scoring call (``score.*``); the model
names the parts of its programs with ``jax.named_scope`` (``qkv``,
``attention``, ``kv_gather``, ...), which XLA keeps in each op's ``tf_op``
metadata.  ``jax.profiler.ProfileData`` shows an op only by its HLO text,
so this module reads the ``.xplane.pb`` itself, with ``protobuf`` alone:
the schema below is the part of ``tsl/profiler/protobuf/xplane.proto`` it
reads.  Times are floored to whole nanoseconds as ``ProfileData`` floors
them, so the idle gaps are the ones ``bench/trace_reduce.py`` finds.

``reduce`` gives, inside the benchmark's window span:

* ``scope_s``: device self-seconds per ``"<program>/<scope>"``: the
  program is the XLA module the op ran in (``jit__decode_paged_fn(..)``
  -> ``_decode_paged_fn``; where no module covers it, the first segment of
  its ``tf_op`` path), the scope the innermost segment of that path that
  is one of ``SCOPES`` (a transform's ``vmap(kv_write)`` counts as
  ``kv_write``), else ``other``;
* ``spans``: per program span name, over the spans wholly inside the
  window, ``count``, ``total_s``, ``self_s`` (less the program spans
  directly inside), ``wait_s`` (the part under spans whose name ends in
  ``wait``, the ones that block on the device, the span itself included)
  and ``args`` (each integer stat summed, ``step`` aside); and ``idle_s``:
  the device-idle seconds for which the span is the innermost program
  span, over every span the window cuts;
* ``span_idle_s``: device-idle seconds under any program span;
* ``idle_gaps``: the ten longest idle gaps, each named by the innermost
  program span covering its middle, else as ``trace_reduce`` names it.

:func:`attach` makes the harness add these keys to every traced run's
summary; each reader of a metric built on them calls it when loaded.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

from bench import trace_reduce as TR

__all__ = ["SCOPES", "PROGRAM_SPANS", "read_space", "reduce", "attach",
           "scope_ms_per_run"]

SCOPES = ("qkv", "attn_out", "mlp_in", "mlp_out", "attention", "kv_gather",
          "kv_write", "lm_head", "sample", "layer_stack")
PROGRAM_SPANS = ("serve.", "score.")
WAIT = "wait"   # serve.wait, serve.chunk_wait, score.wait, ...

_XSPACE = None


def _schema():
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(name, fields, parent=None, oneof=None):
        """``oneof`` (name, field numbers): the fields of a one-of, which
        keeps a zero value on the wire (a write-back must not drop it)."""
        m = (parent.nested_type if parent else fd.message_type).add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof[0])
        for fname, number, ftype, repeated, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if type_name:
                f.type_name = f".bench_xplane.{type_name}"
            if oneof and number in oneof[1]:
                f.oneof_index = 0
        return m

    MSG, STR, BYT, I64, U64, DBL = (F.TYPE_MESSAGE, F.TYPE_STRING,
                                    F.TYPE_BYTES, F.TYPE_INT64,
                                    F.TYPE_UINT64, F.TYPE_DOUBLE)
    message("XSpace", [("planes", 1, MSG, True, "XPlane")])
    plane = message("XPlane", [
        ("id", 1, I64, False, ""), ("name", 2, STR, False, ""),
        ("lines", 3, MSG, True, "XLine"),
        ("event_metadata", 4, MSG, True, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, MSG, True, "XPlane.StatMetadataEntry")])
    message("EventMetadataEntry", [("key", 1, I64, False, ""),
                                   ("value", 2, MSG, False, "XEventMetadata")],
            plane)
    message("StatMetadataEntry", [("key", 1, I64, False, ""),
                                  ("value", 2, MSG, False, "XStatMetadata")],
            plane)
    message("XLine", [("id", 1, I64, False, ""), ("name", 2, STR, False, ""),
                      ("timestamp_ns", 3, I64, False, ""),
                      ("events", 4, MSG, True, "XEvent")])
    message("XEvent", [("metadata_id", 1, I64, False, ""),
                       ("offset_ps", 2, I64, False, ""),
                       ("num_occurrences", 5, I64, False, ""),
                       ("duration_ps", 3, I64, False, ""),
                       ("stats", 4, MSG, True, "XStat")],
            oneof=("data", (2, 5)))
    message("XStat", [("metadata_id", 1, I64, False, ""),
                      ("double_value", 2, DBL, False, ""),
                      ("uint64_value", 3, U64, False, ""),
                      ("int64_value", 4, I64, False, ""),
                      ("str_value", 5, STR, False, ""),
                      ("bytes_value", 6, BYT, False, ""),
                      ("ref_value", 7, U64, False, "")],
            oneof=("value", (2, 3, 4, 5, 6, 7)))
    message("XEventMetadata", [("id", 1, I64, False, ""),
                               ("name", 2, STR, False, ""),
                               ("stats", 5, MSG, True, "XStat")])
    message("XStatMetadata", [("id", 1, I64, False, ""),
                              ("name", 2, STR, False, "")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def read_space(path: str):
    """The parsed ``XSpace`` of a ``.xplane.pb`` file."""
    global _XSPACE
    if _XSPACE is None:
        _XSPACE = _schema()
    with open(path, "rb") as f:
        return _XSPACE.FromString(f.read())


def _events(plane, line):
    """(start_ns, end_ns, name, metadata, event) per event of a line."""
    meta = {e.key: e.value for e in plane.event_metadata}
    for ev in line.events:
        s = line.timestamp_ns + ev.offset_ps // 1000
        md = meta[ev.metadata_id]
        yield s, s + ev.duration_ps // 1000, md.name, md, ev


def _stat_names(plane):
    return {e.key: e.value.name for e in plane.stat_metadata}


def _program(tf_op: str) -> str:
    """``jit(f)/...`` -> ``f``."""
    head = tf_op.split("/", 1)[0]
    return head[4:-1] if head.startswith("jit(") else head


def _module_program(name: str) -> str:
    """``jit__decode_paged_fn(123)`` -> ``_decode_paged_fn``."""
    base = name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def _scope(tf_op: str) -> str:
    for seg in reversed(tf_op.split("/")):
        while seg.endswith(")") and "(" in seg:   # vmap(kv_write) -> kv_write
            seg = seg[seg.index("(") + 1:-1]
        if seg in SCOPES:
            return seg
    return "other"


def _window(space):
    for plane in space.planes:
        if TR._is_device(plane):
            continue
        for line in plane.lines:
            for s, e, name, _, _ in _events(plane, line):
                if name == TR.WINDOW_SPAN:
                    return s, e
    raise ValueError(f"the trace holds no {TR.WINDOW_SPAN!r} span")


def _device(space, lo, hi):
    """(scope_s, gaps per device plane) inside the window."""
    scope_s, gaps, n_dev = defaultdict(float), [], 0
    for plane in space.planes:
        if not TR._is_device(plane):
            continue
        stat = _stat_names(plane)
        modules, ops = [], []
        for line in plane.lines:
            if line.name == TR.MODULES_LINE:
                modules = sorted((s, e, _module_program(name))
                                 for s, e, name, _, _ in _events(plane, line))
        starts = [m[0] for m in modules]
        tf_ops = {}   # event metadata id -> its tf_op path
        for line in plane.lines:
            if line.name != TR.OPS_LINE:
                continue
            for s, e, _, md, ev in _events(plane, line):
                if e <= lo or s >= hi:
                    continue
                tf_op = tf_ops.get(ev.metadata_id)
                if tf_op is None:
                    tf_op = tf_ops[ev.metadata_id] = next(
                        (st.str_value for st in md.stats
                         if stat.get(st.metadata_id) == "tf_op"), "")
                i = bisect.bisect_right(starts, s) - 1
                prog = (modules[i][2] if i >= 0 and s < modules[i][1]
                        else _program(tf_op) or "unknown")
                ops.append((max(s, lo), min(e, hi),
                            f"{prog}/{_scope(tf_op)}"))
        if not ops:
            continue
        n_dev += 1
        for key, secs in TR._self_times(ops):
            scope_s[key] += secs
        gaps.append(TR._gaps([(s, e) for s, e, _ in ops], lo, hi))
    return {k: v / n_dev for k, v in scope_s.items()} if n_dev else {}, gaps


def _host(space, lo, hi):
    """(program spans with their parent index, every other host span)
    cutting the window; a program span is (start, end, name, args,
    parent)."""
    prog, other = [], []
    for plane in space.planes:
        if TR._is_device(plane):
            continue
        stat = _stat_names(plane)
        for line in plane.lines:
            stack = []   # indices into prog of the open program spans
            for s, e, name, _, ev in sorted(_events(plane, line),
                                            key=lambda x: (x[0], -x[1])):
                if e <= lo or s >= hi or name == TR.WINDOW_SPAN:
                    continue
                if not name.startswith(PROGRAM_SPANS):
                    other.append((s, e, name))
                    continue
                while stack and prog[stack[-1]][1] <= s:
                    stack.pop()
                args = {stat.get(st.metadata_id): st.int64_value
                        + st.uint64_value for st in ev.stats}
                args.pop("step", None)
                prog.append((s, e, name, args, stack[-1] if stack else None))
                stack.append(len(prog) - 1)
    return prog, other


def _innermost(prog, lo, hi):
    """Sorted, disjoint (start, end, name) stretches of the window, each
    named by the innermost program span over it."""
    kids = defaultdict(list)
    for i, sp in enumerate(prog):
        kids[sp[4]].append(i)
    out = []

    def walk(i):
        s, e, name = prog[i][:3]
        t = max(s, lo)
        for k in kids[i]:
            ks = max(prog[k][0], lo)
            if ks > t:
                out.append((t, ks, name))
            walk(k)
            t = max(t, min(prog[k][1], hi))
        if min(e, hi) > t:
            out.append((t, min(e, hi), name))

    for root in kids[None]:
        walk(root)
    return sorted(out)


def _overlap(a, b):
    """Nanoseconds per name of ``b`` ((start, end, name), disjoint, sorted)
    that overlap the disjoint, sorted intervals ``a``."""
    out, j = defaultdict(float), 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out[b[k][2]] += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return out


def _span_stats(prog, lo, hi):
    whole = [i for i, sp in enumerate(prog) if sp[0] >= lo and sp[1] <= hi]
    stats = defaultdict(lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0,
                                 "wait_s": 0.0, "idle_s": 0.0, "args": {}})
    child_s = defaultdict(float)
    for sp in prog:
        if sp[4] is not None:
            child_s[sp[4]] += sp[1] - sp[0]
    for i in whole:
        s, e, name, args, _ = prog[i]
        st = stats[name]
        st["count"] += 1
        st["total_s"] += (e - s) / 1e9
        st["self_s"] += (e - s - child_s[i]) / 1e9
        for k, v in args.items():
            st["args"][k] = st["args"].get(k, 0) + v
    # time under wait spans, credited to each of their ancestors too
    whole_set = set(whole)
    for i, sp in enumerate(prog):
        if not sp[2].endswith(WAIT):
            continue
        chain, a = [], i
        while a is not None:
            chain.append(a)
            a = prog[a][4]
        if any(prog[a][2].endswith(WAIT) for a in chain[1:]):
            continue   # an outer wait span holds this one
        for a in chain:
            if a in whole_set:
                stats[prog[a][2]]["wait_s"] += (sp[1] - sp[0]) / 1e9
    return stats


def _name_gap(segments, starts, other, s, e) -> str:
    mid = (s + e) / 2
    i = bisect.bisect_right(starts, mid) - 1
    if i >= 0 and segments[i][0] <= mid <= segments[i][1]:
        return segments[i][2]
    cover = [sp for sp in other if sp[0] <= mid <= sp[1]]
    return max(cover, key=lambda sp: sp[0])[2] if cover else "no host span"


def reduce(space, top: int = 10) -> dict:
    """The program's spans and scopes in a parsed trace (see the module's
    docstring)."""
    lo, hi = _window(space)
    scope_s, gaps = _device(space, lo, hi)
    prog, other = _host(space, lo, hi)
    segments = _innermost(prog, lo, hi)
    starts = [sg[0] for sg in segments]
    stats = _span_stats(prog, lo, hi)
    n_dev = max(len(gaps), 1)
    for g in gaps:
        for name, ns in _overlap(g, segments).items():
            stats[name]["idle_s"] += ns / 1e9 / n_dev
    longest = sorted((gp for g in gaps for gp in g),
                     key=lambda gp: gp[0] - gp[1])[:top]
    return {
        "scope_s": scope_s,
        "spans": {k: dict(v) for k, v in stats.items()},
        "span_idle_s": sum(v["idle_s"] for v in stats.values()),
        "idle_gaps": [[_name_gap(segments, starts, other, s, e),
                       (e - s) / 1e9] for s, e in longest],
    }


def attach():
    """Make ``harness.Tracer.summary`` add :func:`reduce`'s keys (its
    ``idle_gaps`` replaces the summary's: the same gaps, named by program
    spans where they lie under one).  Idempotent."""
    from bench import harness

    plain = harness.Tracer.summary
    if getattr(plain, "program_trace", False):
        return

    def summary(self):
        out = plain(self)
        out.update(reduce(read_space(TR.find_xplane(self.dir))))
        return out

    summary.program_trace = True
    harness.Tracer.summary = summary


def scope_ms_per_run(run, program: str, scope: str):
    """Device ms under ``<program>/<scope>`` per run of ``program`` in the
    traced window, or None where the trace holds no such scope."""
    tr = run["trace"]
    secs = ((tr or {}).get("scope_s") or {}).get(f"{program}/{scope}")
    if secs is None:
        return None
    calls = sum(v for k, v in tr["module_calls"].items() if program in k)
    return 1e3 * secs / calls if calls else None
