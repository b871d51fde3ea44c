#!/usr/bin/env python3
"""Record ``serve_spans_v5e.xplane.pb``: a few paged decode and chunked
prefill iterations of a 2-layer model at yi-9b's widths, then one scoring
call, with the engine's spans and the model's named scopes, inside the
benchmark's window span.

    python3 bench/testdata/record_serve_spans.py OUT_DIR

from the root of a checkout, on a machine with one TPU.  Writes
``OUT_DIR/serve_spans.xplane.pb``, cut to what the benchmark reads: on
the device planes the ``XLA Modules`` and ``XLA Ops`` lines, each op named
by its HLO instruction alone (``%fusion.12``, not the whole instruction)
and with only its ``tf_op`` stat; on the host's CPU plane the lines that
hold the window or a program span.  The uncut trace goes to ``OUT_DIR/raw.xplane.pb``.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

def _keep_line(plane, line) -> bool:
    from bench import program_trace, trace_reduce

    if trace_reduce._is_device(plane):
        return line.name in (trace_reduce.OPS_LINE,
                             trace_reduce.MODULES_LINE)
    names = {e.key: e.value.name for e in plane.event_metadata}
    return any(names[ev.metadata_id] == trace_reduce.WINDOW_SPAN
               or names[ev.metadata_id].startswith(
                   program_trace.PROGRAM_SPANS) for ev in line.events)


def prune(path: str, out: str) -> int:
    """Write what the benchmark reads of a trace; returns the bytes."""
    from bench import program_trace, trace_reduce

    space = program_trace.read_space(path)
    for i in reversed(range(len(space.planes))):
        name = space.planes[i].name
        if not (trace_reduce._is_device(space.planes[i])
                or name == "/host:CPU"):
            del space.planes[i]
    for plane in space.planes:
        for i in reversed(range(len(plane.lines))):
            if not _keep_line(plane, plane.lines[i]):
                del plane.lines[i]
        keep = {e.key for e in plane.stat_metadata if e.value.name == "tf_op"}
        for entry in plane.event_metadata:
            if trace_reduce._is_device(plane):
                entry.value.name = entry.value.name.split(" = ", 1)[0]
            stats = entry.value.stats
            for i in reversed(range(len(stats))):
                if stats[i].metadata_id not in keep:
                    del stats[i]
    data = space.SerializeToString()
    with open(out, "wb") as f:
        f.write(data)
    return len(data)


def main(out_dir: str) -> None:
    import jax
    import numpy as np

    from bench import program, trace_reduce
    from repro.serve.engine import Engine, Request, ServeConfig

    with open(os.path.join(ROOT, "bench", "configs", "yi-9b.json")) as f:
        conf = json.load(f)
    conf["num_hidden_layers"] = 2
    cfg = program.arch_config(conf)
    params = program.build_params(2147483901, cfg)
    eng = Engine(params, cfg, ServeConfig(paged=True, batch_size=4,
                                          max_len=320, kv_block_size=16))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (70, 100)]

    def requests():
        return [Request(uid=i, tokens=p, max_new_tokens=3)
                for i, p in enumerate(prompts)]

    seqs, plens = [prompts[0][:40]], [38]

    eng.serve(requests())        # every program the traced part runs
    eng.score_continuations(seqs, plens)
    log_dir = tempfile.mkdtemp(prefix="serve-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        eng.serve(requests())
        eng.score_continuations(seqs, plens)
    jax.profiler.stop_trace()
    raw = trace_reduce.find_xplane(log_dir)
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "serve_spans.xplane.pb")
    size = prune(raw, out)
    shutil.copy(raw, os.path.join(out_dir, "raw.xplane.pb"))
    print(json.dumps({"bytes": size, "raw_bytes": os.path.getsize(raw),
                      "stats": {k: eng.last_stats[k] for k in (
                          "decode_steps", "chunk_steps", "kv_rows_gathered",
                          "kv_rows_live")}}))
    shutil.rmtree(log_dir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
