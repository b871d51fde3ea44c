"""Share of the KV rows the paged decode steps gather that decoding lanes
hold: the engine's counts on its ``serve.decode`` spans (rows gathered,
every lane's whole table per KV layer; rows live, position + 1 per decoding
lane and KV layer), summed over the traced window
(``bench/program_trace.py``).  Moves time per output token."""

from bench import program_trace

program_trace.attach()


def read(run):
    spans = (run["trace"] or {}).get("spans") or {}
    args = (spans.get("serve.decode") or {}).get("args") or {}
    if not args.get("kv_rows_gathered"):
        return None
    return 100.0 * args["kv_rows_live"] / args["kv_rows_gathered"]
