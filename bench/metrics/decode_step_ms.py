"""Device time of one paged decode program run, from the trace's XLA
modules (the engine's jitted ``_decode_paged_fn``).  Moves time per output
token."""

PROGRAM = "_decode_paged_fn"


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    secs = sum(v for k, v in tr["module_s"].items() if PROGRAM in k)
    calls = sum(v for k, v in tr["module_calls"].items() if PROGRAM in k)
    return 1e3 * secs / calls if calls else None
