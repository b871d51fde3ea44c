"""Device time of the mixture-of-experts layer (the model's ``router``,
``expert_dispatch``, ``experts_in``, ``experts_out`` and
``expert_combine`` scopes, ``bench/expert_trace.py``) per paged decode
program run, summed over the layers.  Moves time per output token."""

from bench import expert_trace

expert_trace.attach()

PROGRAM = "_decode_paged_fn"


def read(run):
    tr = run["trace"]
    secs = [v for k, v in ((tr or {}).get("expert_scope_s") or {}).items()
            if k.startswith(f"{PROGRAM}/")]
    if not secs:
        return None
    calls = sum(v for k, v in tr["module_calls"].items() if PROGRAM in k)
    return 1e3 * sum(secs) / calls if calls else None
