"""Device time of one chunked-prefill iteration: the engine's verify pass
(``_verify_paged_fn``) and its cache commit (``_commit_paged_fn``) per
verify run, from the trace's XLA modules.  Moves time to first token."""

VERIFY, COMMIT = "_verify_paged_fn", "_commit_paged_fn"


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    secs = sum(v for k, v in tr["module_s"].items()
               if VERIFY in k or COMMIT in k)
    calls = sum(v for k, v in tr["module_calls"].items() if VERIFY in k)
    return 1e3 * secs / calls if calls else None
