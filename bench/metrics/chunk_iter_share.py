"""Share of scheduler iterations inside the window that run a chunked
prefill step, from the engine's serving hooks.  Moves time to first
token."""


def read(run):
    c = run["counts"]
    if not c.get("iterations"):
        return None
    return 100.0 * c["chunk_iterations"] / c["iterations"]
