"""Host time per scheduler iteration (``serve.iter``) or scoring call
(``score.call``) that is not spent waiting on the device: the span less
the spans inside it that block on the device (names ending in ``wait``),
from the program's own spans in the trace (``bench/program_trace.py``).
Moves the cell's rate."""

from bench import program_trace

program_trace.attach()

ROOTS = ("serve.iter", "score.call")


def read(run):
    spans = (run["trace"] or {}).get("spans") or {}
    for name in ROOTS:
        s = spans.get(name)
        if s and s["count"]:
            return 1e3 * (s["total_s"] - s["wait_s"]) / s["count"]
    return None
