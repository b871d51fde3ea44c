"""Padding rows the grouped expert GEMMs add to fill each expert's row
blocks, as a share of the (token, expert) rows routed, over the window,
from the engine's ``expert_rows`` hook (``bench/drive_chat_moe.py``).
Moves the output token rate."""


def read(run):
    c = run["counts"]
    if not c.get("expert_rows_real"):
        return None
    return 100.0 * c["expert_rows_padded"] / c["expert_rows_real"]
