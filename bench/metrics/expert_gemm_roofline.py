"""The grouped expert GEMM's share of its roofline in the traced part of
the window: the least time of the expert work the traced passes did
(their real tokens' routed pairs and the experts they touched,
``bench/work_moe.py``) over the summed device time of the grouped
kernel's events.

The kernel is found by what the trace shows of it: its op name or a
string stat naming ``kernels/dsbp_grouped.py``'s entry."""

from bench import weights_moe as WM
from bench import work_moe

MARKERS = ("dsbp_grouped",)


def is_grouped(name: str, stats: dict) -> bool:
    text = " ".join([name, *stats.values()])
    return any(m in text for m in MARKERS)


def read(run):
    tr, c, peaks = run["trace"], run["counts"], run["peaks"]
    passes = c.get("trace_expert_passes")
    if not tr or not peaks or not passes:
        return None
    device_s = sum(v for k, v in tr["op_s"].items()
                   if is_grouped(k, tr["op_info"].get(k, {})))
    if device_s <= 0:
        return None
    least = work_moe.expert_least_seconds(
        passes, WM.Dims(**c["moe_dims"]), c["expert_avg_w_bits"], peaks)
    return 100.0 * least / device_s
