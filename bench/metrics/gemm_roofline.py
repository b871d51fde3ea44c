"""The fused DSBP GEMM's share of its roofline in the traced part of the
window: the least time of the GEMM work the traced passes did (real
tokens only, ``bench/work.py``) over the summed device time of the fused
kernel's events.

The kernel is found by what the trace shows of it: its op name or a
string stat naming the Pallas body of ``kernels/dsbp_fused.py``."""

from bench import work

MARKERS = ("dsbp_fused",)


def is_gemm(name: str, stats: dict) -> bool:
    text = " ".join([name, *stats.values()])
    return any(m in text for m in MARKERS)


def read(run):
    tr, c, peaks = run["trace"], run["counts"], run["peaks"]
    if not tr or not peaks:
        return None
    passes = (c.get("trace_passes", []) + c.get("trace_decode_passes", [])
              + c.get("trace_chunk_passes", []))
    device_s = sum(v for k, v in tr["op_s"].items()
                   if is_gemm(k, tr["op_info"].get(k, {})))
    if not passes or device_s <= 0:
        return None
    least = work.gemm_least_seconds(passes, run["dims"], c["avg_w_bits"],
                                    peaks)
    return 100.0 * least / device_s
