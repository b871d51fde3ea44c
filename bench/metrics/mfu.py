"""Whole-step model FLOP utilization: model FLOPs of the tokens the window
processed (prompt chunks and decoded tokens, or scored sequences; see
``bench/work.py``) over the window times the chip's int8 peak — the same
peak the GEMM roofline uses, since DSBP's products are integers."""

from bench import work


def read(run):
    c, peaks = run["counts"], run["peaks"]
    if not c.get("segments") or not peaks:
        return None
    flops = work.model_flops(run["dims"], c["segments"], c["logit_tokens"])
    return 100.0 * flops / (c["window_s"] * peaks["int8_ops"])
