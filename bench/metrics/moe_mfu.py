"""Whole-step model FLOP utilization of a mixture-of-experts cell: model
FLOPs of the tokens the window processed (active parameters, attention
over each layer kind's reach and the head; ``bench/work_moe.py``) over
the window times the chip's int8 peak, the peak the expert roofline
uses."""

from bench import weights_moe as WM
from bench import work_moe


def read(run):
    c, peaks = run["counts"], run["peaks"]
    if not c.get("segments") or not c.get("moe_dims") or not peaks:
        return None
    flops = work_moe.model_flops(WM.Dims(**c["moe_dims"]), c["segments"],
                                 c["logit_tokens"])
    return 100.0 * flops / (c["window_s"] * peaks["int8_ops"])
