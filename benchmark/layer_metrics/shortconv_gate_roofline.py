"""Least time of the short convolutions' gates and taps (B, C and x read once and the gated output written once, forward, and the backward's equivalent, or their FLOPs: the family's work module, `shortconv_gate_work_per_step`) over the device time under the scope `shortconv_gate`."""
from benchmark.layer_metrics import _roofline

LAYER = "dense model"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "shortconv_gate", "work": ["shortconv_gate_work_per_step"]}


def read(ctx):
    return _roofline.share(ctx, READS["work"][0],
                           [{"scope": READS["scope"]}])
