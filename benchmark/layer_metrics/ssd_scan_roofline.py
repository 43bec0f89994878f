"""Least time of the Mamba-2 scan (the recurrence's two N x P products a head a token, forward and backward, or its least bytes: the family's work module, `ssd_scan_work_per_step`) over the device time under the scope `ssd_scan`."""
from benchmark.layer_metrics import _roofline

LAYER = "dense model"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "ssd_scan", "work": ["ssd_scan_work_per_step"]}


def read(ctx):
    return _roofline.share(ctx, READS["work"][0],
                           [{"scope": READS["scope"]}])
