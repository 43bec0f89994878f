"""Least time of the window layers' attention (two products forward and four backward over the pairs INSIDE the window, or its least bytes: the family's work module, `window_attn_work_per_step`) over the device time under the scope `attn_window`."""
from benchmark.layer_metrics import _roofline

LAYER = "dense model"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "attn_window", "work": ["window_attn_work_per_step"]}


def read(ctx):
    return _roofline.share(ctx, READS["work"][0],
                           [{"scope": READS["scope"]}])
