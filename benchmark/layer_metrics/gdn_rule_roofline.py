"""Least time of the gated delta rule (the recurrence's three 128 x 128 products a head a token, forward and backward, or its least bytes: the family's work module, `gdn_rule_work_per_step`) over the device time under the scope `gdn_rule`."""
from benchmark.layer_metrics import _roofline

LAYER = "dense model"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "gdn_rule", "work": ["gdn_rule_work_per_step"]}


def read(ctx):
    return _roofline.share(ctx, READS["work"][0],
                           [{"scope": READS["scope"]}])
