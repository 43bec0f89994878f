"""Least time of the held experts' grouped products (their weights read twice and their gradients written, and the FLOPs of the MEASURED routed pairs: the family's work module, `experts_work_per_step`, x the counter `moe_pairs`) over the device time under the scope `moe_experts`."""
from benchmark.layer_metrics import _common, _roofline

LAYER = "dense model"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "moe_experts", "counters": ["moe_pairs"],
         "work": ["experts_work_per_step"]}


def read(ctx):
    pairs = _common.counter_delta(ctx, "moe_pairs")
    if not pairs or pairs <= 0:
        return None
    return _roofline.share(ctx, READS["work"][0],
                           [{"scope": READS["scope"]}],
                           pairs / ctx["steps"])
