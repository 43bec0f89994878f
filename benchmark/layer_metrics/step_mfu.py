"""FLOPs the forward and backward passes of the whole model require per example (counts.dense_flops_per_example; the lookups count zero) x examples/s of the traced window over chips x the peak."""
from benchmark import counts
from benchmark.layer_metrics import _common

LAYER = "device"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"


def read(ctx):
    rate = _common.traced_examples_per_s(ctx)
    if not rate or not ctx.get("peaks"):
        return None
    return (100.0 * counts.dense_flops_per_example(ctx["config"]) * rate
            / (ctx["chips"] * ctx["peaks"]["flops_per_s"]))
