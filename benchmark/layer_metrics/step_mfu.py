"""FLOPs the forward and backward passes of the whole model require per example (the family's work module, `flops_per_example`; what counts zero is said there) x examples/s of the traced window over chips x the peak."""
from benchmark.layer_metrics import _common

LAYER = "device"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"window": "rate", "work": ["flops_per_example"]}


def read(ctx):
    rate = _common.traced_examples_per_s(ctx)
    flops = _common.work(ctx, "flops_per_example")
    if not rate or not flops or not ctx.get("peaks"):
        return None
    return (100.0 * flops(ctx["config"], ctx["mix"]) * rate
            / (ctx["chips"] * ctx["peaks"]["flops_per_s"]))
