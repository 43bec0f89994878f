"""Least time of the dense forward and backward (the larger of FLOPs over the peak and least bytes over the bandwidth, from the family's work module) over the dense model's device time."""
from benchmark.layer_metrics import _common

LAYER = "dense model"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"layers": ["dense model"],
         "work": ["flops_per_example", "dense_min_bytes_per_step"]}


def least_ms(work, config, mix, examples, peaks):
    flop_ms = 1e3 * work.flops_per_example(config, mix) * examples \
        / peaks["flops_per_s"]
    byte_ms = 1e3 * work.dense_min_bytes_per_step(config, mix) \
        / peaks["hbm_bytes_per_s"]
    return max(flop_ms, byte_ms), ("compute" if flop_ms >= byte_ms
                                   else "bandwidth")


def read(ctx):
    ms = _common.layer_ms_per_step(ctx, READS["layers"])
    if not ms or not ctx.get("peaks") or not all(
            _common.work(ctx, name) for name in READS["work"]):
        return None
    return 100.0 * least_ms(ctx["work"], ctx["config"], ctx["mix"],
                            ctx["examples_per_step"], ctx["peaks"])[0] / ms
