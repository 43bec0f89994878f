"""Least time of the dense forward and backward (the larger of FLOPs over the peak and least bytes over the bandwidth, from counts.py) over the dense model's device time."""
from benchmark import counts
from benchmark.layer_metrics import _common

LAYER = "dense model"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"


def least_ms(config, batch, peaks):
    flop_ms = 1e3 * counts.dense_flops_per_example(config) * batch \
        / peaks["flops_per_s"]
    byte_ms = 1e3 * counts.dense_min_bytes_per_step(config, batch) \
        / peaks["hbm_bytes_per_s"]
    return max(flop_ms, byte_ms), ("compute" if flop_ms >= byte_ms
                                   else "bandwidth")


def read(ctx):
    ms = _common.layer_ms_per_step(ctx, ("dense model",))
    if not ms or not ctx.get("peaks"):
        return None
    return 100.0 * least_ms(ctx["config"], ctx["examples_per_step"],
                            ctx["peaks"])[0] / ms
