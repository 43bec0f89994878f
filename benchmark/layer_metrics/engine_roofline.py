"""Least time for the engine's algorithmic bytes of a step at the chip's HBM bandwidth (counts.engine_bytes_per_unique x the measured unique ids a step) over the engine's device time: bound by bandwidth."""
from benchmark import counts
from benchmark.layer_metrics import _common

LAYER = "row kernels"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"


def read(ctx):
    ms = _common.layer_ms_per_step(ctx, _common.ENGINE_LAYERS)
    unique = _common.counter_delta(ctx, "dedup_unique")
    if not ms or unique <= 0 or not ctx.get("peaks"):
        return None
    bytes_per_step = (counts.engine_bytes_per_unique(ctx["config"])
                      * unique / ctx["steps"])
    least_ms = 1e3 * bytes_per_step / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
