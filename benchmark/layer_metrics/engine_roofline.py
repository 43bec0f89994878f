"""Least time for the engine's algorithmic bytes of a step at the chip's HBM bandwidth (the family's work module, `engine_bytes_per_unique`, x the measured unique ids a step) over the engine's device time: bound by bandwidth."""
from benchmark.layer_metrics import _common

LAYER = "row kernels"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"layers": ["embedding engine", "row kernels"],
         "counters": ["dedup_unique"], "work": ["engine_bytes_per_unique"]}


def read(ctx):
    ms = _common.layer_ms_per_step(ctx, READS["layers"])
    unique = _common.counter_delta(ctx, "dedup_unique")
    per_unique = _common.work(ctx, "engine_bytes_per_unique")
    if not ms or not unique or unique <= 0 or not per_unique \
            or not ctx.get("peaks"):
        return None
    bytes_per_step = per_unique(ctx["config"]) * unique / ctx["steps"]
    least_ms = 1e3 * bytes_per_step / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
