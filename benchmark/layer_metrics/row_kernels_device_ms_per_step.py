"""Device time of the Pallas row kernels (the tpu_custom_call events), per traced step."""
from benchmark.layer_metrics import _common

LAYER = "row kernels"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"


def read(ctx):
    ms = _common.layer_ms_per_step(ctx, ("row kernels",))
    return ms if ms else None
