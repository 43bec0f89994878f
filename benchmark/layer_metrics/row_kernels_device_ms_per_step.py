"""Device time of the Pallas calls that `layers/10-row-kernels.json` lists by their kernels' `name=` (a call is known by its `tpu_custom_call` target, or as the `kCustom` fusion the compiler wraps one in), per traced step."""
from benchmark.layer_metrics import _common

LAYER = "row kernels"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"layers": ["row kernels"]}


def read(ctx):
    ms = _common.layer_ms_per_step(ctx, READS["layers"])
    return ms if ms else None
