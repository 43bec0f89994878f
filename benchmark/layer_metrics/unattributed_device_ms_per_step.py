"""Device self time of the operations whose source the reduction could not recover, even by inheritance, per traced step: the check on the reduction itself."""
from benchmark.layer_metrics import _common

LAYER = "device"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"layers": ["unattributed"]}


def read(ctx):
    return _common.layer_ms_per_step(ctx, READS["layers"])
