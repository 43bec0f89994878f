"""Device self time of the operations whose source is the embedding engine's files, the row kernels among them, per traced step."""
from benchmark.layer_metrics import _common

LAYER = "embedding engine"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"layers": ["embedding engine", "row kernels"]}


def read(ctx):
    return _common.layer_ms_per_step(ctx, READS["layers"])
