"""Device self time of the operations whose source is models/, nn.py or the loss, per traced step."""
from benchmark.layer_metrics import _common

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"layers": ["dense model"]}


def read(ctx):
    return _common.layer_ms_per_step(ctx, READS["layers"])
