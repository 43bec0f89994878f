"""Device self time of the operations whose source is optim/ or optax, per traced step."""
from benchmark.layer_metrics import _common

LAYER = "sparse + dense apply"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"layers": ["sparse + dense apply"]}


def read(ctx):
    return _common.layer_ms_per_step(ctx, READS["layers"])
