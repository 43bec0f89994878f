"""Device self time of operations under no `phase_*` scope even after inheriting, per traced step: the check on the reduction by scopes."""
from benchmark import phase_reduce

LAYER = "device"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"phase": "unphased"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
