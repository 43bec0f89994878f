"""Device self time under the scope `block_head_loss` (final norm, the head's product and the cross-entropy by blocks of positions), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "block_head_loss"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
