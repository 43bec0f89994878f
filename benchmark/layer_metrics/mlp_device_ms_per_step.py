"""Device self time under the scope `block_mlp` (the dense gated feed-forward of a leading layer that has no experts), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "block_mlp"}


def read(ctx):
    # a program that writes another name of this scope's group and not this
    # one reads 0.0 there: nothing to read, so nothing is reported
    return phase_reduce.reading(ctx, READS) or None
