"""Device self time under the scope `block_mamba` (the Mamba-2 mixers: input projection, convolution, step sizes, the scan, the D skip, the gated group norm and the output projection; forward, remat and backward), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "block_mamba"}


def read(ctx):
    # a program that writes another name of this scope's group and not this
    # one reads 0.0 there: nothing to read, so nothing is reported
    return phase_reduce.reading(ctx, READS) or None
