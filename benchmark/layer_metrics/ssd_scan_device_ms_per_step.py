"""Device self time under the scope `ssd_scan` (the chunked Mamba-2 scan alone, ops/ssd.py: forward, remat and backward, every Mamba-2 layer), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "ssd_scan"}


def read(ctx):
    # a program that writes another name of this scope's group and not this
    # one reads 0.0 there: nothing to read, so nothing is reported
    return phase_reduce.reading(ctx, READS) or None
