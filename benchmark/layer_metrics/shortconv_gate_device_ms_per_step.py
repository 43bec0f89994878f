"""Device self time under the scope `shortconv_gate` (the short convolution between its two products: the input gate B x, the causal taps and the output gate C; forward, remat and backward, every conv mixer), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "shortconv_gate"}


def read(ctx):
    # a program that writes another name of this scope's group and not this
    # one reads 0.0 there: nothing to read, so nothing is reported
    return phase_reduce.reading(ctx, READS) or None
