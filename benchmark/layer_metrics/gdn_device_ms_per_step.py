"""Device self time under the scope `block_gdn` (the gated-delta-net mixers: projections, convolution, gates, the rule, output norm and projection; forward, remat and backward), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "block_gdn"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
