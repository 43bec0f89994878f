"""Device self time under the scope `attn_window` (tight round the flash call of a layer whose attention is causal inside the sliding window: the three flash kernels and the casts beside them, of every such layer), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "attn_window"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
