"""Device self time under the scope `block_attn` (the gated full-attention mixer: projections, norms, rotary, the flash kernels, gate and output projection), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "block_attn"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
