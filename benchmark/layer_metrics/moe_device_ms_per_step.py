"""Device self time under the scope `block_moe` (the expert block of every layer: router, dispatch and combine, the held experts' grouped products, the shared expert), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "block_moe"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
