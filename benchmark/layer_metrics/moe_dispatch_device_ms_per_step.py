"""Device self time under the scope `moe_dispatch` (router product, softmax and top-k, the compaction and sort of the held pairs, the rows' gather and the weighted scatter back), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "moe_dispatch"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
