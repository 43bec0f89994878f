"""Device self time under the `engine_gather` scope (EmbeddingTable._finish_resolved: the value gather and the admission mask), per traced step."""
from benchmark import phase_reduce

LAYER = "embedding engine"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"stage": "engine_gather"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
