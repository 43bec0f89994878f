"""Device self time under the `engine_probe` scope (EmbeddingTable._probe: the probe-and-claim while_loop), per traced step."""
from benchmark import phase_reduce

LAYER = "embedding engine"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"stage": "engine_probe"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
