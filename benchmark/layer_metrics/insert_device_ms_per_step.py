"""Device self time under the `engine_insert` scope less the probe inside it (EmbeddingTable._resolve: initializer rows and their scatter, the fused metadata gather and scatter, admission), per traced step."""
from benchmark import phase_reduce

LAYER = "embedding engine"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"stage": "engine_insert"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
