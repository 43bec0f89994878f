"""Device self time under the `engine_route` scope (ops/dedup.py::route_ids: flatten, pad-collapse, hash dedup, compaction, and the dedup counters), per traced step."""
from benchmark import phase_reduce

LAYER = "embedding engine"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"stage": "engine_route"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
