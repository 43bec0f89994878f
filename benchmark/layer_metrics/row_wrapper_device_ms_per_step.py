"""Device self time under a `rows_gather` or `rows_scatter` scope (ops/packed.py) that is not a Pallas call: the loops, slices, update-slices and fills round the vmapped row kernels, per traced step."""
from benchmark import phase_reduce

LAYER = "row kernels"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"rows": "wrapper"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
