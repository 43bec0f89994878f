"""Device self time of the Pallas calls under a `rows_gather` or `rows_scatter` scope (ops/packed.py), each known by its `tpu_custom_call` target or as the `kCustom` fusion the compiler wraps one in, whatever the compiler names it, per traced step."""
from benchmark import phase_reduce

LAYER = "row kernels"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"rows": "kernel"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
