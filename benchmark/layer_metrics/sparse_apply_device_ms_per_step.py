"""Device self time under the `phase_sparse_apply` scope (apply_gradients for every bundle, the optimizer's row reads and writes among it), per traced step."""
from benchmark import phase_reduce

LAYER = "sparse + dense apply"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"phase": "phase_sparse_apply"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
