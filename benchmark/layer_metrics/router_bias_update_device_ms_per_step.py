"""Device self time under the scope `router_bias_update` (inside `phase_dense_apply`: the rule that moves the routers' selection bias from the step's expert loads, the model's `after_update`), per traced step."""
from benchmark import phase_reduce

LAYER = "sparse + dense apply"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "router_bias_update"}


def read(ctx):
    # a program that writes another name of this scope's group and not this
    # one reads 0.0 there: nothing to read, so nothing is reported
    return phase_reduce.reading(ctx, READS) or None
