"""Device self time under the scope `moe_shared` (the shared experts inside `block_moe`: one gated feed-forward every token goes through, no routing, of every expert layer), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "moe_shared"}


def read(ctx):
    # a program that writes another name of this scope's group and not this
    # one reads 0.0 there: nothing to read, so nothing is reported
    return phase_reduce.reading(ctx, READS) or None
