"""Device self time under the family's own scope `nexttok_mix` (the causal mean over the sequence), per traced step."""
from benchmark import phase_reduce

LAYER = "sequence mixer"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "nexttok_mix"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
