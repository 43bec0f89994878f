"""Device self time under the scope `moe_latent` (inside `block_moe`: the projections of the token into the routed experts' latent space and of their sum back, every expert layer, forward, remat and backward), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "moe_latent"}


def read(ctx):
    # a program that writes another name of this scope's group and not this
    # one reads 0.0 there: nothing to read, so nothing is reported
    return phase_reduce.reading(ctx, READS) or None
