"""Device self time under the scope `attn_latent` (tight round the flash call of a latent-attention layer, queries and keys 192 wide and values 128: the three flash kernels and the casts beside them, of every layer), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "attn_latent"}


def read(ctx):
    # a program that writes another name of this scope's group and not this
    # one reads 0.0 there: nothing to read, so nothing is reported
    return phase_reduce.reading(ctx, READS) or None
