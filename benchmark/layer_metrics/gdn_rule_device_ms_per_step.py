"""Device self time under the scope `gdn_rule` (the chunked gated delta rule alone, forward, remat and backward, every gated-delta layer), per traced step."""
from benchmark import phase_reduce

LAYER = "dense model"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "gdn_rule"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
