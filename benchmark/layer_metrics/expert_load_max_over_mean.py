"""The fullest held expert's rows over the mean held expert's, whole window: the counter `moe_max_load` (each layer's largest group, summed over layers and steps) over `moe_pairs` / the held experts (the configuration's `num_experts`). 1 is an even router."""
from benchmark.layer_metrics import _common

LAYER = "dense model"
UNIT = "ratio"
MOVES = "train_examples_per_s"
SOURCE = "program_counter"
READS = {"counters": ["moe_max_load", "moe_pairs"]}


def read(ctx):
    top, pairs = (_common.counter_delta(ctx, name)
                  for name in READS["counters"])
    if not pairs or pairs <= 0 or top is None:
        return None
    return top * ctx["config"]["num_experts"] / pairs
