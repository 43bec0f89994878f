"""The share of the held experts' hidden units that the gate's ReLU leaves above 0, whole window: the counter `moe_hidden_live` over `moe_pairs` x the expert width (the configuration's `moe_ffn_hidden_size`). 0.5 at initialisation; what a kernel that skipped dead units could save."""
from benchmark.layer_metrics import _common

LAYER = "dense model"
UNIT = "fraction"
MOVES = "train_examples_per_s"
SOURCE = "program_counter"
READS = {"counters": ["moe_hidden_live", "moe_pairs"]}


def read(ctx):
    live, pairs = (_common.counter_delta(ctx, name)
                   for name in READS["counters"])
    width = ctx["config"].get("moe_ffn_hidden_size")
    if live is None or not pairs or pairs <= 0 or not width:
        return None
    # the program's counters are int32 and wrap; a window's rise does not
    # pass 2^32
    return (live % 2 ** 32) / (pairs * width)
