"""The fullest of ALL the router's outputs' loads over the mean output's, whole window: the counter `moe_all_max_load` (each expert layer's most-chosen output's tokens, held here or not, summed over layers and steps) over what an even router gives every output (the family's work module, `router_even_load_per_step`). 1 is an even router; it says whether the selection bias' rule holds the load whichever experts are held."""
from benchmark.layer_metrics import _common

LAYER = "dense model"
UNIT = "ratio"
MOVES = "train_examples_per_s"
SOURCE = "program_counter"
READS = {"counters": ["moe_all_max_load"],
         "work": ["router_even_load_per_step"]}


def read(ctx):
    top = _common.counter_delta(ctx, READS["counters"][0])
    even = _common.work(ctx, READS["work"][0])
    if top is None or even is None or not ctx.get("steps"):
        return None
    # the program's counters are int32 and wrap; a window's rise does not
    # pass 2^32
    return (top % 2 ** 32) / (ctx["steps"] * even(ctx["config"], ctx["mix"]))
