"""(token, expert) pairs routed to the experts held here, a step, all layers: the counter `moe_pairs` over the window's steps."""
from benchmark.layer_metrics import _common

LAYER = "dense model"
UNIT = "count"
MOVES = "train_examples_per_s"
SOURCE = "program_counter"
READS = {"counters": ["moe_pairs"]}


def read(ctx):
    pairs = _common.counter_delta(ctx, "moe_pairs")
    if pairs is None or not ctx.get("steps"):
        return None
    return pairs / ctx["steps"]
