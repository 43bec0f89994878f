"""Unique ids over ids looked up, whole window, from the tables' dedup counters."""
from benchmark.layer_metrics import _common

LAYER = "embedding engine"
UNIT = "fraction"
MOVES = "train_examples_per_s"
SOURCE = "program_counter"
READS = {"counters": ["dedup_unique", "dedup_ids"]}


def read(ctx):
    unique, ids = (_common.counter_delta(ctx, name)
                   for name in READS["counters"])
    if not ids or ids <= 0 or unique is None:
        return None
    return unique / ids
