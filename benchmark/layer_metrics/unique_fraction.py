"""Unique ids over ids looked up, whole window, from the tables' dedup counters."""
from benchmark.layer_metrics import _common

LAYER = "embedding engine"
UNIT = "fraction"
MOVES = "train_examples_per_s"
SOURCE = "program_counter"


def read(ctx):
    ids = _common.counter_delta(ctx, "dedup_ids")
    if ids <= 0:
        return None
    return _common.counter_delta(ctx, "dedup_unique") / ids
