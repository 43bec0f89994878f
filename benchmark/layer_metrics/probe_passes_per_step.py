"""Executions of the probe loop's body (the events of one instruction of the body of the `while` under `engine_probe`), per traced step."""
from benchmark import phase_reduce

LAYER = "embedding engine"
UNIT = "count"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"loop": "engine_probe"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
