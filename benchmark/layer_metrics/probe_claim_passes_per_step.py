"""Executions of the body of the probe's claim loop (the `while` under `probe_claim`, the second of `EmbeddingTable._probe`'s two: the race for empty slots), per traced step; 0.0 where a window creates no row, no reading from a program that has one probe loop."""
from benchmark import phase_reduce

LAYER = "embedding engine"
UNIT = "count"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"loop": "probe_claim"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
