"""Median device span of the step program over the traced steps."""
from benchmark.layer_metrics import _common

LAYER = "benchmark loop"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"step_program": "device_spans"}


def read(ctx):
    spans = _common.step_spans_ms(ctx)
    return _common.quantile(spans, 0.5) if spans else None
