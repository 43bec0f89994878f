"""90th percentile of the step program's device spans over the traced steps: read, not deciding, until a window holds about a thousand steps (PERF.md section 2)."""
from benchmark.layer_metrics import _common

LAYER = "benchmark loop"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"step_program": "device_spans"}


def read(ctx):
    spans = _common.step_spans_ms(ctx)
    return _common.quantile(spans, 0.9) if spans else None
