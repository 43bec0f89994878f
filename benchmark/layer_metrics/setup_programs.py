"""Programs compiled or loaded."""
from benchmark.layer_metrics import _program_registry

LAYER = "trainer / step builder"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"
READS = {"counters": "deeprec_compile_spans_total{stage=backend}"}


def read(ctx):
    return _program_registry.total("deeprec_compile_spans", stage="backend")
