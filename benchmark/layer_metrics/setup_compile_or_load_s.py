"""Seconds in the backend, over programs: an XLA compile, or a load from the
persistent cache."""
from benchmark.layer_metrics import _program_registry

LAYER = "trainer / step builder"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
READS = {"counters": "deeprec_compile_seconds_total{stage=backend}"}


def read(ctx):
    return _program_registry.total("deeprec_compile_seconds", stage="backend")
