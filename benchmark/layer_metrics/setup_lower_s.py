"""Seconds lowering jaxprs to MLIR, over programs: every Pallas call's Mosaic
lowering is inside."""
from benchmark.layer_metrics import _program_registry

LAYER = "trainer / step builder"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
READS = {"counters": "deeprec_compile_seconds_total{stage=lower}"}


def read(ctx):
    return _program_registry.total("deeprec_compile_seconds", stage="lower")
