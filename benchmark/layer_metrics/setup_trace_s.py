"""Seconds jax spent tracing programs to jaxprs, over programs: SELF time, so
no nested trace counts twice and no kernel body counts here."""
from benchmark.layer_metrics import _program_registry

LAYER = "trainer / step builder"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
READS = {"counters": "deeprec_compile_seconds_total{stage=trace}"}


def read(ctx):
    return _program_registry.total("deeprec_compile_seconds", stage="trace")
