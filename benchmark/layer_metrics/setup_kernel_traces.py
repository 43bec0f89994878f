"""Pallas kernel bodies traced, over kernels."""
from benchmark.layer_metrics import _program_registry

LAYER = "trainer / step builder"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"
READS = {"counters": "deeprec_pallas_traces_total"}


def read(ctx):
    return _program_registry.total("deeprec_pallas_traces")
