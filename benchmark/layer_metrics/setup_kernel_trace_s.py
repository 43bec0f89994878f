"""Seconds binding Pallas calls, over kernels: each kernel body's trace, made
again for every call of every program on every run, which no cache serves."""
from benchmark.layer_metrics import _program_registry

LAYER = "trainer / step builder"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
READS = {"counters": "deeprec_pallas_trace_seconds_total"}


def read(ctx):
    return _program_registry.total("deeprec_pallas_trace_seconds")
