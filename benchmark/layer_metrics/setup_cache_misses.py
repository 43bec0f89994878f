"""Programs the persistent cache did not serve (compiled, then written): 0
where it served every program."""
from benchmark.layer_metrics import _program_registry

LAYER = "trainer / step builder"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"
READS = {"counters": "deeprec_compile_cache_total{outcome=miss}"}


def read(ctx):
    return _program_registry.total("deeprec_compile_cache", outcome="miss")
