"""The package's own import, seconds (jax's import is inside it where the
package is the first to import jax, as under benchmark/run.py; the device's
coming up is not)."""
from benchmark.layer_metrics import _program_registry

LAYER = "trainer / step builder"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
READS = {"counters": "deeprec_setup_seconds_total{stage=import}"}


def read(ctx):
    return _program_registry.total("deeprec_setup_seconds", stage="import")
