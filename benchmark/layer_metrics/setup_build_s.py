"""`Trainer(...)` and `Trainer.init` (tables and dense weights made; under
the builder's `jit`, the trace of them), self seconds."""
from benchmark.layer_metrics import _program_registry

LAYER = "trainer / step builder"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"
READS = {"counters": ("deeprec_setup_seconds_total{stage=trainer_build} + "
                     "{stage=init_state}")}


def read(ctx):
    return _program_registry.total(
        "deeprec_setup_seconds", stage=("trainer_build", "init_state"))
