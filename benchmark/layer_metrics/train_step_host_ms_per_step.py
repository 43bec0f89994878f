"""Host time inside the program's `deeprec.train_step` spans (Trainer.train_step, on the trace's clock), per traced step."""
from benchmark import phase_reduce

LAYER = "trainer / step builder"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "program_span"
READS = {"span": "deeprec.train_step"}


def read(ctx):
    return phase_reduce.reading(ctx, READS)
