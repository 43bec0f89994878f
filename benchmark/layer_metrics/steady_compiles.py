"""Programs jax compiled or loaded inside the measured window (jax.monitoring's compile events): 0 in a steady state."""
LAYER = "trainer / step builder"
UNIT = "count"
MOVES = "train_examples_per_s"
SOURCE = "program_counter"
READS = {"window": "compiles"}


def read(ctx):
    return float(ctx["window_compiles"])
