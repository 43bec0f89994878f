"""Host time inside the train_step call (and the read of the engine's counters) until it returns, per step."""
LAYER = "trainer / step builder"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "host_clock"
READS = {"bench_span": "dispatch"}


def read(ctx):
    if not ctx["spans"].by_name.get("dispatch"):
        return None
    return ctx["spans"].total_ms("dispatch") / ctx["steps"]
