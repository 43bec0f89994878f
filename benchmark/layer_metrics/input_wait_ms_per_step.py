"""Host time the loop spent taking the next batch from the producer, per step."""
LAYER = "host input"
UNIT = "ms"
MOVES = "train_examples_per_s"
SOURCE = "host_clock"
READS = {"bench_span": "input_wait"}


def read(ctx):
    if not ctx["spans"].by_name.get("input_wait"):
        return None
    return ctx["spans"].total_ms("input_wait") / ctx["steps"]
