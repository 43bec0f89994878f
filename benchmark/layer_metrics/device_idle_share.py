"""1 - the union of the device operations' intervals over the traced window."""
LAYER = "device"
UNIT = "fraction"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"window": "busy"}


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return max(0.0, 1.0 - trace["busy_s"] / trace["window_s"])
