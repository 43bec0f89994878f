"""What several readers share."""
from __future__ import annotations

import math
from typing import Dict, List, Optional


def layer_ms_per_step(ctx: Dict, layers) -> Optional[float]:
    trace, steps = ctx.get("trace"), ctx.get("traced_steps")
    if not trace or not steps:
        return None
    total = sum(trace["by_layer_s"].get(layer, 0.0) for layer in layers)
    return 1e3 * total / steps


def step_spans_ms(ctx: Dict) -> List[float]:
    """Device spans of the step program: the events of the `XLA Modules`
    line whose program took most of the time."""
    trace = ctx.get("trace")
    if not trace or not trace["modules"]:
        return []
    by_name: Dict[str, List[float]] = {}
    for _, dur, name in trace["modules"]:
        by_name.setdefault(name, []).append(dur * 1e-6)
    return max(by_name.values(), key=sum)


def quantile(values: List[float], q: float) -> float:
    """The smallest value with at least a share q of the sample at or under
    it (an order statistic, no interpolation)."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def counter_delta(ctx: Dict, name: str) -> Optional[int]:
    """The counter's rise over the window; None where the program has no
    counter of that name."""
    if name not in ctx["counter_names"]:
        return None
    col = ctx["counter_names"].index(name)
    return int(ctx["counters"][-1, col] - ctx["counters"][0, col])


def work(ctx: Dict, name: str):
    """The function `name` of the configuration's work module, or None
    where the family does not have it."""
    return getattr(ctx.get("work"), name, None)


def traced_examples_per_s(ctx: Dict) -> Optional[float]:
    if not ctx.get("traced_steps") or not ctx.get("traced_window_s"):
        return None
    return (ctx["traced_steps"] * ctx["examples_per_step"]
            / ctx["traced_window_s"])
