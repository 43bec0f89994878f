"""What the three rooflines of the token stack share: the least time for a
part's work (the larger of its FLOPs over the peak and its least bytes over
the bandwidth, both from the family's work module) over the device time
read under the part's scope or kernels. The count is the LEAST work of the
mathematics, so the share reads the same work whatever implements it and
cannot pass 100."""
from __future__ import annotations

from typing import Dict, Optional

from benchmark import phase_reduce


def share(ctx: Dict, work_fn: str, reads, *extra) -> Optional[float]:
    """100 x least ms / measured ms a step; `reads` one READS-like dict or
    several whose times add up."""
    fn = getattr(ctx.get("work"), work_fn, None)
    peaks = ctx.get("peaks")
    if fn is None or not peaks:
        return None
    times = [phase_reduce.reading(ctx, r) for r in reads]
    if any(t is None for t in times) or sum(times) <= 0:
        return None
    flops, least_bytes = fn(ctx["config"], ctx["mix"], *extra)
    least_ms = 1e3 * max(flops / peaks["flops_per_s"],
                         least_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least_ms / sum(times)
