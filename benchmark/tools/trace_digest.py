#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, how many events, the stats the
first events of each line carry, and the reduction's own result.

    python3 benchmark/tools/trace_digest.py <trace dir> <out.json> [chips]
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax

    from benchmark import trace_reduce

    trace_dir, out = sys.argv[1], sys.argv[2]
    chips = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    path = trace_reduce.find_xplane(trace_dir)
    digest = {"file": path, "bytes": os.path.getsize(path), "planes": []}
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            events = list(line.events)
            names = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + 1
            p["lines"].append({
                "name": line.name, "events": len(events),
                "distinct_names": len(names),
                "most_common": sorted(names.items(),
                                      key=lambda kv: -kv[1])[:8],
                "first": [{"name": e.name, "start_ns": e.start_ns,
                           "dur_ns": e.duration_ns,
                           "stats": {k: str(v)[:300] for k, v in e.stats}}
                          for e in events[:6]],
            })
        digest["planes"].append(p)
    try:
        red = trace_reduce.reduce_dir(trace_dir, chips)
        red["modules"] = red["modules"][:20]
        digest["reduced"] = red
    except Exception as e:  # a digest must still say what the trace holds
        digest["reduced_error"] = repr(e)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(digest, f, indent=1)
    print(f"digest of {path} ({digest['bytes']} bytes) -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
