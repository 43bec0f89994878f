"""One reader per per-layer metric, found by the metric's name.

Each module gives LAYER, UNIT, MOVES and SOURCE (what BENCHMARK.json says of
the metric) and `read(ctx) -> float | None`. `ctx` is the harness's record of
the traced run (harness.run_cell builds it). A reader that finds nothing to
read returns None and the metric is left out of the line; it never returns 0
for a share of a roofline or of a peak.
"""
