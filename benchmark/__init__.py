"""The on-chip benchmark of deeprec_tpu (BENCHMARK.json at the repo root).

Everything the yardstick needs lives here so that later PRs cannot move it:
traffic generation, the trace reduction, the table of peaks, the FLOP and
byte counts, each configuration's plain reference and the comparison that
decides `correct`. PERF.md says how to add a configuration, a traffic mix,
a cell or a per-layer metric with new files only.
"""
