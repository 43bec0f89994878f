"""The on-chip benchmark of deeprec_tpu (BENCHMARK.json at the repo root).

Everything the yardstick needs lives here so that later PRs cannot move it:
traffic generators, the trace reductions, the table of peaks, each family's
work counts, each configuration's plain reference and the comparison that
decides `correct`. PERF.md section 3 lists the files a configuration, a
family, a mix, a generator, a cell, a layer, a scope and a per-layer metric
each are: a later PR adds them as new files and entries only.
"""
