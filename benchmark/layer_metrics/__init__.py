"""One reader per per-layer metric, found by the metric's name.

Each module gives LAYER, UNIT, MOVES and SOURCE (what BENCHMARK.json says of
the metric), READS (what it reads, each key a kind: `KINDS` below) and
`read(ctx) -> float | None`. `ctx` is the harness's record of
the traced run (harness.run_cell builds it). A reader that finds nothing to
read returns None and the metric is left out of the line; it never returns 0
for a share of a roofline or of a peak.
"""

from benchmark.phase_reduce import KINDS as _BY_SCOPE

# The kinds a READS may name. The first seven read the traced run's device
# time or host spans through the program's scopes (benchmark/phase_reduce.py,
# whose docstring says what each is); the rest: `layers` device self time of
# layers of `layers/*.json` (trace_reduce), `work` functions of the
# configuration's work module, `counters` the program's counters over the
# window, `bench_span` a host span of the harness's loop, `step_program` the
# device spans of the step program, `window` the window's own facts (rate,
# busy time, compiles).
KINDS = _BY_SCOPE + ("layers", "work", "counters", "bench_span",
                     "step_program", "window")
