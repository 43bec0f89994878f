"""Runtime trace-guard: assert a compile budget over a code region.

Every serving/perf incident of the retrace class — PR 5's
``_prune_to_live`` eager closure re-tracing the rebuild probe loop on
every delta (45–115 ms stalls next to live traffic), PR 2's stale jit
executables after ``update_budgets`` — was ultimately "XLA compiled
when we believed it could not". This module makes that belief
executable:

    from deeprec_tpu.analysis import trace_guard

    with trace_guard(max_compiles=0):
        predictor.poll_updates()          # replay must be cache-hit only

    with trace_guard(max_compiles=0) as g:
        state, mets = trainer.train_steps(state, stacked)
    print(g.compiles)                     # 0 after warmup, by contract

Counting rides jax.monitoring through the package's one recorder of
compile events (obs/compile_log.py: installed on first use at the latest,
never removed; its own counts, which ``DEEPREC_OBS=off`` does not
silence): the ``/jax/core/compile/backend_compile_duration`` event — fired
exactly once per real XLA compilation, never on an executable-cache hit —
and ``/jax/core/compile/jaxpr_trace_duration`` (tracing; informational,
retraces that hit the persistent compilation cache still cost a trace).
Counters are process-wide: a guard around region R sees compiles from
ANY thread that lands inside R's window. That is the desired semantics
for the serving tests (a background poller compiling next to traffic is
exactly the bug), but it means guards should not wrap regions where
unrelated threads legitimately warm code.

Used as a hard gate in tests/test_serving_update.py (delta replay),
tests/test_dedup.py (update_budgets rebuild), tests/test_analysis.py
(steady-state K-step training) and bench.py --smoke (steady-state
windows record their compile count into the bench JSON;
``tools/roofline.py --assert-compiles`` fails CI when it drifts above
zero).
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional

from deeprec_tpu.obs import compile_log


class TraceGuardViolation(AssertionError):
    """A guarded region compiled more XLA programs than its budget."""

    def __init__(self, message: str, compiles: int, max_compiles: int):
        super().__init__(message)
        self.compiles = compiles
        self.max_compiles = max_compiles


def compile_count() -> int:
    """Process-lifetime count of real XLA compilations observed so far
    (only since the recorder was installed: by an entry point's
    `enable_compile_cache()`, a `Trainer`, or the first use here)."""
    compile_log.install()
    return compile_log.compiles()


def trace_count() -> int:
    """Process-lifetime count of jaxpr traces observed so far."""
    compile_log.install()
    return compile_log.traces()


class _Guard:
    """Live view of a guarded region's counters."""

    def __init__(self, c0: int, t0: int):
        self._c0 = c0
        self._t0 = t0

    @property
    def compiles(self) -> int:
        return compile_log.compiles() - self._c0

    @property
    def traces(self) -> int:
        return compile_log.traces() - self._t0


@contextmanager
def trace_guard(max_compiles: Optional[int] = 0, note: str = ""):
    """Context manager asserting the region compiles at most
    ``max_compiles`` XLA programs (``None`` = measure only, never
    raise). Yields a guard whose ``.compiles``/``.traces`` read live and
    remain valid after exit. Exceptions from the body propagate
    unchanged (the budget is not checked on an already-failing region).
    """
    g = _Guard(compile_count(), trace_count())
    # A body exception propagates from the yield on its own and skips the
    # budget check — a failing region is never double-reported.
    yield g
    if max_compiles is not None and g.compiles > max_compiles:
        where = f" [{note}]" if note else ""
        raise TraceGuardViolation(
            f"trace_guard{where}: region compiled {g.compiles} XLA "
            f"program(s), budget {max_compiles} — something inside is "
            "re-tracing (per-call jit(lambda)/closure, a stale "
            "executable rebuild, or an unwarmed shape); see "
            "docs/analysis.md",
            g.compiles, max_compiles,
        )
