"""Profiling/tracing — the timeline analog.

DeepRec exposes per-step timelines via RunOptions.trace_level +
StepStatsCollector and modelzoo --timeline flags (SURVEY.md §5). On TPU the
native equivalent is the XLA/JAX profiler: traces capture HLO-level device
timelines viewable in TensorBoard/Perfetto. `StepWindowTracer` is the
step-windowed helper matching the reference's "--timeline N" UX; what the
trace then names (the step's phases, the engine's stages, the host spans)
is the vocabulary of utils/scopes.py, and docs/profiling.md says how to
reduce a trace by it. `LatencyHistogram` is the serving stages' timer.
"""
from __future__ import annotations

import bisect
import os
import threading
from typing import Dict

import jax


class LatencyHistogram:
    """Fixed-bucket log-scale latency histogram: O(1) record, bounded
    memory, mergeable — the accounting primitive behind serving's
    per-stage timers (serving/stats.py) and anything else that needs
    percentiles without keeping every sample.

    Buckets grow geometrically from `lo` seconds; values above the last
    bound land in an overflow bucket whose percentile estimate is the
    tracked exact max. Thread-safe (one small lock per record)."""

    GROWTH = 1.5

    def __init__(self, lo: float = 50e-6, hi: float = 120.0):
        bounds = []
        b = lo
        while b < hi:
            bounds.append(b)
            b *= self.GROWTH
        self._bounds = bounds  # upper edge of each bucket, seconds
        self._counts = [0] * (len(bounds) + 1)  # +1 overflow
        self._n = 0
        self._sum = 0.0
        self._max = 0.0
        self._lock = threading.Lock()

    def record(self, seconds: float) -> None:
        s = float(seconds)
        i = bisect.bisect_left(self._bounds, s)
        with self._lock:
            self._counts[i] += 1
            self._n += 1
            self._sum += s
            if s > self._max:
                self._max = s

    def merge(self, other: "LatencyHistogram") -> None:
        with other._lock:
            counts, n = list(other._counts), other._n
            tot, mx = other._sum, other._max
        with self._lock:
            for i, c in enumerate(counts):
                self._counts[i] += c
            self._n += n
            self._sum += tot
            self._max = max(self._max, mx)

    def percentile(self, q: float) -> float:
        """Upper-bucket-edge estimate of the q-quantile in seconds."""
        with self._lock:
            n, counts, mx = self._n, list(self._counts), self._max
        if n == 0:
            return 0.0
        target = min(int(q * n), n - 1)
        seen = 0
        for i, c in enumerate(counts):
            seen += c
            if seen > target:
                # clamp to the exact max: a coarse bucket's upper edge can
                # exceed every sample in it (p99 > max is self-contradictory)
                return min(self._bounds[i], mx) if i < len(self._bounds) else mx
        return mx

    def summary(self) -> Dict[str, float]:
        """{count, mean_ms, p50_ms, p90_ms, p99_ms, max_ms} — the shape
        `/v1/stats` and SERVING_BENCH.json report per stage."""
        with self._lock:
            n, tot, mx = self._n, self._sum, self._max
        return {
            "count": n,
            "mean_ms": round(tot / n * 1e3, 3) if n else 0.0,
            "p50_ms": round(self.percentile(0.50) * 1e3, 3),
            "p90_ms": round(self.percentile(0.90) * 1e3, 3),
            "p99_ms": round(self.percentile(0.99) * 1e3, 3),
            "max_ms": round(mx * 1e3, 3),
        }


class StepWindowTracer:
    """Trace steps [start, stop) of a training loop — the
    START/STOP_NODE_STATS_STEP pattern (Executor-Optimization.md) without a
    cost-model executor to feed: the trace goes to the human/profiler."""

    def __init__(self, start_step: int, stop_step: int,
                 logdir: str = "/tmp/deeprec_tpu_trace"):
        self.start = start_step
        self.stop = stop_step
        self.logdir = logdir
        self._active = False

    def on_step(self, step: int) -> None:
        """Call BEFORE dispatching step `step`; traces steps in
        [start, stop). Range-based so a run resuming past `start` (e.g. from
        a checkpoint) still enters the window if any of it remains."""
        if self.start <= step < self.stop and not self._active:
            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            self._active = True
        elif step >= self.stop and self._active:
            jax.profiler.stop_trace()
            self._active = False

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
