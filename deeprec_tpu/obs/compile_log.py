"""The one recorder of what a process spends before its first step: every
trace, lowering and compile-or-load jax makes, by program; every Pallas
kernel body traced, by kernel; the program's own set-up calls; and what the
persistent compilation cache answered.

jax tells what it does through `jax.monitoring`, and `install()` (called by
`utils.backend.enable_compile_cache()` and by `Trainer.__init__`; idempotent,
once a process, never removed) registers the only three listeners of the
package:

  * a time-span listener for `/jax/core/compile/jaxpr_trace_duration`
    (stage `trace`), `jaxpr_to_mlir_module_duration` (`lower`: jaxpr to MLIR,
    every Pallas call's Mosaic lowering inside) and `backend_compile_duration`
    (`backend`: an XLA compile, or a load from the persistent cache), each
    with its start, its end and jax's `fun_name`;
  * an event listener for the cache's answers (`request`, `hit`, `miss`,
    `disabled`; jax counts a `miss` when it WRITES the program it compiled,
    so a program under the cache's thresholds is a request and neither);
  * a duration listener for `cache_retrieval_time_sec` and
    `compile_time_saved_sec`.

Pallas has no event that names a kernel (jax hears a kernel's bind as the
trace of a function called `wrapped`), so a kernel's bind tells the recorder
itself (`utils/scopes.py::kernel_trace`), and so do the program's own set-up
calls
(`scopes.host_spanned`; the package's import, from its own first and last
statements).

**Self time.** jax traces a jitted function called inside another's trace,
a kernel's body inside a program's trace, and `Trainer.init` under a
caller's `jit`: the inner span lies inside the outer, and a sum over names
would count it twice. A span's `parent` is the span of the same thread that
encloses it (a span ends after everything inside it, so the spans that ended
on this thread with a start at or after its own are inside it), and what is
booked everywhere is a span's duration LESS what its children cover. A
kernel's bind is the one span that keeps what is inside it: the traces jax
makes while it is open (`wrapped`, and every jitted `jnp` function the body
calls) are the body's trace, so they are counted under their own names with
no seconds, and the kernel's series holds them. The sum of every series
below over one thread is therefore at most the wall time that thread spent,
whatever nests in what (two threads that compile at once add up to more).

Published on the default registry (`GET /metrics`; docs/observability.md):

  deeprec_compile_seconds_total{stage,program}   self seconds
  deeprec_compile_spans_total{stage,program}     spans
  deeprec_pallas_trace_seconds_total{kernel}     a kernel body's bind
  deeprec_pallas_traces_total{kernel}
  deeprec_setup_seconds_total{stage}             import, trainer_build,
                                                 init_state
  deeprec_compile_cache_total{outcome}           request, hit, miss, disabled
  deeprec_compile_cache_seconds_total{kind}      retrieval, saved
  deeprec_compile_cache_dir_bytes, deeprec_compile_cache_cap_bytes   gauges

`program` is jax's `fun_name` without the `jit(...)` the later stages wrap
it in, so the three stages of one program share a label; the label sets are
bounded by the functions a process jits. The recorder's own counts
(`compiles()`, `traces()`, `snapshot()`, `spans()`) live in this module and
not in the registry, so `analysis/trace_guard.py` and
`chip_smoke.CompileClock` read them under `DEEPREC_OBS=off` too. Where
`obs.trace` is configured the finished spans go to its JSONL as well
(`trace.phase_span`), for `tools/obs_trace.py`'s timeline.

Always on: a dictionary update when jax traces or compiles something, and
nothing when it does not (a warm `train_step` reaches none of this).
"""
from __future__ import annotations

import collections
import os
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional, Tuple

from deeprec_tpu.obs import metrics as _metrics
from deeprec_tpu.obs import trace as _trace

TRACE, LOWER, BACKEND = "trace", "lower", "backend"
KERNEL_TRACE, SETUP = "kernel_trace", "setup"

_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": BACKEND,
}
_CACHE_OUTCOMES = {
    "/jax/compilation_cache/compile_requests_use_cache": "request",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/task_disabled_cache": "disabled",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval",
    "/jax/compilation_cache/compile_time_saved_sec": "saved",
}
# (seconds, count) series of a stage, and the label its spans' names go by
_SERIES = {
    **{stage: ("deeprec_compile_seconds", "deeprec_compile_spans", "program")
       for stage in (TRACE, LOWER, BACKEND)},
    KERNEL_TRACE: ("deeprec_pallas_trace_seconds", "deeprec_pallas_traces",
                   "kernel"),
    SETUP: ("deeprec_setup_seconds", None, "stage"),
}
_HELP = {
    "deeprec_compile_seconds": "self seconds jax spent tracing, lowering and "
                               "compiling or loading, by program",
    "deeprec_compile_spans": "traces, lowerings and compiles-or-loads, by "
                             "program",
    "deeprec_pallas_trace_seconds": "self seconds binding a Pallas kernel "
                                    "(its body's trace)",
    "deeprec_pallas_traces": "Pallas kernel bodies traced",
    "deeprec_setup_seconds": "self seconds of the program's own set-up",
    "deeprec_compile_cache": "the persistent compilation cache's answers",
    "deeprec_compile_cache_seconds": "seconds loading from the persistent "
                                     "cache, and compile seconds it saved",
}
_MAX_SPANS = 8192    # the list of finished spans kept for a dump
_MAX_OPEN = 4096     # a thread's finished spans that no span encloses yet


class Span(NamedTuple):
    id: int
    stage: str
    program: str
    start: float             # time.time(), as jax gives it
    end: float
    parent: Optional[int]    # the enclosing span's id, once that has ended
    self_s: float            # the duration less what the children cover
    thread: int


class _Kept:
    """A finished span as the recorder keeps it: `parent` is written when
    the span that encloses it ends."""

    __slots__ = Span._fields + ("seconds",)

    def __init__(self, *fields):
        for slot, value in zip(self.__slots__, fields):
            setattr(self, slot, value)


def _program(fun_name: str) -> str:
    """jax's `fun_name` without the `jit(...)` round a module's name."""
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


class _Recorder:
    def __init__(self):
        self.lock = threading.Lock()
        self.installed = False
        self.clock_offset = 0.0   # time.time() - time.perf_counter()
        self.next_id = 0
        self.spans: Deque[_Kept] = collections.deque(maxlen=_MAX_SPANS)
        self.open: Dict[int, List[_Kept]] = {}    # thread -> finished, free
        self.by_stage: Dict[str, list] = {}   # stage -> [n, self s, all s]
        self.by_name: Dict[Tuple[str, str], list] = {}
        self.cache: Dict[str, int] = dict.fromkeys(
            _CACHE_OUTCOMES.values(), 0)
        self.cache_seconds: Dict[str, float] = dict.fromkeys(
            _CACHE_SECONDS.values(), 0.0)


_REC = _Recorder()
_BINDS = threading.local()   # .open: kernel binds open on this thread


def _counter(name: str, labels: Dict[str, str]):
    return _metrics.default_registry().counter(name, _HELP[name], labels)


def _tally(stage: str, name: str, self_s: float, dur: float) -> None:
    """One more span in the recorder's own counts; the caller holds the
    lock."""
    for key, table in ((stage, _REC.by_stage), ((stage, name), _REC.by_name)):
        tot = table.setdefault(key, [0, 0.0, 0.0])
        tot[0] += 1
        tot[1] += self_s
        tot[2] += dur


def kernel_bind_begins() -> None:
    """A kernel's bind opens on this thread: until its `record`, the traces
    jax makes are its own (they carry no seconds of their own)."""
    _BINDS.open = getattr(_BINDS, "open", 0) + 1


def record(stage: str, name: str, start: float, end: float,
           seconds: Optional[float] = None) -> None:
    """A finished span of this thread: `start` and `end` on `time.time()`
    (where it nests), `seconds` its duration where another clock measured
    it. jax's listeners, utils/scopes.py and the package's `__init__` (its
    import) call this; nothing else does."""
    dur = max((end - start) if seconds is None else seconds, 0.0)
    tid = threading.get_ident()
    if stage == KERNEL_TRACE:
        _BINDS.open -= 1
    # a trace inside a kernel's bind is the kernel's: counted, no seconds
    held = stage == TRACE and getattr(_BINDS, "open", 0) > 0
    rec = _REC
    with rec.lock:
        sid = rec.next_id
        rec.next_id += 1
        free = rec.open.setdefault(tid, [])
        covered = 0.0
        while free and free[-1].start >= start:
            child = free.pop()
            child.parent = sid
            covered += child.seconds
        if held:   # what it covers that is not a trace stays subtracted
            self_s, dur = 0.0, covered
        else:
            self_s = max(dur - covered, 0.0)
        span = _Kept(sid, stage, name, start, end, None, self_s, tid, dur)
        free.append(span)
        if len(free) > _MAX_OPEN:
            del free[:_MAX_OPEN // 2]
        rec.spans.append(span)
        _tally(stage, name, self_s, dur)
    seconds_series, count_series, label = _SERIES[stage]
    labels = {label: name}
    if label == "program":
        labels["stage"] = stage
    _counter(seconds_series, labels).inc(self_s)
    if count_series:
        _counter(count_series, labels).inc()
    if _trace.tracing_enabled():
        _trace.phase_span(f"{stage} {name}", start, end, cat="train")


def _on_span(event: str, start: float, end: float, **kw) -> None:
    stage = _JAX_STAGES.get(event)
    if stage is not None:
        record(stage, _program(kw.get("fun_name", "")), start, end)


def _on_event(event: str, **kw) -> None:
    outcome = _CACHE_OUTCOMES.get(event)
    if outcome is not None:
        with _REC.lock:
            _REC.cache[outcome] += 1
        _counter("deeprec_compile_cache", {"outcome": outcome}).inc()


def _on_seconds(event: str, seconds: float, **kw) -> None:
    kind = _CACHE_SECONDS.get(event)
    if kind is not None:
        # jax keeps a program's compile time in whole seconds, so what a
        # load "saved" of a quick program can come out under zero
        seconds = max(float(seconds), 0.0)  # noqa: DRT002 — a host duration jax hands its listeners
        with _REC.lock:
            _REC.cache_seconds[kind] += seconds
        _counter("deeprec_compile_cache_seconds", {"kind": kind}).inc(seconds)


def install() -> None:
    """Register the listeners, once a process."""
    rec = _REC
    if rec.installed:
        return
    with rec.lock:
        if rec.installed:
            return
        import jax

        rec.clock_offset = time.time() - time.perf_counter()
        jax.monitoring.register_event_time_span_listener(_on_span)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_seconds)
        rec.installed = True
    # the cache's four answers read 0, not nothing, until one is heard
    for outcome in rec.cache:
        _counter("deeprec_compile_cache", {"outcome": outcome})


def note_cache_dir(path: str, cap_bytes: int) -> None:
    """The two gauges of the cache's directory: what it holds now (one
    `os.scandir`) and its cap (-1 where the caller placed the cache)."""
    try:
        with os.scandir(path) as entries:
            held = sum(e.stat().st_size for e in entries if e.is_file())
    except OSError:
        held = 0
    reg = _metrics.default_registry()
    reg.gauge("deeprec_compile_cache_dir_bytes",
              "bytes in the persistent compilation cache's directory when "
              "the process enabled it").set(held)
    reg.gauge("deeprec_compile_cache_cap_bytes",
              "the cache's cap in bytes; -1 where the caller placed the "
              "cache").set(cap_bytes)


# ------------------------------------------------- the recorder's own counts


def compiles() -> int:
    """Compiles-or-loads heard so far (jax's backend_compile events)."""
    return _REC.by_stage.get(BACKEND, (0,))[0]


def traces() -> int:
    """jaxpr traces heard so far."""
    return _REC.by_stage.get(TRACE, (0,))[0]


def clock_offset() -> float:
    """`time.time() - time.perf_counter()` at install: lays a dump of
    `spans()` on a clock that `perf_counter` keeps."""
    return _REC.clock_offset


def snapshot() -> Dict:
    """{"spans": {stage: n}, "self_s": {stage: s}, "total_s": {stage: s},
    "by_name": {(stage, name): (n, self s, total s)}, "cache": {outcome: n},
    "cache_seconds": {kind: s}}: the counts as of now."""
    rec = _REC
    with rec.lock:
        return {
            "spans": {k: v[0] for k, v in rec.by_stage.items()},
            "self_s": {k: v[1] for k, v in rec.by_stage.items()},
            "total_s": {k: v[2] for k, v in rec.by_stage.items()},
            "by_name": {k: tuple(v) for k, v in rec.by_name.items()},
            "cache": dict(rec.cache),
            "cache_seconds": dict(rec.cache_seconds),
        }


def spans() -> List[Span]:
    """The finished spans kept (the newest `_MAX_SPANS`), oldest first."""
    with _REC.lock:
        return [Span(*(getattr(s, f) for f in Span._fields))
                for s in _REC.spans]
