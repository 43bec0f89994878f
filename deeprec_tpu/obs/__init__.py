"""Unified telemetry plane — the one subsystem the whole stack reports
into (docs/observability.md).

Three halves:

  * ``obs.metrics`` — a thread-safe ``MetricsRegistry`` of labeled
    counters / gauges / histograms with a fixed-depth ring-buffer time
    series per metric (windowed p99 / rate / slope — the primitives the
    autoscaler and the placement drift detector consume), mergeable
    snapshots, and Prometheus-text exposition (``GET /metrics``).
  * ``obs.trace`` — sampled cross-process request tracing: a trace id
    born at the HTTP edge rides the frontend's TCP frames into the
    backend micro-batcher stages and back, training-side spans come from
    the checkpoint writer / the tier worker / the delta poll loop (the
    train step's own phases and host spans are the profiler's:
    ``utils/scopes.py``), and everything serializes to Chrome-trace /
    Perfetto JSON via ``tools/obs_trace.py``.
  * ``obs.schema`` — the single health-payload schema the predictor,
    the socket frontend, and the online loop all emit (the old JSON
    keys stay valid as aliases).

Everything here records only host-side values that already exist — no
device sync, no extra compile (the trace_guard / DRT002 contracts hold
with instrumentation on). ``DEEPREC_OBS=off`` turns the metrics plane
into no-op singletons; tracing is off unless explicitly configured
(``DEEPREC_TRACE=<file>`` or ``trace.configure``).
"""
from deeprec_tpu.obs.metrics import (  # noqa: F401
    MetricsRegistry,
    default_registry,
    metrics_enabled,
    parse_prometheus,
)
from deeprec_tpu.obs import schema, trace  # noqa: F401
