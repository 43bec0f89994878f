"""obs core (deeprec_tpu/obs/): metrics registry semantics — labeled
counters/gauges/histograms, ring-buffer windowed queries (p99 over a
window, rate, slope), Prometheus render/parse round trip, mergeable
snapshots, the DEEPREC_OBS=off null plane — and the tracer: off by
default with a PROVABLY allocation-free disabled path, span
nesting/propagation, append-only files that survive a process restart
while process-local counters reset, and the Perfetto exporter."""
import json
import os
import subprocess
import sys
import tempfile

import pytest

from deeprec_tpu.obs import metrics as M
from deeprec_tpu.obs import schema, trace as T

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def clockreg():
    """Registry on an injectable clock, so window queries are exact."""
    clk = [1000.0]
    reg = M.MetricsRegistry(clock=lambda: clk[0])
    return clk, reg


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with the tracer disabled."""
    T.shutdown()
    yield
    T.shutdown()


# ------------------------------------------------------------- registry


def test_counter_window_rate_and_total(clockreg):
    clk, reg = clockreg
    c = reg.counter("deeprec_x_steps", "steps")
    for _ in range(20):
        c.inc()
        clk[0] += 1.0
    assert c.value == 20
    # only the last 10 s of increments are inside the window
    w = reg.window("deeprec_x_steps", seconds=10.0)
    assert w["delta"] == pytest.approx(10.0, abs=2.0)
    assert w["rate_per_sec"] == pytest.approx(1.0, abs=0.2)
    # get-or-create: same (name, labels) -> same object
    assert reg.counter("deeprec_x_steps", "steps") is c
    assert reg.counter("deeprec_x_steps", labels={"a": "b"}) is not c


def test_gauge_window_slope(clockreg):
    clk, reg = clockreg
    g = reg.gauge("deeprec_x_imb", "imbalance", {"table": "t0"})
    for i in range(8):
        g.set(2.0 + 0.5 * i)   # slope 0.25/s at 2 s per set
        clk[0] += 2.0
    w = reg.window("deeprec_x_imb", {"table": "t0"}, seconds=30.0)
    assert w["last"] == 5.5
    assert w["slope_per_sec"] == pytest.approx(0.25, rel=0.05)


def test_histogram_windowed_p99_forgets_old_samples(clockreg):
    clk, reg = clockreg
    h = reg.histogram("deeprec_x_lat", "lat", {"stage": "e2e"})
    for _ in range(100):
        h.record(0.5)          # old: 500 ms spike era
    clk[0] += 300.0            # ... scrolls out of the ring entirely
    for _ in range(100):
        h.record(0.001)
    win = h.window_summary(60.0)
    assert win["count"] == 100
    assert win["p99_ms"] < 10.0          # the spike era is forgotten
    assert h.summary()["p99_ms"] > 100.0  # lifetime totals still see it


def test_histogram_summary_shape_matches_latency_histogram():
    """ServingStats swaps LatencyHistogram for the registry Histogram —
    identical recordings must produce the identical summary dict."""
    from deeprec_tpu.training.profiler import LatencyHistogram

    reg = M.MetricsRegistry()
    h = reg.histogram("deeprec_x_h", "")
    ref = LatencyHistogram()
    for v in (0.0001, 0.002, 0.03, 0.4, 5.0, 0.002, 0.002):
        h.record(v)
        ref.record(v)
    assert h.summary() == ref.summary()


def test_prometheus_render_parse_roundtrip_and_callbacks(clockreg):
    _, reg = clockreg
    reg.counter("deeprec_x_req", "requests", {"stage": "e2e"}).inc(7)
    reg.gauge("deeprec_x_g", "a gauge").set(1.5)
    reg.histogram("deeprec_x_h", "hist").record(0.01)
    depth = [3]
    reg.register_callback("deeprec_x_depth", lambda: depth[0], "queue",
                          {"srv": "a"})
    text = reg.render_prometheus()
    parsed = M.parse_prometheus(text)
    assert parsed[("deeprec_x_req_total", '{stage="e2e"}')] == 7.0
    assert parsed[("deeprec_x_g", "")] == 1.5
    assert parsed[("deeprec_x_depth", '{srv="a"}')] == 3.0
    assert parsed[("deeprec_x_h_count", "")] == 1.0
    assert any(k[0] == "deeprec_x_h_bucket" for k in parsed)
    # callbacks are live, and survive a reset() (bindings, not counts)
    depth[0] = 9
    reg.reset()
    parsed = M.parse_prometheus(reg.render_prometheus())
    assert parsed[("deeprec_x_depth", '{srv="a"}')] == 9.0
    assert ("deeprec_x_req_total", '{stage="e2e"}') not in parsed


def test_render_extra_labels_and_stale_marking(clockreg):
    _, reg = clockreg
    reg.counter("deeprec_x_req", "r").inc()
    text = M.render_snapshot(reg.snapshot(),
                             extra_labels={"member": "h:1"}, stale=True)
    parsed = M.parse_prometheus(text)
    assert parsed[("deeprec_x_req_total",
                   '{member="h:1",stale="1"}')] == 1.0


def test_concat_prometheus_dedupes_family_headers(clockreg):
    """Real Prometheus parsers reject a repeated # TYPE line for the
    same family — concatenating per-member renders must collapse them
    while keeping every sample line."""
    _, reg = clockreg
    reg.counter("deeprec_x_req", "r").inc()
    a = M.render_snapshot(reg.snapshot(), extra_labels={"member": "h:1"})
    b = M.render_snapshot(reg.snapshot(), extra_labels={"member": "h:2"},
                          stale=True)
    text = M.concat_prometheus([a, b])
    lines = text.splitlines()
    assert lines.count("# TYPE deeprec_x_req counter") == 1
    assert sum(1 for ln in lines
               if ln.startswith("deeprec_x_req_total")) == 2
    M.parse_prometheus(text)  # still well-formed


def test_merge_snapshots_sums_counters_and_hists(clockreg):
    _, reg = clockreg
    reg.counter("deeprec_x_req", "r").inc(3)
    reg.histogram("deeprec_x_h", "h").record(0.01)
    s = reg.snapshot()
    merged = M.merge_snapshots([s, s, s])
    ent = merged["metrics"]["deeprec_x_req"]["series"][0]
    assert ent["value"] == 9.0
    assert merged["metrics"]["deeprec_x_h"]["series"][0]["n"] == 3


def test_disabled_plane_hands_out_noops(monkeypatch):
    M.set_metrics_enabled(False)
    try:
        reg = M.MetricsRegistry()
        c = reg.counter("deeprec_x", "")
        g = reg.gauge("deeprec_y", "")
        h = reg.histogram("deeprec_z", "")
        assert c is g is h  # THE null singleton
        c.inc()
        g.set(3)
        h.record(0.5)
        assert h.summary()["count"] == 0
        assert reg.snapshot() == {"metrics": {}}
    finally:
        M.set_metrics_enabled(None)


def test_serving_stats_works_with_plane_off():
    """DEEPREC_OBS=off must leave the legacy /v1/stats surface fully
    functional (plain LatencyHistograms, no registry)."""
    from deeprec_tpu.serving.stats import ServingStats

    M.set_metrics_enabled(False)
    try:
        st = ServingStats()
        assert st.registry is None
        st.record_stage("e2e", 0.01)
        st.record_batch(2, 16)
        snap = st.snapshot()
        assert snap["requests"] == 2 and snap["rows"] == 16
        assert snap["stages"]["e2e"]["count"] == 1
        assert st.window_p99_ms() is None
        assert st.metrics_snapshot() is None
    finally:
        M.set_metrics_enabled(None)


def test_serving_stats_registry_backed_windows():
    from deeprec_tpu.serving.stats import ServingStats

    st = ServingStats()
    assert st.registry is not None
    st.record_stage("e2e", 0.02)
    st.record_batch(1, 4)
    assert st.snapshot()["stages"]["e2e"]["count"] == 1
    assert st.window_p99_ms("e2e", 60.0) == pytest.approx(20.0, rel=0.6)
    text = M.render_snapshot(st.metrics_snapshot())
    assert "deeprec_serving_stage_seconds_bucket" in text


# --------------------------------------------------------------- schema


def test_health_payload_canonical_keys_and_aliases():
    h = schema.health_payload("ok", model_version=3, step=10,
                              staleness_seconds=0.5, quarantined=1,
                              member="h:1")
    assert schema.is_health_payload(h)
    assert h["schema"] == schema.HEALTH_SCHEMA
    # the historical keys ARE canonical members — old readers keep working
    for k in ("status", "model_version", "step", "staleness_seconds",
              "consecutive_poll_failures", "last_good_version",
              "quarantined"):
        assert k in h
    assert h["member"] == "h:1"  # surface-specific extras ride along


# ---------------------------------------------------------------- trace


def test_tracing_off_by_default_and_identity_noop():
    assert not T.tracing_enabled()
    s1 = T.span("a")
    s2 = T.server_span("b", "c")
    assert s1 is s2 is T.NOOP_SPAN
    assert T.start_request() is None
    with s1:
        assert T.current() is None


def test_disabled_tracing_is_zero_allocation():
    """The disabled path allocates NOTHING per call: span() returns the
    module singleton, emit()/phase_span() return before building
    anything. Pinned with tracemalloc over 2000 calls — the only
    allocations attributable to trace.py are a handful of transient
    CPython frame objects (frame-pool noise, O(1) count), never O(N)."""
    import tracemalloc

    assert not T.tracing_enabled()
    with T.span("warm"):   # touch every lazy path once before measuring
        pass
    T.emit("warm", "", 0.0, 0.0)
    N = 2000
    tracemalloc.start()
    try:
        for _ in range(N):
            with T.span("x", "y"):
                pass
            T.emit("x", "y", 0.0, 1.0)
            T.phase_span("x", 0.0, 1.0)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    tfile = T.__file__
    stats = [st for st in snap.statistics("filename")
             if st.traceback[0].filename == tfile]
    count = sum(st.count for st in stats)
    size = sum(st.size for st in stats)
    assert count < N / 100, (
        f"disabled tracing allocated {count} objects over {N} calls "
        f"({size}B) — the no-op path is allocating per call")


def test_span_nesting_propagation_and_export(tmp_path):
    path = str(tmp_path / "t.jsonl")
    T.configure(path, sample=1.0, service="svc")
    with T.server_span("edge", "serving") as edge:
        assert T.current() == edge.ctx
        with T.span("inner") as inner:
            assert inner.ctx[0] == edge.ctx[0]  # same trace id
            assert inner.parent == edge.ctx[1]
    # retrospective child emission (the micro-batcher idiom)
    T.emit("stage_queue", "serving", 1.0, 2.0,
           ctx=T.child(edge.ctx), parent=edge.ctx[1])
    T.flush()
    evs = [json.loads(ln) for ln in open(path)]
    names = {e["name"] for e in evs}
    assert names == {"edge", "inner", "stage_queue"}
    tids = {e["args"]["trace"] for e in evs}
    assert len(tids) == 1
    assert all(e["args"]["service"] == "svc" for e in evs)

    # header + wire propagation round-trips
    hdr = T.to_header(edge.ctx)
    assert T.from_header(hdr) == edge.ctx
    assert T.from_header("garbage") is None
    assert T.unpack_wire(T.pack_wire(edge.ctx)) == edge.ctx

    # exporter: Perfetto/Chrome shape + trace-id filter
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import obs_trace

    out = str(tmp_path / "trace.json")
    rep = obs_trace.export([path], out)
    assert rep["events"] == 3 and rep["traces"] == 1
    doc = json.load(open(out))
    assert {e["name"] for e in doc["traceEvents"]} >= names
    assert any(e.get("ph") == "M" for e in doc["traceEvents"])
    ids = obs_trace.trace_ids(obs_trace.load_events([path]))
    (tid,) = ids
    assert set(ids[tid]) == names


def test_sampling_zero_never_traces(tmp_path):
    T.configure(str(tmp_path / "t.jsonl"), sample=0.0)
    assert all(T.start_request() is None for _ in range(50))
    # ...but a propagated context is always honored
    sp = T.server_span("hop", header="00000000000000aa-00000000000000bb")
    assert sp is not T.NOOP_SPAN
    assert sp.ctx[0] == 0xAA


def test_restart_resets_counters_but_trace_file_survives(tmp_path):
    """The supervisor-restart contract: a respawned worker starts its
    process-local registry from zero, while the shared trace JSONL only
    GROWS (append mode) — two real worker processes prove both halves."""
    trace_path = str(tmp_path / "worker.jsonl")
    script = (
        "import json, os, sys\n"
        "from deeprec_tpu.obs import metrics as M, trace as T\n"
        "reg = M.default_registry()\n"
        "c = reg.counter('deeprec_restart_probe', '')\n"
        "before = c.value\n"
        "c.inc(5)\n"
        "T.phase_span('work', 1.0, 2.0)\n"
        "T.flush()\n"
        "print(json.dumps({'pid': os.getpid(), 'before': before,"
        " 'after': c.value}))\n"
    )
    outs = []
    for _ in range(2):  # generation 0, then the "restarted" generation
        r = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                 "DEEPREC_TRACE": trace_path},
            timeout=120, check=True)
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert [o["before"] for o in outs] == [0.0, 0.0]  # counters reset
    assert [o["after"] for o in outs] == [5.0, 5.0]
    evs = [json.loads(ln) for ln in open(trace_path)]
    # (beside each generation's own span the file holds what the package's
    # recorder of set-up wrote there: the import, obs/compile_log.py)
    assert {e["name"] for e in evs} == {"work", "setup import"}
    evs = [e for e in evs if e["name"] == "work"]
    assert len(evs) == 2                              # file accumulated
    assert {e["pid"] for e in evs} == {o["pid"] for o in outs}


def test_exporter_skips_torn_tail(tmp_path):
    """A SIGKILL mid-append leaves a torn last line — the exporter must
    load everything else, not die (fault traces are the point)."""
    p = tmp_path / "t.jsonl"
    good = json.dumps({"name": "a", "ph": "X", "ts": 1, "dur": 1, "pid": 1,
                       "tid": 1})
    p.write_text(good + "\n" + good[: len(good) // 2])
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import obs_trace

    assert len(obs_trace.load_events([str(p)])) == 1
