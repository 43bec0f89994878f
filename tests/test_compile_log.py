"""The one recorder of set-up (obs/compile_log.py): what it books of jax's
traces, lowerings and compiles-or-loads, of the cache's answers and of the
Pallas kernels' bodies; that a warm step reaches none of it; and that the
benchmark's nine `setup_*` readers read it from the program's registry."""
import importlib

import jax
import jax.numpy as jnp
import optax
import pytest

from deeprec_tpu.analysis import TraceGuardViolation, trace_guard
from deeprec_tpu.data import SyntheticCriteo
from deeprec_tpu.models import DLRMDCN
from deeprec_tpu.obs import compile_log, default_registry, metrics
from deeprec_tpu.optim import Adagrad
from deeprec_tpu.training import Trainer
from deeprec_tpu.utils import scopes

SERIES = ("deeprec_compile_seconds", "deeprec_compile_spans",
          "deeprec_pallas_trace_seconds", "deeprec_pallas_traces",
          "deeprec_setup_seconds", "deeprec_compile_cache",
          "deeprec_compile_cache_seconds")
SETUP_METRICS = ("setup_import_s", "setup_build_s", "setup_trace_s",
                 "setup_kernel_trace_s", "setup_kernel_traces",
                 "setup_lower_s", "setup_compile_or_load_s",
                 "setup_programs", "setup_cache_misses")


def series(names=SERIES):
    """{(family, labels): value} of the recorder's series as of now."""
    snap = default_registry().snapshot()["metrics"]
    return {(n, tuple(sorted(s["labels"].items()))): s["value"]
            for n in names for s in snap.get(n, {"series": ()})["series"]}


def rise(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def spans_after(mark):
    return [s for s in compile_log.spans() if s.id >= mark]


def next_id():
    compile_log.install()
    got = compile_log.spans()
    return got[-1].id + 1 if got else 0


# ------------------------------------------------------------- jax's spans


def test_a_nested_program_books_self_time_once():
    def nested_inner(x):
        return jnp.sin(x) * 2.0

    inner = jax.jit(nested_inner)

    def nested_outer(x):
        return inner(x) + jnp.cos(x)

    x = jnp.arange(8.0)
    mark, before = next_id(), series()
    jax.jit(nested_outer)(x).block_until_ready()
    got = spans_after(mark)
    (outer,) = [s for s in got if s.program == "nested_outer"
                and s.stage == compile_log.TRACE]
    (child,) = [s for s in got if s.program == "nested_inner"]
    assert child.stage == compile_log.TRACE and child.parent == outer.id
    assert outer.start <= child.start and child.end <= outer.end
    # what the outer trace covers is booked once: its own part and, under
    # their own names, the parts of what was traced inside it
    inside, stack = [], [outer.id]
    while stack:
        kids = [s for s in got if s.parent == stack[-1]]
        stack.pop()
        inside += kids
        stack += [k.id for k in kids]
    assert child in inside
    assert outer.self_s + sum(s.self_s for s in inside) == pytest.approx(
        outer.end - outer.start, abs=1e-6)
    assert outer.self_s < outer.end - outer.start
    # one lowering and one compile, under the outer's name without `jit(`
    for stage in (compile_log.LOWER, compile_log.BACKEND):
        (s,) = [s for s in got if s.stage == stage]
        assert s.program == "nested_outer" and s.parent is None
    grown = rise(before, series())
    for stage in ("trace", "lower", "backend"):
        key = (("program", "nested_outer"), ("stage", stage))
        assert grown[("deeprec_compile_spans", key)] == 1
        assert grown[("deeprec_compile_seconds", key)] > 0
    assert grown[("deeprec_compile_seconds",
                  (("program", "nested_outer"), ("stage", "trace")))] \
        == pytest.approx(outer.self_s)


def test_the_caches_answers_are_counted(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    kept = {n: getattr(jax.config, n) for n in names}
    compile_log.install()

    def cached_program(x):
        return jnp.tanh(x) @ x.T

    try:
        cc.reset_cache()
        for n, v in zip(names, (str(tmp_path), True, 0.0, 0)):
            jax.config.update(n, v)
        x = jnp.ones((16, 16))
        before = compile_log.snapshot()
        jax.jit(cached_program)(x).block_until_ready()
        first = compile_log.snapshot()
        jax.clear_caches()
        jax.jit(cached_program)(x).block_until_ready()
        second = compile_log.snapshot()
    finally:
        for n, v in kept.items():
            jax.config.update(n, v)
        cc.reset_cache()

    def answers(a, b):
        return {k: b["cache"][k] - a["cache"][k] for k in b["cache"]}

    assert answers(before, first) == {"request": 1, "hit": 0, "miss": 1,
                                      "disabled": 0}
    assert answers(first, second) == {"request": 1, "hit": 1, "miss": 0,
                                      "disabled": 0}
    assert (second["cache_seconds"]["retrieval"]
            > first["cache_seconds"]["retrieval"])
    # the load is a backend span like the compile was
    assert (second["spans"]["backend"] - first["spans"]["backend"]) == 1


def test_the_cache_events_by_hand():
    """What jax tells of the cache, told by hand: whatever a backend chooses
    to store, each answer lands in its series."""
    compile_log.install()
    before, own = series(), compile_log.snapshot()
    for event in ("compile_requests_use_cache", "cache_hits", "cache_misses",
                  "task_disabled_cache", "tasks_using_cache"):
        jax.monitoring.record_event("/jax/compilation_cache/" + event)
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", 1.5)
    jax.monitoring.record_event_duration_secs(  # whole seconds less a load
        "/jax/compilation_cache/compile_time_saved_sec", -0.2)
    assert rise(before, series()) == {
        ("deeprec_compile_cache", (("outcome", o),)): 1
        for o in ("request", "hit", "miss", "disabled")} | {
        ("deeprec_compile_cache_seconds", (("kind", "retrieval"),)): 0.25,
        ("deeprec_compile_cache_seconds", (("kind", "saved"),)): 1.5}
    now = compile_log.snapshot()
    assert {k: now["cache"][k] - own["cache"][k] for k in now["cache"]} == {
        "request": 1, "hit": 1, "miss": 1, "disabled": 1}


def test_the_cache_directory_is_measured_once(tmp_path):
    (tmp_path / "a").write_bytes(b"x" * 300)
    (tmp_path / "b").write_bytes(b"y" * 724)
    compile_log.note_cache_dir(str(tmp_path), -1)
    got = series(("deeprec_compile_cache_dir_bytes",
                  "deeprec_compile_cache_cap_bytes"))
    assert got == {("deeprec_compile_cache_dir_bytes", ()): 1024.0,
                   ("deeprec_compile_cache_cap_bytes", ()): -1.0}
    compile_log.note_cache_dir(str(tmp_path / "none"), 128 << 20)
    got = series(("deeprec_compile_cache_dir_bytes",
                  "deeprec_compile_cache_cap_bytes"))
    assert got[("deeprec_compile_cache_dir_bytes", ())] == 0.0
    assert got[("deeprec_compile_cache_cap_bytes", ())] == float(128 << 20)


# ---------------------------------------------------------- the kernels'


def test_a_kernel_body_is_booked_when_it_is_traced_and_only_then():
    from deeprec_tpu.ops import fused_lookup

    values = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128)
    ix = jnp.asarray([3, 9, 60, 0, 3, 17, 5, 41], jnp.int32)
    key = ("deeprec_pallas_traces",
           (("kernel", scopes.KERNEL_GATHER_ROWS),))
    mark, before = next_id(), series()
    first = fused_lookup.gather_rows(values, ix, interpret=True)
    grown = rise(before, series())
    assert grown[key] == 1
    assert grown[("deeprec_pallas_trace_seconds", key[1])] > 0
    (span,) = [s for s in spans_after(mark)
               if s.stage == compile_log.KERNEL_TRACE]
    assert span.program == scopes.KERNEL_GATHER_ROWS
    # the traces jax makes inside the bind (`wrapped`, the body's jitted
    # `jnp` calls) are the body's: counted under their names, no seconds
    inside = [s for s in spans_after(mark) if s.parent == span.id]
    assert "wrapped" in {s.program for s in inside}
    assert all(s.stage == compile_log.TRACE and s.self_s == 0.0
               for s in inside)
    assert span.self_s == pytest.approx(
        grown[("deeprec_pallas_trace_seconds", key[1])])
    assert span.self_s > 0.5 * (span.end - span.start)
    # the program that was being traced leaves the kernel's part out
    encloser = [s for s in spans_after(mark) if s.id == span.parent]
    assert encloser and encloser[0].stage == compile_log.TRACE
    assert encloser[0].self_s <= (encloser[0].end - encloser[0].start
                                  - span.self_s + 1e-6)
    before = series()
    again = fused_lookup.gather_rows(values, ix, interpret=True)
    assert key not in rise(before, series())
    assert (first == values[ix]).all() and (again == first).all()


# ------------------------------------------------------ the trainer's part


def model():
    return DLRMDCN(emb_dim=8, capacity=1 << 10, bottom=(16, 8), top=(16, 1),
                   num_cat=26, num_dense=13, cross_depth=1)


def batches(n):
    gen = SyntheticCriteo(batch_size=64, num_cat=26, num_dense=13, vocab=300,
                          seed=3)
    return [{k: jnp.asarray(v) for k, v in gen.batch().items()}
            for _ in range(n)]


def test_a_trainer_books_its_set_up_and_warm_steps_book_nothing():
    import tracemalloc

    before = series()
    tr = Trainer(model(), Adagrad(lr=0.1), optax.adam(1e-3),
                 unique_budget=48)
    state = tr.init(0)
    bs = batches(4)
    state, mets = tr.train_step(state, bs[0])
    jax.block_until_ready(mets["loss"])
    grown = rise(before, series())
    for stage in ("trainer_build", "init_state"):
        assert grown[("deeprec_setup_seconds", (("stage", stage),))] > 0
    step = (("program", "_step_impl"), ("stage", "backend"))
    assert grown[("deeprec_compile_spans", step)] == 1
    # what `GET /metrics` renders holds them, the import's seconds too
    text = default_registry().render_prometheus()
    for line in ('deeprec_setup_seconds_total{stage="import"}',
                 'deeprec_setup_seconds_total{stage="trainer_build"}',
                 'deeprec_compile_seconds_total{program="_step_impl",'
                 'stage="trace"}',
                 'deeprec_compile_cache_total{outcome="miss"}'):
        assert line in text, line

    warm, mark = series(), next_id()
    own = compile_log.snapshot()
    tracemalloc.start()
    try:
        snap0 = tracemalloc.take_snapshot()
        for b in bs[1:]:
            state, mets = tr.train_step(state, b)
        jax.block_until_ready(mets["loss"])
        snap1 = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    assert series() == warm and not spans_after(mark)
    assert compile_log.snapshot() == own
    grown = [st for st in snap1.compare_to(snap0, "filename")
             if st.traceback[0].filename in (compile_log.__file__,
                                             scopes.__file__)]
    assert sum(st.size_diff for st in grown) < 4096, grown


def test_trace_guard_counts_through_the_recorder(monkeypatch):
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.arange(4.0)
    with trace_guard(max_compiles=None) as g:
        f(x).block_until_ready()
    assert g.compiles == 1 and g.traces >= 1   # `multiply`, `add` inside
    with trace_guard(max_compiles=0) as g:
        f(x).block_until_ready()
    assert (g.compiles, g.traces) == (0, 0)
    with pytest.raises(TraceGuardViolation) as err:
        with trace_guard(max_compiles=0):
            jax.jit(lambda x: x - 5.0)(x).block_until_ready()
    assert err.value.compiles == 1
    # the recorder's own counts do not wait for the metrics plane
    monkeypatch.setattr(metrics, "_ENABLED", False)
    before = series()
    with trace_guard(max_compiles=None) as g:
        jax.jit(lambda x: x / 7.0)(x).block_until_ready()
    assert g.compiles == 1
    monkeypatch.setattr(metrics, "_ENABLED", True)
    assert series() == before


# ------------------------------------------------- the benchmark's readers


@pytest.fixture
def own_registry(monkeypatch):
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "_DEFAULT", reg)
    return reg


FILLED = {
    "setup_import_s": 7.5, "setup_build_s": 1.25 + 0.5,
    "setup_trace_s": 2.0 + 3.0, "setup_kernel_trace_s": 0.75 + 0.25,
    "setup_kernel_traces": 25 + 4, "setup_lower_s": 1.5,
    "setup_compile_or_load_s": 4.0 + 0.5, "setup_programs": 11 + 1,
    "setup_cache_misses": 0.0,
}


def fill(reg):
    def add(name, value, **labels):
        reg.counter(name, "", labels).inc(value)

    add("deeprec_setup_seconds", 7.5, stage="import")
    add("deeprec_setup_seconds", 1.25, stage="trainer_build")
    add("deeprec_setup_seconds", 0.5, stage="init_state")
    for program, t, lower, b, n in (("step", 2.0, 1.0, 4.0, 11),
                                    ("init", 3.0, 0.5, 0.5, 1)):
        add("deeprec_compile_seconds", t, stage="trace", program=program)
        add("deeprec_compile_seconds", lower, stage="lower", program=program)
        add("deeprec_compile_seconds", b, stage="backend", program=program)
        add("deeprec_compile_spans", n, stage="backend", program=program)
        add("deeprec_compile_spans", 40, stage="trace", program=program)
    add("deeprec_pallas_trace_seconds", 0.75, kernel="gather_rows")
    add("deeprec_pallas_trace_seconds", 0.25, kernel="apply_rows_sr")
    add("deeprec_pallas_traces", 25, kernel="gather_rows")
    add("deeprec_pallas_traces", 4, kernel="apply_rows_sr")
    add("deeprec_compile_cache", 12, outcome="hit")
    add("deeprec_compile_cache", 0, outcome="miss")


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_a_setup_reader_reads_the_programs_registry(name, own_registry,
                                                    monkeypatch):
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    assert (reader.LAYER, reader.MOVES) == ("trainer / step builder",
                                            "setup_s")
    assert reader.read({}) is None          # an empty registry: no reading
    fill(own_registry)
    assert reader.read({}) == pytest.approx(FILLED[name])
    assert isinstance(reader.read({}), float)
    monkeypatch.setattr(metrics, "_ENABLED", False)   # DEEPREC_OBS=off
    assert reader.read({}) is None
