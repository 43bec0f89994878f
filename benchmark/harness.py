"""The harness: one run of one cell.

`run_cell` loads the cell's configuration and traffic mix by the names in
BENCHMARK.json, builds the program through the configuration's builder,
drives the first steps through the timed call while reading what the
comparison needs, fills the tables through the same call, measures the
window, reads the per-layer metrics (traced
run) and then, with the program's state freed, follows the same first steps
in the plain reference and decides `correct`. It has no `if workload == ...`
and knows no model family, no generator and no scope: everything of one
configuration, family, mix, generator, cell or per-layer metric is a file
found by the name that data gives (`load_cell`, `load_module`; PERF.md
section 3 lists the files and the contract of each).
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import queue
import shutil
import sys
import threading
import time
from contextlib import contextmanager
from types import ModuleType
from typing import Callable, Dict, List, NamedTuple, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHECK_STEPS = 3          # the steps the reference follows
QUEUE_DEPTH = 4          # batches the producer keeps ready
TRACE_MAX_STEPS = 128    # a traced run stops its trace after this many steps
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_manifest(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: Dict, name: str) -> Dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in manifest['workloads']]}")


def load_config(manifest: Dict, name: str, root: str = ROOT) -> Dict:
    for cfg in manifest["configs"]:
        if cfg["name"] == name:
            with open(os.path.join(root, cfg["file"])) as f:
                return json.load(f)
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def load_module(kind: str, name: str, data: str = HERE) -> ModuleType:
    """The module `<kind>/<name>.py`: the benchmark's own
    (`benchmark.<kind>.<name>`), or, where a run is made under another
    directory of data files (the tests'), the one that directory holds.
    `kind` is `builders`, `reference`, `work`, `generators` or
    `layer_metrics`."""
    path = os.path.join(data, kind, name + ".py")
    if data == HERE or not os.path.exists(path):
        return importlib.import_module(f"benchmark.{kind}.{name}")
    qualified = f"benchmark_data[{data}].{kind}.{name}"
    if qualified not in sys.modules:
        spec = importlib.util.spec_from_file_location(qualified, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[qualified] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[qualified]
            raise
    return sys.modules[qualified]


def load_mix(name: str, data: str = HERE):
    """(the mix `traffic/<name>.json`, its generator's module), the mix
    checked by the generator it names."""
    with open(os.path.join(data, "traffic", name + ".json")) as f:
        mix = json.load(f)
    mix["name"] = name
    if "generator" not in mix:
        raise ValueError(f"traffic mix {name!r} names no generator")
    generator = load_module("generators", mix["generator"], data)
    generator.check(mix)
    return mix, generator


class Cell(NamedTuple):
    """A workload's data and modules, each found by a name that data gives:
    the manifest under `root`; mixes, limits and any module the benchmark
    itself does not hold under `data`."""
    manifest: Dict
    cell: Dict
    config: Dict
    mix: Dict
    builder: ModuleType      # configuration's `builder`: class Program
    reference: ModuleType    # configuration's `reference`: run, row_init
    work: ModuleType         # configuration's `work`: the family's counts
    generator: ModuleType    # mix's `generator`


def load_cell(workload: str, root: str = ROOT, data: str = HERE) -> Cell:
    manifest = load_manifest(root)
    cell = find_cell(manifest, workload)
    config = load_config(manifest, cell["config"], root)
    mix, generator = load_mix(cell["traffic"], data)
    return Cell(manifest, cell, config, mix,
                load_module("builders", config["builder"], data),
                load_module("reference", config["reference"], data),
                load_module("work", config["work"], data), generator)


def load_peaks(device_kind: str, data: str = HERE,
               required: bool = True) -> Optional[Dict]:
    """The peaks of a device kind from `peaks.json` (the benchmark's, else
    the one under `data`). A device that is in neither is an error, or,
    off a TPU (the tests), no peaks."""
    peaks: Dict = {}
    for base in dict.fromkeys((data, HERE)):
        path = os.path.join(base, "peaks.json")
        if os.path.exists(path):
            with open(path) as f:
                peaks = {**json.load(f), **peaks}
    if device_kind not in peaks:
        if not required:
            return None
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"benchmark/peaks.json (it has {sorted(peaks)})")
    return peaks[device_kind]


def program_seed(seed: int) -> int:
    """The driver's seeds pass 2**31; jax's PRNGKey takes an int32."""
    return int(seed) % (2 ** 31 - 1)


class CompileClock:
    """Seconds jax spent compiling or loading programs, how many, and how
    many the persistent cache served (a copy of chip_smoke.CompileClock)."""

    def __init__(self):
        import jax

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_secs)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_secs(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self):
        return {"compile_s": round(self.seconds, 3),
                "programs": self.programs, "cache_hits": self.cache_hits}


class Producer:
    """One thread that makes batch k = 0, 1, 2, ... from the seed, stages it
    on the device through the program's own staging call and keeps
    QUEUE_DEPTH of them ready. `get()` hands out (host batch, device batch)
    in order."""

    def __init__(self, make_batch: Callable[[int], Dict], put):
        self._make, self._put = make_batch, put
        self._q: "queue.Queue" = queue.Queue(maxsize=QUEUE_DEPTH)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._work, daemon=True,
                                        name="bench-producer")
        self._thread.start()

    def _work(self):
        k = 0
        try:
            while not self._stop.is_set():
                host = self._make(k)
                item = (host, self._put(host))
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.05)
                        break
                    except queue.Full:
                        continue
                k += 1
        except BaseException as e:  # surfaces in get(); re-raised there
            self._q.put(e)
            raise

    def get(self):
        item = self._q.get(timeout=300)
        if isinstance(item, BaseException):
            raise RuntimeError("the batch producer failed") from item
        return item

    def close(self):
        self._stop.set()
        while True:  # unblock a producer waiting on a full queue
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("the batch producer did not stop")


class Spans:
    """Host spans of the window, by name: (start, end) on perf_counter. In a
    traced run each is also a jax TraceAnnotation `bench.<name>`, so that the
    trace reduction can say what the host was doing in a device gap."""

    def __init__(self, annotate: bool):
        self.by_name: Dict[str, List] = {}
        self._annotate = annotate

    @contextmanager
    def span(self, name: str):
        if self._annotate:
            import jax

            with jax.profiler.TraceAnnotation("bench." + name):
                t0 = time.perf_counter()
                yield
                self.by_name.setdefault(name, []).append(
                    (t0, time.perf_counter()))
        else:
            yield

    def total_ms(self, name: str) -> float:
        return 1e3 * sum(b - a for a, b in self.by_name.get(name, ()))


def device_facts(chips: int, require_tpu: bool) -> Dict:
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": chips}
    if require_tpu and facts["platform"] != "tpu":
        raise SystemExit(f"the benchmark measures on a TPU; jax found "
                         f"platform {facts['platform']!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s); jax found "
                         f"{len(devs)}")
    return facts


def peak_bytes(chips: int) -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def load_layer_metric(name: str, data: str = HERE) -> ModuleType:
    return load_module("layer_metrics", name, data)


def check_steps(program, state, next_batch, config: Dict, reference,
                after_first: Optional[Callable[[], None]] = None):
    """Drive the first CHECK_STEPS steps through the timed call, reading
    what the comparison needs of the program's state after the first and
    after the last. `next_batch()` gives (host batch, device batch).
    Returns (state, the program's readings as floats, the host batches)."""
    import jax

    from benchmark import correct

    readings = correct.ProgramReadings(
        program, config, reference.row_init(config, program.fields))
    readings.before_first_step(state)
    host_batches = []
    for i in range(CHECK_STEPS):
        host, dev = next_batch()
        host_batches.append(host)
        state, loss = program.step(state, dev)
        if i == 0 and after_first is not None:
            jax.block_until_ready(loss)
            after_first()
        readings.after_step(state, host, dev, loss)
    readings.after_last_step(state)
    return state, readings.host(), host_batches


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, root: str = ROOT,
             data: str = HERE, trace_dir: Optional[str] = None) -> Dict:
    """One run. Returns the result object (the last stdout line's content)."""
    phases: Dict[str, float] = {}
    t_mark = [t_start]

    def mark(name: str):
        now = time.perf_counter()
        phases[name] = round(now - t_mark[0], 3)
        t_mark[0] = now

    from benchmark import correct

    manifest, cell, config, mix, builder, reference, work, generator = \
        load_cell(workload, root, data)
    limits = correct.load_limits(workload, data)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = device_facts(cell["chips"], require_tpu)
    peaks = load_peaks(device["kind"], data, required=require_tpu)
    clock = CompileClock()
    mark("imports_and_device_s")

    pseed = program_seed(seed)
    program = builder.Program(config, mix)
    state = program.fresh_state(pseed)
    jax.block_until_ready(state)
    mark("tables_and_weights_s")

    n_fill = generator.fill_steps(mix)

    def make_batch(k: int):
        # batches 0..CHECK_STEPS-1 are read for `correct`, the next n_fill
        # insert the vocabulary, the window takes the rest
        j = k - CHECK_STEPS
        if 0 <= j < n_fill:
            return generator.fill_batch(mix, seed, j)
        return generator.make_batch(mix, seed, k)

    producer = Producer(make_batch, program.put)
    spans = Spans(annotate=trace)
    try:
        # ---- the first steps, through the timed call, read for `correct`
        def first_step_done():
            mark("first_step_s")
            phases["first_step_compile_or_load_s"] = round(
                clock.snapshot()["compile_s"] - c0["compile_s"], 3)

        c0 = clock.snapshot()
        state, prog_readings, check_batches = check_steps(
            program, state, producer.get, config, reference, first_step_done)
        mark("check_steps_and_reads_s")

        # ---- the fill, through the timed call: every id of the vocabulary
        loss = prev = None
        for _ in range(n_fill):
            _, dev = producer.get()
            prev, (state, loss) = loss, program.step(state, dev)
            if prev is not None:
                prev.block_until_ready()
        counters0 = program.counters(state)
        occ0 = program.occupied_rows(state)
        jax.block_until_ready(counters0)
        mark("fill_s")
        setup_clock = clock.snapshot()

        # ---- the window
        losses, counters = [], []
        tracing = False
        if trace:
            trace_dir = trace_dir or os.path.join(root, "benchmark_out",
                                                  "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)  # one trace on disk
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        traced_steps, traced_end = 0, None
        while True:
            with spans.span("input_wait"):
                _, dev = producer.get()
            with spans.span("dispatch"):
                state, loss = program.step(state, dev)
                counters.append(program.counters(state))
            losses.append(loss)
            if len(losses) >= 2:
                # at most two steps in flight: the device never waits on the
                # host, and the host never runs far ahead of the device
                with spans.span("sync"):
                    losses[-2].block_until_ready()
            done = time.perf_counter() - t0 >= seconds
            if done or (tracing and len(losses) >= TRACE_MAX_STEPS):
                with spans.span("sync"):
                    losses[-1].block_until_ready()
                t1 = time.perf_counter()
                if tracing:
                    traced_steps, traced_end = len(losses), t1
                    jax.profiler.stop_trace()
                    tracing = False
            if done:
                break
        window_compiles = clock.snapshot()["programs"] - setup_clock["programs"]
    finally:
        producer.close()

    steps = len(losses)
    window_s = t1 - t0
    import numpy as np

    loss_host = np.asarray([float(x) for x in losses])
    cnt = np.stack([np.asarray(c) for c in [counters0] + counters])
    names = program.COUNTERS
    # a step fails where a counter the program lists as a failure rose, or
    # its loss is not finite
    rose = np.zeros(steps, bool)
    for name in program.FAIL_COUNTERS:
        rose |= np.diff(cnt[:, names.index(name)]) > 0
    failed = int(np.sum(rose | ~np.isfinite(loss_host)))
    occ1 = program.occupied_rows(state)
    mem_peak = peak_bytes(cell["chips"])
    examples_per_step = generator.examples(mix)
    examples = steps * examples_per_step
    capacity = program.capacity_rows()
    log(f"window: {steps} steps, {examples} examples in {window_s:.4f} s; "
        f"loss {loss_host[0]:.5f} -> {loss_host[-1]:.5f}; table occupancy "
        f"{occ0}/{capacity} rows at the window's start, "
        f"{occ1} at its end; compiles inside the window: {window_compiles}")

    # ---- free the program before the reference runs
    del state, program, producer, counters, counters0, losses, dev, loss, prev
    gc.collect()

    ctx = {
        "manifest": manifest, "cell": cell, "config": config, "mix": mix,
        "work": work, "data": data, "trace_dir": trace_dir, "peaks": peaks,
        "chips": cell["chips"], "steps": steps,
        "window_s": window_s, "examples": examples,
        "examples_per_step": examples_per_step, "spans": spans,
        "counters": cnt,
        "counter_names": names, "window_compiles": window_compiles,
        "traced_steps": traced_steps,
        "traced_window_s": (traced_end - t0) if traced_end else None,
        "trace": None,
    }
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace:
        from benchmark import trace_reduce

        ctx["trace"] = trace_reduce.reduce_dir(
            trace_dir, ctx["chips"], ctx["traced_window_s"], data)
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        breakdown = ctx["trace"]["breakdown"]
        log("device ms a step by source file: " + json.dumps(
            {k: round(1e3 * v / max(traced_steps, 1), 3) for k, v in
             list(ctx["trace"]["by_file_s"].items())[:14]}))
        for m in manifest["per_layer"]:
            value = load_layer_metric(m["name"], data).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        mark("window_and_trace_reduction_s")
    else:
        ours = {
            "train_examples_per_s": examples / window_s,
            "peak_hbm_gib": (mem_peak or 0) / 2 ** 30,
            "setup_s": setup_s,
        }
        for m in manifest["end_to_end"]:
            metrics[m["name"]] = {"value": float(ours[m["name"]]),
                                  "unit": m["unit"]}
        mark("window_s")

    # ---- the reference, after the peak was read and the state freed
    ref_readings = reference.run(config, check_batches, pseed)
    numbers = correct.compare(prog_readings, ref_readings)
    # exact: the window has to start on tables that hold the vocabulary
    want = generator.filled_rows(mix)
    numbers["fill_gap"] = {"value": abs(occ0 - want) / max(want, 1),
                           "leaf": ""}
    limits["fill_gap"] = 0.0
    occupancy = {"window_start_rows": occ0, "window_end_rows": occ1,
                 "filled_rows_wanted": want, "capacity_rows": capacity}
    ok, table = correct.verdict(numbers, limits)
    mark("reference_and_compare_s")
    clock.close()

    device["memory_peak_bytes"] = mem_peak
    phases.update({"setup_s": round(setup_s, 3),
                   "setup_compile_or_load_s": setup_clock["compile_s"],
                   "setup_programs": setup_clock["programs"],
                   "setup_cache_hits": setup_clock["cache_hits"]})
    log("set-up by phase: " + json.dumps(phases))
    log("read (value, limit or null where not compared, worst leaf): "
        + json.dumps({n: [v["value"], limits.get(n), v["leaf"]]
                      for n, v in numbers.items()}))
    result = {"correct": bool(ok), "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["occupancy"] = occupancy
    result["compared"] = table
    return result
