"""The CPU rehearsal: the harness's functions run end to end at a tiny
configuration, the result holds exactly the contract's keys, the generators
make the batches they made, the command itself refuses a CPU, and a second
model family that exists only as test data runs through the same harness
with no file of the benchmark naming it."""
import hashlib
import json
import lzma
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import harness, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
# a family that is test data: a directory of DATA with a manifest of its own
FAMILIES = sorted(d for d in os.listdir(DATA)
                  if os.path.exists(os.path.join(DATA, d, "BENCHMARK.json")))
# off a chip the only peaks are what a directory of test data hands in: a
# reader that finds nothing to read leaves its metric out rather than print 0
NEED_PEAKS = {"engine_roofline", "dense_roofline", "step_mfu"}


def manifest_of(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def run(cell, trace, tmp_path, seed=2 ** 31 + 11, root=DATA):
    return harness.run_cell(cell, seed, 1.0, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            root=root, data=root,
                            trace_dir=str(tmp_path / "trace"))


def check_untraced_line(line, manifest):
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "occupancy", "compared"]
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["fill_gap"] == {"value": 0.0, "limit": 0.0}
    assert line["attempted"] > 3 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in
                                    manifest["end_to_end"]}
    for m in manifest["end_to_end"]:
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
    assert line["metrics"]["train_examples_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}


@pytest.mark.parametrize("cell", ["tiny-dlrm.zipf", "tiny-dcn.zipf"])
def test_untraced_run_end_to_end(cell, tmp_path):
    line = json.loads(json.dumps(run(cell, False, tmp_path)))
    check_untraced_line(line, manifest_of(DATA))
    # the window met tables holding the vocabulary: 26 fields x 1024 ids
    occ = line["occupancy"]
    assert occ["window_start_rows"] == occ["window_end_rows"] == occ[
        "filled_rows_wanted"] == 26 * 1024 == occ["capacity_rows"] // 2


def read_recorded_trace(monkeypatch, recorded):
    """The CPU's trace holds no TPU plane, and a run that finds none is
    refused: both reductions read a trace recorded on the chip instead,
    once the run has written its own."""
    find = trace_reduce.find_xplane

    def recorded_file(trace_dir):
        assert os.path.exists(find(trace_dir))
        return recorded

    monkeypatch.setattr(trace_reduce, "find_xplane", recorded_file)


def unpacked(xz, tmp_path):
    path = tmp_path / os.path.basename(xz)[:-3]
    with lzma.open(xz) as src:
        path.write_bytes(src.read())
    return str(path)


def check_traced_line(line, manifest, missing):
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "occupancy", "compared"]
    assert line["correct"] is True, line["compared"]
    names = {m["name"]: m for m in manifest["per_layer"]}
    assert set(names) - set(line["metrics"]) == missing
    assert set(line["metrics"]) <= set(names)
    for name, m in line["metrics"].items():
        assert m["unit"] == names[name]["unit"]
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert 0 <= line["metrics"]["device_idle_share"]["value"] <= 1
    assert 0 < line["metrics"]["unique_fraction"]["value"] <= 1
    assert line["metrics"]["steady_compiles"]["value"] == 0
    for key in ("device_ops", "idle_gaps"):
        assert len(line["breakdown"][key]) <= 10


# the trace PR 25 recorded is of a program that wrote its phases and nothing
# under them: the readers of stages, row funnels, loops and spans are silent
UNSPOKEN = {"route_device_ms_per_step", "probe_device_ms_per_step",
            "probe_passes_per_step", "insert_device_ms_per_step",
            "gather_device_ms_per_step", "row_wrapper_device_ms_per_step",
            "row_calls_device_ms_per_step", "train_step_host_ms_per_step"}


@pytest.mark.parametrize("cell, recorded, missing", [
    ("tiny-dlrm.zipf", "recorded.xplane.pb", NEED_PEAKS | UNSPOKEN),
    ("tiny-dcn.zipf", "phases.xplane.pb.xz", NEED_PEAKS)])
def test_traced_run_reports_the_per_layer_metrics(cell, recorded, missing,
                                                  tmp_path, monkeypatch):
    with pytest.raises(RuntimeError, match="no `XLA Ops` line"):
        trace_reduce.reduce_events({}, {}, [], 1)
    path = os.path.join(DATA, recorded)
    if path.endswith(".xz"):
        path = unpacked(path, tmp_path)
    read_recorded_trace(monkeypatch, path)
    line = json.loads(json.dumps(run(cell, True, tmp_path)))
    # the names come from the manifest the run was made under
    check_traced_line(line, manifest_of(DATA), missing)


def test_the_command_refuses_a_cpu():
    manifest = manifest_of(ROOT)
    cmd = manifest["command"] + ["--workload", manifest["workloads"][0]["name"],
                                 "--seed", "3000000019", "--seconds", "1",
                                 "--trace", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "platform 'cpu'" in done.stderr


# ------------------------------------------------------------ the generators


def batch_hash(batch):
    h = hashlib.sha256()
    for k in sorted(batch):
        a = np.ascontiguousarray(batch[k])
        h.update(f"{k}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_the_accepted_mixes_make_the_bytes_they_made():
    """Batch k of seed s of both accepted mixes, hashed on the parent of
    the PR that turned `traffic.py` into the first generator, and pinned."""
    with open(os.path.join(DATA, "batches.expected.json")) as f:
        want = json.load(f)["batches"]
    cells = manifest_of(ROOT)["workloads"]
    assert {c["traffic"] for c in cells} >= set(want)
    for name, by_seed in want.items():
        mix, generator = harness.load_mix(name)
        for seed, rec in by_seed.items():
            got = {"make_batch": {k: batch_hash(generator.make_batch(
                       mix, int(seed), int(k))) for k in rec["make_batch"]},
                   "fill_batch": {j: batch_hash(generator.fill_batch(
                       mix, int(seed), int(j))) for j in rec["fill_batch"]}}
            assert got == rec, (name, seed)


def test_traffic_is_a_pure_function_of_seed_and_batch_index():
    mix, generator = harness.load_mix("tiny-zipf", DATA)
    a = generator.make_batch(mix, 3_000_000_019, 7)
    b = generator.make_batch(mix, 3_000_000_019, 7)
    c = generator.make_batch(mix, 3_000_000_019, 8)
    assert all((a[k] == b[k]).all() for k in a)
    assert any((a[k] != c[k]).any() for k in a)
    assert a["C3"].dtype.name == "int32" and a["I1"].shape == (64, 1)
    lo, hi = 2 * mix["vocab"], 3 * mix["vocab"]
    assert ((a["C3"] >= lo) & (a["C3"] < hi)).all()


def test_every_seed_draws_its_own_ids_under_the_same_law():
    mix, generator = harness.load_mix("mid-zipf", DATA)
    ids = {seed: np.stack([generator.draw_ids(mix, seed, k)
                           for k in range(8)])
           for seed in (1, 2, 3_000_000_019)}
    assert (ids[1] != ids[2]).any() and (ids[2] != ids[3_000_000_019]).any()
    for drawn in ids.values():
        assert drawn.min() >= 0 and drawn.max() < mix["vocab"]
        uniq = np.mean([len(np.unique(row)) for b in drawn for row in b])
        assert 0.18 < uniq / mix["batch"] < 0.30   # zipf 1.2 over 4096 ids
    batch = generator.make_batch(mix, 2, 5)
    assert (batch["C1"] == ids[2][5, 0]).all()


def test_fill_batches_hold_the_vocabulary_within_the_budget():
    mix, generator = harness.load_mix("tiny-zipf-u48", DATA)
    n = generator.fill_steps(mix)
    assert n == -(-mix["vocab"] // mix["unique_budget"]) == 22
    assert generator.fill_steps(harness.load_mix("tiny-zipf", DATA)[0]) == 16
    assert generator.filled_rows(mix) == 26 * 1024
    assert generator.examples(mix) == mix["batch"]
    seen = [set() for _ in range(mix["num_cat"])]
    for j in range(n):
        batch = generator.fill_batch(mix, 7, j)
        assert batch["I1"].shape == (mix["batch"], 1)
        for c, ids in enumerate(seen):
            col = batch[f"C{c + 1}"]
            assert len(np.unique(col)) <= mix["unique_budget"]
            ids.update(col.tolist())
    for c, ids in enumerate(seen):
        assert ids == set(range(c * mix["vocab"], (c + 1) * mix["vocab"]))


def test_a_mix_is_checked_by_the_generator_it_names(tmp_path):
    (tmp_path / "traffic").mkdir()
    good = dict(harness.load_mix("tiny-zipf", DATA)[0])
    del good["name"]
    for name, change, error in [
            ("nameless", {"generator": None}, "names no generator"),
            ("lacking", {"vocab": None}, r"lacks \['vocab'\]"),
            ("lawless", {"id_law": "normal"}, "unknown id_law"),
            ("overdrawn", {"unique_budget": 65}, "unique_budget of 65")]:
        mix = {k: v for k, v in {**good, **change}.items() if v is not None}
        (tmp_path / "traffic" / f"{name}.json").write_text(json.dumps(mix))
        with pytest.raises(ValueError, match=error):
            harness.load_mix(name, str(tmp_path))
    # a generator the benchmark does not hold is looked for beside the mix
    (tmp_path / "traffic" / "foreign.json").write_text(
        json.dumps(dict(good, generator="elsewhere")))
    with pytest.raises(ModuleNotFoundError):
        harness.load_mix("foreign", str(tmp_path))


def test_the_yardstick_imports_nothing_of_the_program():
    code = ("import sys; sys.modules['deeprec_tpu'] = None; "
            "import benchmark.reference.dlrm, benchmark.correct, "
            "benchmark.generators.criteo, benchmark.work.dlrm, "
            "benchmark.trace_reduce, benchmark.phase_reduce, "
            "benchmark.harness")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr


# ---------------------- a second family that exists only as test data


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    root = os.path.join(DATA, request.param)
    manifest = manifest_of(root)
    (cell,) = manifest["workloads"]
    return request.param, root, manifest, harness.load_cell(
        cell["name"], root, root)


def test_a_family_that_is_test_data_brings_every_module_itself(family):
    name, root, manifest, cell = family
    assert FAMILIES, "no family among the test data"
    for module in (cell.builder, cell.reference, cell.work, cell.generator):
        assert module.__file__.startswith(root + os.sep), module
    own = [m["name"] for m in manifest["per_layer"] if os.path.exists(
        os.path.join(root, "layer_metrics", m["name"] + ".py"))]
    assert own, "the family has no per-layer metric of its own"
    for metric in own:
        reader = harness.load_layer_metric(metric, root)
        assert reader.__file__.startswith(root + os.sep)
        (scope,) = [v for k, v in reader.READS.items() if k == "scope"]
        # the scope is in a vocabulary file of the family's own, and in none
        # of the benchmark's
        from benchmark import phase_reduce

        groups = phase_reduce.groups_of(phase_reduce.load_vocabulary(root))
        assert any(scope in g.names for g in groups)
        assert not any(scope in g.names for g in phase_reduce.groups_of(
            phase_reduce.load_vocabulary()))
        assert reader.LAYER in [r["layer"] for r in
                                trace_reduce.load_rules(root)]
        assert reader.LAYER not in [r["layer"] for r in
                                    trace_reduce.load_rules()]


def test_no_file_outside_its_directory_names_the_family(family):
    name, root, _, _ = family
    tracked = subprocess.run(["git", "ls-files", "-co", "--exclude-standard"],
                             cwd=ROOT, capture_output=True, text=True,
                             timeout=60)
    files = tracked.stdout.split() if tracked.returncode == 0 else [
        os.path.relpath(os.path.join(d, f), ROOT)
        for d, _, fs in os.walk(BENCH) for f in fs]
    inside = os.path.relpath(root, ROOT) + os.sep
    # the issue and the notes of the PR that added it may speak of it
    prose = {"ISSUE.md", "REVIEW.md", "CHANGES.md", "PERF.md",
             "PERF_LEDGER.jsonl"}
    hits = []
    for rel in files:
        if rel.startswith(inside) or rel in prose or not os.path.isfile(
                os.path.join(ROOT, rel)):
            continue
        with open(os.path.join(ROOT, rel), "rb") as f:
            if name.encode() in f.read() or name in rel:
                hits.append(rel)
    assert not hits, hits


def test_the_familys_cell_runs_untraced(family, tmp_path):
    name, root, manifest, cell = family
    seed = 2 ** 31 + 11
    line = json.loads(json.dumps(run(cell.cell["name"], False, tmp_path,
                                     seed, root)))
    check_untraced_line(line, manifest)
    occ = line["occupancy"]
    assert occ["window_start_rows"] == occ["filled_rows_wanted"] \
        == cell.generator.filled_rows(cell.mix)
    # its generator's batches: what it calls an example is what is counted
    batch = cell.generator.make_batch(cell.mix, seed, 3)
    again = cell.generator.make_batch(cell.mix, seed, 3)
    assert all((batch[k] == again[k]).all() for k in batch)
    per_step = cell.generator.examples(cell.mix)
    assert per_step == batch["label"][..., 0].size
    rate = line["metrics"]["train_examples_per_s"]["value"]
    assert rate * 1.0 == pytest.approx(line["attempted"] * per_step, rel=0.2)


def test_the_familys_faults_are_not_correct(family):
    _, _, _, cell = family
    from benchmark import correct

    limits = correct.load_limits(cell.cell["name"],
                                 os.path.dirname(cell.work.__file__) + "/..")
    for seed in (1, 3_000_000_019):
        batches = [cell.generator.make_batch(cell.mix, seed, k)
                   for k in range(harness.CHECK_STEPS)]
        pseed = harness.program_seed(seed)
        ref = cell.reference.run(cell.config, batches, pseed)
        assert correct.verdict(correct.compare(ref, ref), limits)[0]
        for kind, kw in cell.reference.CONTROLS.items():
            other = cell.reference.run(cell.config, batches, pseed, **kw)
            ok, table = correct.verdict(correct.compare(other, ref), limits)
            assert not ok, (kind, table)


def test_the_familys_cell_runs_traced(family, tmp_path, monkeypatch):
    """Traced, the reductions reading the trace of the family's own program
    recorded on the chip (tools/record_phase_trace.py --cell): its metric
    reads its scope through the same code as any metric of the benchmark,
    and `step_mfu` is its own work count x the rate over the peak handed
    in."""
    name, root, manifest, cell = family
    recorded = os.path.join(root, "recorded.xplane.pb.xz")
    read_recorded_trace(monkeypatch, unpacked(recorded, tmp_path))
    line = json.loads(json.dumps(run(cell.cell["name"], True, tmp_path,
                                     root=root)))
    check_traced_line(line, manifest, set())
    own = [m["name"] for m in manifest["per_layer"] if os.path.exists(
        os.path.join(root, "layer_metrics", m["name"] + ".py"))]
    with open(os.path.join(root, "recorded.expected.json")) as f:
        pinned = json.load(f)
    for metric in own:
        scope = harness.load_layer_metric(metric, root).READS["scope"]
        # the recording holds three steps, whatever the run counted
        steps = min(line["attempted"], harness.TRACE_MAX_STEPS)
        assert line["metrics"][metric]["value"] == pytest.approx(
            pinned["by_scope_s"][scope] * 1e3 / steps)
    assert 0 < line["metrics"]["step_mfu"]["value"] < 100
    # the share of the peak, to the digit: the reader on a window of known
    # length, with the peaks the family's directory hands in
    peaks = harness.load_peaks(line["device"]["kind"], root, required=False)
    flops = cell.work.flops_per_example(cell.config, cell.mix)
    per_step = cell.generator.examples(cell.mix)
    ctx = {"traced_steps": 10, "traced_window_s": 2.0, "chips": 1,
           "examples_per_step": per_step, "peaks": peaks, "work": cell.work,
           "config": cell.config, "mix": cell.mix}
    assert flops > 0 and harness.load_layer_metric("step_mfu").read(ctx) \
        == pytest.approx(100.0 * flops * (10 * per_step / 2.0)
                         / peaks["flops_per_s"])
    # a family whose work module lacks a count leaves that metric out
    assert not hasattr(cell.work, "engine_bytes_per_unique")
    assert harness.load_layer_metric("engine_roofline").read(dict(
        ctx, trace={"by_layer_s": {"row kernels": 1.0}}, steps=10,
        counter_names=("dedup_unique",),
        counters=np.asarray([[0], [5]]))) is None
