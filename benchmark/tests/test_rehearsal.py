"""The CPU rehearsal: the harness's functions run end to end at a tiny
configuration, the result holds exactly the contract's keys, the manifest
and the files it names agree, and the command itself refuses a CPU."""
import importlib
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import harness, trace_reduce, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run(cell, trace, tmp_path, seed=2 ** 31 + 11):
    return harness.run_cell(cell, seed, 1.0, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            root=DATA, data=DATA,
                            trace_dir=str(tmp_path / "trace"))


@pytest.mark.parametrize("cell", ["tiny-dlrm.zipf", "tiny-dcn.zipf"])
def test_untraced_run_end_to_end(cell, tmp_path, manifest):
    result = run(cell, False, tmp_path)
    line = json.loads(json.dumps(result))  # what the last line would hold
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "occupancy", "compared"]
    assert line["correct"] is True, line["compared"]
    # the window met tables holding the vocabulary: 26 fields x 1024 ids
    occ = line["occupancy"]
    assert occ["window_start_rows"] == occ["window_end_rows"] == occ[
        "filled_rows_wanted"] == 26 * 1024 == occ["capacity_rows"] // 2
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


def test_traced_run_reports_the_per_layer_metrics(tmp_path, manifest,
                                                  monkeypatch):
    # the CPU's trace holds no TPU plane, and a run that finds none is
    # refused: the rehearsal reads the trace recorded on the chip instead
    def recorded(trace_dir, chips, window_s=None):
        assert os.path.isdir(trace_dir)  # the run did write its own trace
        ops, modules, host = trace_reduce.read_events(
            os.path.join(DATA, "recorded.xplane.pb"))
        return trace_reduce.reduce_events(ops, modules, host, chips,
                                          window_s)

    monkeypatch.setattr(trace_reduce, "reduce_dir", recorded)
    with pytest.raises(RuntimeError, match="no `XLA Ops` line"):
        trace_reduce.reduce_events({}, {}, [], 1)
    line = json.loads(json.dumps(run("tiny-dlrm.zipf", True, tmp_path)))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "occupancy", "compared"]
    names = {m["name"]: m for m in manifest["per_layer"]}
    assert set(line["metrics"]) <= set(names)
    # off a chip there are no peaks: a reader that finds nothing to read
    # leaves its metric out rather than print a 0
    assert set(names) - set(line["metrics"]) == {
        "engine_roofline", "dense_roofline", "step_mfu"}
    for name, m in line["metrics"].items():
        assert m["unit"] == names[name]["unit"]
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert 0 <= line["metrics"]["device_idle_share"]["value"] <= 1
    assert 0 < line["metrics"]["unique_fraction"]["value"] <= 1
    assert line["metrics"]["steady_compiles"]["value"] == 0
    for key in ("device_ops", "idle_gaps"):
        assert len(line["breakdown"][key]) <= 10


def test_the_command_refuses_a_cpu(manifest):
    cmd = manifest["command"] + ["--workload", manifest["workloads"][0]["name"],
                                 "--seed", "3000000019", "--seconds", "1",
                                 "--trace", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "platform 'cpu'" in done.stderr


def test_manifest_names_files_that_are_there(manifest):
    assert manifest["paths"] == ["benchmark"]
    for cfg in manifest["configs"]:
        assert NAME.match(cfg["name"]) and len(cfg["why"]) <= 200
        with open(os.path.join(ROOT, cfg["file"])) as f:
            body = json.load(f)
        assert body["name"] == cfg["name"]
        assert body["reduced"] == cfg["reduced"]
        importlib.import_module(f"benchmark.builders.{body['builder']}")
        importlib.import_module(f"benchmark.reference.{body['reference']}")
    cfg_names = {c["name"] for c in manifest["configs"]}
    for cell in manifest["workloads"]:
        assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
        assert cell["config"] in cfg_names and cell["chips"] == 1
        mix = traffic.load_mix(cell["traffic"])
        cfg = harness.load_config(manifest, cell["config"])
        # the vocabulary of a field is half its table's capacity
        assert mix["vocab"] * 2 == cfg["capacity"]
        assert 0 < mix["unique_budget"] <= mix["batch"]
        assert os.path.exists(os.path.join(BENCH, "limits",
                                           cell["name"] + ".json"))


def test_every_per_layer_metric_has_its_reader(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    assert not any("step_ms" in n for n in e2e)
    for m in manifest["per_layer"]:
        mod = harness.load_layer_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), m["name"]
        assert m["moves"] in e2e and callable(mod.read)
    assert any("mfu" in m["name"].split("_") for m in manifest["per_layer"])


def test_traffic_is_a_pure_function_of_seed_and_batch_index():
    mix = traffic.load_mix("tiny-zipf", DATA)
    a = traffic.make_batch(mix, 3_000_000_019, 7)
    b = traffic.make_batch(mix, 3_000_000_019, 7)
    c = traffic.make_batch(mix, 3_000_000_019, 8)
    assert all((a[k] == b[k]).all() for k in a)
    assert any((a[k] != c[k]).any() for k in a)
    assert a["C3"].dtype.name == "int32" and a["I1"].shape == (64, 1)
    lo, hi = 2 * mix["vocab"], 3 * mix["vocab"]
    assert ((a["C3"] >= lo) & (a["C3"] < hi)).all()


def test_every_seed_draws_its_own_ids_under_the_same_law():
    mix = traffic.load_mix("mid-zipf", DATA)
    ids = {seed: np.stack([traffic.draw_ids(mix, seed, k) for k in range(8)])
           for seed in (1, 2, 3_000_000_019)}
    assert (ids[1] != ids[2]).any() and (ids[2] != ids[3_000_000_019]).any()
    for drawn in ids.values():
        assert drawn.min() >= 0 and drawn.max() < mix["vocab"]
        uniq = np.mean([len(np.unique(row)) for b in drawn for row in b])
        assert 0.18 < uniq / mix["batch"] < 0.30   # zipf 1.2 over 4096 ids
    batch = traffic.make_batch(mix, 2, 5)
    assert (batch["C1"] == ids[2][5, 0]).all()


def test_fill_batches_hold_the_vocabulary_within_the_budget():
    mix = traffic.load_mix("tiny-zipf-u48", DATA)
    n = traffic.fill_steps(mix)
    assert n == -(-mix["vocab"] // mix["unique_budget"]) == 22
    assert traffic.fill_steps(traffic.load_mix("tiny-zipf", DATA)) == 16
    seen = [set() for _ in range(mix["num_cat"])]
    for j in range(n):
        batch = traffic.fill_batch(mix, 7, j)
        assert batch["I1"].shape == (mix["batch"], 1)
        for c, ids in enumerate(seen):
            col = batch[f"C{c + 1}"]
            assert len(np.unique(col)) <= mix["unique_budget"]
            ids.update(col.tolist())
    for c, ids in enumerate(seen):
        assert ids == set(range(c * mix["vocab"], (c + 1) * mix["vocab"]))


def test_the_reference_imports_nothing_of_the_program():
    code = ("import sys; sys.modules['deeprec_tpu'] = None; "
            "import benchmark.reference.dlrm, benchmark.correct, "
            "benchmark.traffic, benchmark.counts, benchmark.trace_reduce")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr
