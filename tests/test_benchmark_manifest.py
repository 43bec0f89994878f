"""The tier-1 guard of the benchmark's manifest: every name BENCHMARK.json
and its data files give resolves to a file that is there and keeps its
contract. No jax, under a second. A copy of benchmark/tests/test_manifest.py
(which runs with the benchmark's own tests, outside tier-1); the two are
kept the same."""
import ast
import json
import os
import re

import pytest

from benchmark import harness, layer_metrics, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(ROOT)


def defined(kind, name, inside=None):
    """The names a module of the benchmark binds at its top level (or in
    its class `inside`), read from its text: builders and references import
    jax, and this file does not."""
    with open(os.path.join(BENCH, kind, name + ".py")) as f:
        body = ast.parse(f.read()).body
    if inside:
        (cls,) = [n for n in body if isinstance(n, ast.ClassDef)
                  and n.name == inside]
        body = cls.body
    names = set()
    for node in body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            names.update(t.id for t in node.targets
                         if isinstance(t, ast.Name))
    return names


def test_every_configuration_names_modules_that_are_there(manifest):
    assert manifest["paths"] == ["benchmark"]
    used = {cell["config"] for cell in manifest["workloads"]}
    for cfg in manifest["configs"]:
        assert NAME.match(cfg["name"]) and len(cfg["why"]) <= 200
        assert cfg["name"] in used
        body = harness.load_config(manifest, cfg["name"], ROOT)
        assert body["name"] == cfg["name"]
        assert body["reduced"] == cfg["reduced"]
        assert {"COUNTERS", "FAIL_COUNTERS", "fresh_state", "put", "step",
                "counters", "occupied_rows", "capacity_rows", "read_rows",
                "dense_params", "dense_first_moment"} <= defined(
                    "builders", body["builder"], inside="Program")
        assert {"run", "row_init", "CONTROLS"} <= defined(
            "reference", body["reference"])
        work = harness.load_module("work", body["work"])
        # the whole step's share of the peak needs the family's count
        assert callable(work.flops_per_example)


def test_every_cell_names_a_mix_a_generator_and_limits(manifest):
    names = {c["name"] for c in manifest["configs"]}
    pairs = set()
    for cell in manifest["workloads"]:
        assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
        assert cell["config"] in names and cell["chips"] in (1, 4)
        pairs.add((cell["config"], cell["traffic"]))
        # load_mix has the mix checked by the generator it names
        mix, generator = harness.load_mix(cell["traffic"])
        for fn in ("check", "make_batch", "fill_steps", "fill_batch",
                   "filled_rows", "examples"):
            assert callable(getattr(generator, fn)), (mix["generator"], fn)
        assert generator.examples(mix) > 0
        assert generator.fill_steps(mix) >= 0
        assert generator.filled_rows(mix) >= 0
        # (correct.load_limits, which holds the names to the numbers that
        # are compared, imports jax; every run goes through it)
        with open(os.path.join(BENCH, "limits", cell["name"] + ".json")) as f:
            limits = json.load(f)["limits"]
        assert limits and all(isinstance(v, float) for v in limits.values())
    assert len(pairs) == len(manifest["workloads"])


def test_every_per_layer_metric_has_its_reader(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    assert not any("step_ms" in n for n in e2e)
    layers = {rule["layer"] for rule in trace_reduce.load_rules()}
    # layers that are no rule's: the harness's own and the chip
    layers |= {"host input", "device"}
    for m in manifest["per_layer"]:
        mod = harness.load_layer_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), m["name"]
        assert m["moves"] in e2e and callable(mod.read)
        assert mod.READS and set(mod.READS) <= set(layer_metrics.KINDS), \
            m["name"]
        assert m["layer"] in layers, m["name"]
        for layer in mod.READS.get("layers", ()):
            assert layer in layers | {trace_reduce.UNATTRIBUTED}, m["name"]
    assert any("mfu" in m["name"].split("_") for m in manifest["per_layer"])


def test_what_a_metric_reads_is_in_the_vocabulary(manifest):
    from benchmark import phase_reduce

    vocab = phase_reduce.load_vocabulary()
    groups = {g.name: g for g in phase_reduce.groups_of(vocab)}
    every = set().union(*(g.names for g in groups.values()))
    listed = {m["name"] for m in manifest["per_layer"]}
    reads = phase_reduce.metric_reads()
    assert set(reads) <= listed
    for metric, what in reads.items():
        (kind, name), = what.items()
        assert name in {
            "phase": groups["phase"].names | {phase_reduce.UNPHASED},
            "stage": groups["stage"].names, "scope": every, "loop": every,
            "kernel": groups["kernel"].names, "rows": {"wrapper", "kernel"},
            "span": set(vocab["host_spans"]) | {vocab["step_span"]},
        }[kind], metric


def test_the_rule_of_the_row_kernels_lists_the_programs_names():
    with open(os.path.join(BENCH, "phases.json")) as f:
        kernels = json.load(f)["kernels"]
    (rule,) = [r for r in trace_reduce.load_rules()
               if r["layer"] == "row kernels"]
    assert rule["kernels"] == kernels and not rule["sources"]
