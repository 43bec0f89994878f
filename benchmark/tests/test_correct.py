"""`correct` comes out false when it should: the control (the reference in
float8 in the program's place) at a size a test can hold, and a whole run of
the harness with the timed path broken underneath, once for each fault a
one-chip training cell can have."""
import os
import time

import pytest

from benchmark import correct, harness
from benchmark.builders import dlrm as builder
from benchmark.reference import dlrm as reference

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
SEEDS = (1, 2, 3_000_000_019)


def cell_files(cell_name):
    cell = harness.load_cell(cell_name, DATA, DATA)
    return (cell.config, cell.mix, cell.generator,
            correct.load_limits(cell_name, DATA))


def test_a_leaf_gap_is_the_gap_of_norms_against_leaf_or_median():
    ref = {"a": 1.0, "b": 0.01, "c": 4.0}
    prog = {"a": 1.1, "b": 0.06, "c": 4.0}
    gaps = correct.leaf_gaps(prog, ref, list(ref))
    assert gaps == pytest.approx({"a": 0.1, "b": 0.05, "c": 0.0})  # b / 1.0
    assert correct.leaf_gaps({"a": float("nan")}, ref, ["a"]) == {
        "a": float("inf")}
    assert correct.leaf_gaps({}, ref, ["c"]) == {"c": float("inf")}


def test_a_leaf_with_no_gradient_is_left_out_of_the_change():
    ref = {"loss": [1.0] * 3, "grad": {"a": 1.0, "b": 1.0, "dead": 1e-5},
           "change": {"a": 1.0, "b": 1.0, "dead": 1.0}}
    prog = {"loss": [1.0] * 3, "grad": dict(ref["grad"]),
            "change": {"a": 1.0, "b": 1.0, "dead": 3.0}}
    numbers = correct.compare(prog, ref)
    assert numbers["change_gap"]["value"] == 0.0
    prog["change"]["b"] = 2.0
    numbers = correct.compare(prog, ref)
    assert numbers["change_gap"] == {"value": 1.0, "leaf": "b"}
    assert numbers["change_median_gap"]["value"] == 0.5   # gaps 0 and 1


def test_a_leaf_of_one_element_is_held_on_its_gradient_not_its_change():
    """The last layer's bias: its gradient is a cancelling sum, above the
    floor but small, and under Adam its change is about the learning rate
    whichever way the rounding falls (seeds 3000000205 and 3000002751 read
    a change gap of 0.56 and 0.63 on it, parent and change alike)."""
    ref = {"loss": [1.0] * 3,
           "grad": {"w": 1.0, "v": 1.0, "bias": 4e-3},
           "change": {"w": 1.0, "v": 1.0, "bias": 1e-3},
           "size": {"w": 64, "v": 64, "bias": 1}}
    prog = {"loss": [1.0] * 3, "grad": dict(ref["grad"], bias=4.1e-3),
            "change": dict(ref["change"], bias=1.7e-3)}
    numbers = correct.compare(prog, ref)
    assert numbers["change_gap"] == {"value": 0.0, "leaf": "w"}
    assert numbers["change_median_gap"]["value"] == 0.0
    # its gradient is compared, against the median leaf's as any small leaf's
    assert numbers["grad_gap"] == {"value": pytest.approx(1e-4),
                                   "leaf": "bias"}
    # the same readings on a leaf of two elements are a change gap
    ref["size"]["bias"] = 2
    numbers = correct.compare(prog, ref)
    assert numbers["change_gap"] == {"value": pytest.approx(7e-4),
                                     "leaf": "bias"}
    # a state left unchanged still reads 1 on every leaf of more elements
    ref["size"]["bias"] = 1
    still = dict(prog, change={k: 0.0 for k in ref["change"]})
    numbers = correct.compare(still, ref)
    assert numbers["change_gap"]["value"] == 1.0
    assert numbers["change_median_gap"]["value"] == 1.0
    # and a reference that gives no sizes leaves no leaf out
    del ref["size"]
    assert correct.compare(prog, ref)["change_gap"]["leaf"] == "bias"


def test_the_reference_gives_each_leafs_size():
    config, mix, generator, _ = cell_files("tiny-dlrm.zipf")
    ref = reference.run(config, [generator.make_batch(mix, 1, 0)], 1)
    assert set(ref["size"]) == set(ref["grad"]) == set(ref["change"])
    last = max(k for k in ref["size"] if k.startswith("top."))
    assert ref["size"][last.rsplit(".", 1)[0] + ".b"] == 1
    assert ref["size"]["table.C1"] == mix["batch"] * config["emb_dim"]


def test_only_numbers_with_a_limit_are_compared():
    numbers = {n: {"value": 0.5, "leaf": ""} for n in correct.NUMBERS}
    ok, table = correct.verdict(numbers, {"loss1_gap": 1.0, "grad_gap": 0.1})
    assert not ok and list(table) == ["loss1_gap", "grad_gap"]
    assert correct.verdict(numbers, {"loss1_gap": 1.0})[0]


@pytest.fixture(scope="module")
def mid_readings():
    """Reference, control, second witness and fault at the mid size."""
    config, mix, generator, limits = cell_files("mid-dlrm.zipf")
    out = {}
    for seed in SEEDS:
        pseed = harness.program_seed(seed)
        batches = [generator.make_batch(mix, seed, k)
                   for k in range(harness.CHECK_STEPS)]
        ref = reference.run(config, batches, pseed)
        out[seed] = {
            "fp8": correct.verdict(correct.compare(reference.run(
                config, batches, pseed, mode="fp8"), ref), limits),
            "bf16": correct.verdict(correct.compare(reference.run(
                config, batches, pseed, mode="bf16"), ref), limits),
            "half": correct.verdict(correct.compare(reference.run(
                config, batches, pseed, half_batch=True), ref), limits),
        }
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_float8_control_is_not_correct(mid_readings, seed):
    ok, table = mid_readings[seed]["fp8"]
    assert not ok, table


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_in_the_stated_precision_is_correct(mid_readings, seed):
    ok, table = mid_readings[seed]["bf16"]
    assert ok, table


@pytest.mark.parametrize("seed", SEEDS)
def test_half_a_batch_in_the_reference_is_not_correct(mid_readings, seed):
    ok, table = mid_readings[seed]["half"]
    assert not ok, table


class StateUnchanged(builder.Program):
    """A step that returns its state unchanged (the loss is the true one)."""

    def step(self, state, batch):
        import jax
        import jax.numpy as jnp

        keep = jax.tree.map(jnp.copy, state)
        _, loss = super().step(state, batch)
        return keep, loss


class HalfBatch(builder.Program):
    """Half of the batch left out, the mean taken over the rest."""

    def step(self, state, batch):
        return super().step(state, {k: v[:v.shape[0] // 2]
                                    for k, v in batch.items()})


def drive(monkeypatch, tmp_path, program_class):
    monkeypatch.setattr(builder, "Program", program_class)
    return harness.run_cell("tiny-dlrm.zipf", 2 ** 31 + 11, 0.5, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            root=DATA, data=DATA,
                            trace_dir=str(tmp_path / "trace"))


@pytest.mark.parametrize("fault, number", [
    (StateUnchanged, "change_gap"), (HalfBatch, "grad_gap")])
def test_a_run_over_a_broken_timed_path_is_not_correct(monkeypatch, tmp_path,
                                                       fault, number):
    result = drive(monkeypatch, tmp_path, fault)
    assert result["correct"] is False
    row = result["compared"][number]
    assert row["value"] > row["limit"], result["compared"]


def test_the_same_run_over_the_sound_path_is_correct(monkeypatch, tmp_path):
    result = drive(monkeypatch, tmp_path, builder.Program)
    assert result["correct"] is True, result["compared"]
