"""The trace reduction: its arithmetic on hand-made events, and the whole
path from `.xplane.pb` to the per-layer numbers on the small trace recorded
on the chip and kept beside this file (data/recorded.xplane.pb: four steps
of tests/data's tiny-dlrm.zipf on a TPU v5 lite; the numbers in
data/recorded.expected.json were worked out another way: each operation's
parent by a brute-force search, the union on a 1 ns raster)."""
import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_self_time_takes_nested_operations_out():
    # a while of 100 ns holding two body operations of 30 and 50 ns
    events = [(0, 100, "while.1", "a.py:1"), (10, 30, "fusion.1", "b.py:2"),
              (50, 50, "fusion.2", "b.py:3"), (120, 10, "copy.1", "")]
    got = {e[2]: e[4] for e in tr.self_times(events)}
    assert got == {"while.1": 20, "fusion.1": 30, "fusion.2": 50,
                   "copy.1": 10}


def test_union_and_idle_gaps_named_by_the_host_span():
    intervals = [(0, 10), (5, 20), (50, 60), (100, 130)]
    assert tr.union_ns(intervals) == 20 + 10 + 30
    host = [(18, 34, "bench.input_wait"), (58, 45, "bench.sync")]
    gaps = tr.idle_gaps(intervals, host)
    assert [g[0] for g in gaps] == ["bench.sync", "bench.input_wait"]
    assert [g[1] for g in gaps] == pytest.approx([40e-9, 30e-9])


@pytest.mark.parametrize("name, source, layer", [
    # a Pallas call (`kernel` below) is known by the `name=` the program
    # gave its kernel, wherever that stands in the `op_name`
    (("gather_rows.3", ("jit", "step", "phase_lookup", "vmap",
                        "engine_gather", "rows_gather", "gather_rows",
                        "pallas_call")),
     "deeprec_tpu/ops/fused_lookup.py:650", "row kernels"),
    (("tpu_custom_call:closed_call.100", ("vmap", "apply_rows_sr", "while",
                                          "body", "closed_call")), "",
     "row kernels"),
    # one whose kernel no rule lists goes by its source file
    (("tpu_custom_call.7", ("jit", "step", "pallas_call")),
     "deeprec_tpu/ops/fused_lookup.py:650", "embedding engine"),
    (("flash_fwd.2", ("jit", "step", "flash_fwd", "pallas_call")),
     "deeprec_tpu/nn.py:40", "dense model"),
    # and an operation that is no Pallas call never goes by a name
    ("gather_rows.3", "deeprec_tpu/nn.py:40", "dense model"),
    ("dynamic-update-slice.114", "deeprec_tpu/embedding/table.py:503",
     "embedding engine"),
    ("fusion.565", "/x/deeprec_tpu/ops/dedup.py:168", "embedding engine"),
    ("fusion.2", "deeprec_tpu/optim/apply.py:131", "sparse + dense apply"),
    ("fusion.3", "site-packages/optax/_src/transform.py:10",
     "sparse + dense apply"),
    ("convolution.4", "deeprec_tpu/nn.py:40", "dense model"),
    ("fusion.9", "deeprec_tpu/training/trainer.py:520",
     "trainer / step builder"),
    ("reduce_sum.28", "/x/benchmark/builders/dlrm.py:75", "benchmark loop"),
    ("fusion.9", "site-packages/jax/_src/numpy/reductions.py:1", "unattributed"),
    ("copy.1", "", "unattributed"),
])
def test_layer_rules(name, source, layer):
    name, kernel = name if isinstance(name, tuple) else (name, ())
    assert tr.layer_of(name, source, tr.load_rules(), kernel) == layer


def test_the_rules_are_one_file_a_layer_in_the_order_of_their_names(tmp_path):
    rules = tr.load_rules()
    assert [r["layer"] for r in rules] == [
        "row kernels", "embedding engine", "sparse + dense apply",
        "dense model", "trainer / step builder", "benchmark loop"]
    assert all(set(r) - {"note"} == {"layer", "kernels", "sources"}
               for r in rules)
    # a directory of data files beside the benchmark's adds its own
    (tmp_path / "layers").mkdir()
    (tmp_path / "layers" / "45-mixer.json").write_text(json.dumps(
        {"layer": "mixer", "kernels": ["mix_fwd"], "sources": ["mixer.py"]}))
    more = tr.load_rules(str(tmp_path))
    assert [r["layer"] for r in more if r not in rules] == ["mixer"]
    assert [r["layer"] for r in more].index("mixer") == 4
    assert tr.layer_of("c.1", "", more, ("vmap", "mix_fwd")) == "mixer"


def test_reduce_events_adds_up():
    ops = {"/device:TPU:0": [
        (0, 100, "while.1", "deeprec_tpu/embedding/table.py:503"),
        (10, 30, "fusion.1", "deeprec_tpu/embedding/table.py:470"),
        (50, 50, "apply_rows_sr.2", "deeprec_tpu/ops/fused_lookup.py:650",
         ("phase_sparse_apply", "rows_scatter", "apply_rows_sr")),
        (150, 50, "convolution.3", "deeprec_tpu/nn.py:40"),
        (200, 10, "fusion.4", "jax/_src/numpy/reductions.py:1"),
    ]}
    modules = {"/device:TPU:0": [(0, 210, "jit__step_impl(1)")]}
    host = [(90, 70, "bench.sync")]
    red = tr.reduce_events(ops, modules, host, chips=1, window_s=300e-9)
    assert red["busy_s"] == pytest.approx(160e-9)
    assert red["window_s"] == 300e-9
    assert red["by_layer_s"] == pytest.approx({
        "embedding engine": 50e-9, "row kernels": 50e-9,
        "dense model": 50e-9, "unattributed": 10e-9})
    assert sum(red["by_layer_s"].values()) == pytest.approx(red["busy_s"])
    gap = red["breakdown"]["idle_gaps"][0]
    assert gap[0] == "bench.sync" and gap[1] == pytest.approx(50e-9)
    assert len(red["breakdown"]["device_ops"]) <= 10


HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

FileNames
1 "/x/deeprec_tpu/embedding/table.py"
2 "/x/deeprec_tpu/ops/fused_lookup.py"

FunctionNames
1 "probe"

FileLocations
1 {file_name_id=1 function_name_id=1 line=503 column=1}
2 {file_name_id=2 function_name_id=1 line=650 column=1}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}

%fused_computation.1 (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%p0, %p0), metadata={op_name="jit(step)/add" stack_frame_id=1}
}

%region_1.body (arg: (s32[], s32[8])) -> (s32[], s32[8]) {
  %arg = (s32[], s32[8]{0}) parameter(0)
  %gte.1 = s32[8]{0} get-tuple-element(%arg), index=1
  %copy.3 = s32[8]{0:T(1024)S(1)} copy(%gte.1)
  %closed_call.7 = s32[8]{0} custom-call(%copy.3), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step)/pallas_call" stack_frame_id=2}
  ROOT %tuple.1 = (s32[], s32[8]{0}) tuple(%gte.1, %closed_call.7)
}

ENTRY %main () -> f32[] {
  %iota.1 = s32[8]{0} iota(), iota_dimension=0
  %fusion.2 = s32[8]{0} fusion(%iota.1), kind=kLoop, calls=%fused_computation.1
  %sort.4 = (s32[8]{0}, s32[8]{0}) sort(%fusion.2, %iota.1), dimensions={0}, to_apply=%compare
  %while.5 = (s32[], s32[8]{0}) while(%tuple.0), condition=%region_0.cond, body=%region_1.body, metadata={source_file="/x/deeprec_tpu/ops/fused_lookup.py" source_line=417}
  %copy.9 = s32[8]{0} copy(%iota.1)
  %broadcast.6 = s32[8]{0} broadcast(%constant.0), dimensions={}
  %tuple.0 = (s32[], s32[8]{0}) tuple(%constant.0, %broadcast.6)
  %iota.9 = s32[8]{0} iota(), iota_dimension=0
}
"""


def test_an_instruction_of_the_compilers_inherits_its_source():
    instrs = tr.parse_hlo(HLO)
    assert instrs["add.1"]["source"] == "/x/deeprec_tpu/embedding/table.py:503"
    assert instrs["fusion.2"] == {
        "source": "", "target": "", "operands": ["iota.1"],
        "calls": ["fused_computation.1"], "computation": "main",
        "op": "fusion", "op_name": "", "body": ""}
    assert instrs["add.1"]["op_name"] == "jit(step)/add"
    assert (instrs["while.5"]["op"], instrs["while.5"]["body"]) == (
        "while", "region_1.body")
    # a kernel's own metadata, not the empty `kernel_metadata={}` before it
    assert instrs["closed_call.7"]["source"].endswith("fused_lookup.py:650")
    assert instrs["closed_call.7"]["target"] == "tpu_custom_call"
    assert instrs["while.5"]["calls"] == ["region_0.cond", "region_1.body"]
    got = tr.inherit_sources(instrs)
    probe = ("/x/deeprec_tpu/embedding/table.py:503", "")
    assert got["fusion.2"] == probe          # from the computation it calls
    assert got["sort.4"] == probe            # from its first operand
    assert got["copy.3"] == ("/x/deeprec_tpu/ops/fused_lookup.py:417", "")
    assert got["broadcast.6"] == got["while.5"]   # from where it flows to
    assert got["iota.1"] == probe                 # likewise: fusion.2
    assert got["iota.9"] == ("", "")         # nothing to inherit from


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(RuntimeError, match="no `XLA Ops` line"):
        tr.reduce_events({}, {}, [], chips=1)


def test_recorded_trace_reduces_to_the_numbers_worked_out_another_way():
    with open(os.path.join(DATA, "recorded.expected.json")) as f:
        want = json.load(f)
    ops, modules, host = tr.read_events(
        os.path.join(DATA, "recorded.xplane.pb"))
    red = tr.reduce_events(ops, modules, host, chips=1,
                           window_s=want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert len(red["modules"]) == want["modules"]
    # when the numbers were worked out a Pallas call was known by a name
    # that begins with its target; the gather's calls, which the compiler
    # wraps in a `kCustom` fusion named `closed_call.N`, stood with their
    # source file's layer. The rule by the kernel's `name=` knows them
    # (leaf operations: their self time is their duration)
    wrapped = sum(d for _, d, name, *_ in ops["/device:TPU:0"]
                  if name.startswith("closed_call.")) * 1e-9
    assert wrapped > want["by_layer_s"]["row kernels"]
    want["by_layer_s"]["row kernels"] += wrapped
    want["by_layer_s"]["embedding engine"] -= wrapped
    for layer, seconds in want["by_layer_s"].items():
        assert red["by_layer_s"][layer] == pytest.approx(seconds, rel=1e-6)
    assert sum(red["by_layer_s"].values()) == pytest.approx(red["busy_s"],
                                                            rel=1e-6)
    assert any(name.startswith("bench.") for _, _, name in host)


KERNELS_HLO = """HloModule jit_step

%wrapped (w: f32[4,8], i: s32[8]) -> f32[4,8] {
  %w = f32[4,8]{1,0} parameter(0)
  %i = s32[8]{0} parameter(1)
  %closed_call.8 = f32[8]{0} custom-call(%i, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/phase_lookup/vmap(engine_gather)/rows_gather/vmap(gather_rows)/closed_call/pallas_call" source_file="/x/deeprec_tpu/ops/fused_lookup.py" source_line=650}
  ROOT %dus.4 = f32[4,8]{1,0} dynamic-update-slice(%w, %closed_call.8)
}

ENTRY %main (x: f32[4,8], k: s32[8]) -> f32[4,8] {
  %x = f32[4,8]{1,0} parameter(0)
  %k = s32[8]{0} parameter(1)
  %apply_rows_sr.5 = f32[4,8]{1,0} custom-call(%x, %k), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/phase_sparse_apply/rows_scatter/apply_rows_sr/pallas_call" source_file="/x/deeprec_tpu/ops/fused_lookup.py" source_line=700}
  %closed_call.9 = f32[4,8]{1,0} fusion(%apply_rows_sr.5, %k), kind=kCustom, calls=%wrapped
  %flash_attention_fwd.3 = f32[4,8]{1,0} custom-call(%closed_call.9), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/phase_dense_fwd_bwd/flash_attention_fwd/pallas_call" source_file="/x/deeprec_tpu/ops/flash_attention.py" source_line=120}
  %flash_in_nn.4 = f32[4,8]{1,0} custom-call(%flash_attention_fwd.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/phase_dense_fwd_bwd/flash_attention_fwd/pallas_call" source_file="/x/deeprec_tpu/nn.py" source_line=300}
  ROOT %topk.6 = f32[4,8]{1,0} custom-call(%flash_in_nn.4), custom_call_target="TopK", metadata={op_name="jit(step)/phase_dense_fwd_bwd/top_k" source_file="/x/deeprec_tpu/nn.py" source_line=310}
}
"""


def test_a_pallas_call_no_rule_lists_goes_to_its_source_files_layer():
    """One module with two row kernels (one wrapped in the `kCustom` fusion
    the trace then times) and another kernel, `ops/flash_attention.py`'s
    forward as a later PR would name it: only the row kernels' calls are
    booked to `row kernels`."""
    rules = tr.load_rules()
    names = frozenset(k for r in rules for k in r["kernels"])
    facts = tr.instruction_facts(KERNELS_HLO, names)
    assert facts["apply_rows_sr.5"][2] and facts["closed_call.9"][2]
    assert "gather_rows" in facts["closed_call.9"][2]   # the wrapped call's
    assert facts["flash_attention_fwd.3"][2]            # a Pallas call too
    assert facts["topk.6"] == ("/x/deeprec_tpu/nn.py:310", "TopK", ())
    ops = {"/device:TPU:0": [
        (100 * i, 10 * (i + 1), name, *facts[name][::2])
        for i, name in enumerate(("apply_rows_sr.5", "closed_call.9",
                                  "flash_attention_fwd.3", "flash_in_nn.4",
                                  "topk.6"))]}
    red = tr.reduce_events(ops, {}, [], chips=1)
    assert red["by_layer_s"] == pytest.approx({
        "row kernels": 30e-9,        # the two row-kernel calls, 10 + 20
        "unattributed": 30e-9,       # flash_attention.py is no layer's file
        "dense model": 90e-9})       # the same kernel called from nn.py: 40,
                                     # and nn.py's own TopK custom call: 50
