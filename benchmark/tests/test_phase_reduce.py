"""The reduction by the program's scopes (benchmark/phase_reduce.py): on a
module's HLO text written by hand, and on a small scoped trace recorded on
the chip (data/phases.xplane.pb.xz, three traced steps of `tiny-dcn` at dim
128; benchmark/tools/record_phase_trace.py wrote it)."""
import json
import lzma
import os

import pytest

from benchmark import harness, phase_reduce, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
VOCAB = phase_reduce.load_vocabulary()
LOOKUP = "jit(step)/phase_lookup"
PROBE = LOOKUP + "/vmap(engine_insert)/engine_probe"
GATHER = LOOKUP + "/vmap(engine_gather)/rows_gather/vmap(gather_rows)"

HLO = f"""HloModule jit_step

%fused_eq (a: s32[8]) -> s32[8] {{
  %a = s32[8]{{0}} parameter(0)
  ROOT %add.1 = s32[8]{{0}} add(%a, %a), metadata={{op_name="{PROBE}/while/body/eq"}}
}}

%probe_body (p: (s32[], s32[8])) -> (s32[], s32[8]) {{
  %p = (s32[], s32[8]{{0}}) parameter(0)
  %gte.1 = s32[8]{{0}} get-tuple-element(%p), index=1
  %scatter.1 = s32[8]{{0}} scatter(%gte.1), metadata={{op_name="{PROBE}/while/body/scatter"}}
  %fusion.9 = s32[8]{{0}} fusion(%scatter.1), kind=kLoop, calls=%fused_eq
  ROOT %tuple.1 = (s32[], s32[8]{{0}}) tuple(%gte.1, %fusion.9)
}}

%probe_cond (p: (s32[], s32[8])) -> pred[] {{
  %p.1 = (s32[], s32[8]{{0}}) parameter(0)
  ROOT %lt.1 = pred[] compare(%p.1), direction=LT, metadata={{op_name="{PROBE}/while/cond/lt"}}
}}

%rows_body (q: f32[4,8]) -> f32[4,8] {{
  %q = f32[4,8]{{1,0}} parameter(0)
  %dynamic-slice.3 = f32[8]{{0}} dynamic-slice(%q), metadata={{op_name="{GATHER}/while/body/dynamic_slice"}}
  %closed_call.7 = f32[8]{{0}} custom-call(%dynamic-slice.3), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name="{GATHER}/while/body/closed_call/pallas_call"}}
  ROOT %dynamic-update-slice.2 = f32[4,8]{{1,0}} dynamic-update-slice(%q, %closed_call.7)
}}

%wrapped_call (w: f32[4,8], i: s32[8]) -> f32[4,8] {{
  %w = f32[4,8]{{1,0}} parameter(0)
  %i = s32[8]{{0}} parameter(1)
  %closed_call.8 = f32[8]{{0}} custom-call(%i, %w), custom_call_target="tpu_custom_call", metadata={{op_name="{GATHER}/while/body/closed_call/pallas_call"}}
  ROOT %dynamic_update_slice.4 = f32[4,8]{{1,0}} dynamic-update-slice(%w, %closed_call.8), metadata={{op_name="{GATHER}/while/body/closed_call/dynamic_update_slice"}}
}}

%rows_cond (q: f32[4,8]) -> pred[] {{
  %q.1 = f32[4,8]{{1,0}} parameter(0)
  ROOT %lt.2 = pred[] compare(%q.1), direction=LT
}}

ENTRY %main (x: f32[4,8], k: s32[8]) -> f32[4,8] {{
  %x = f32[4,8]{{1,0}} parameter(0), metadata={{op_name="state.values"}}
  %k = s32[8]{{0}} parameter(1)
  %tuple.0 = (s32[], s32[8]{{0}}) tuple(%k, %k)
  %while.1 = (s32[], s32[8]{{0}}) while(%tuple.0), condition=%probe_cond, body=%probe_body, metadata={{op_name="{PROBE}/while"}}
  %constant.1 = f32[] constant(0)
  %broadcast.5 = f32[4,8]{{1,0}} broadcast(%constant.1), dimensions={{}}
  %while.2 = f32[4,8]{{1,0}} while(%broadcast.5), condition=%rows_cond, body=%rows_body, metadata={{op_name="{GATHER}/while"}}
  %copy.3 = f32[4,8]{{1,0}} copy(%while.2)
  %closed_call.9 = f32[4,8]{{1,0}} fusion(%copy.3, %k), kind=kCustom, calls=%wrapped_call, metadata={{op_name="{GATHER}/while/body/closed_call/pallas_call"}}
  %dot.1 = f32[4,8]{{1,0}} dot(%copy.3, %x), metadata={{op_name="jit(step)/phase_dense_fwd_bwd/transpose(jvp(dense))/dot_general"}}
  %mul.2 = f32[4,8]{{1,0}} multiply(%dot.1, %dot.1), metadata={{op_name="jit(step)/transpose(jvp(phase_dense_fwd_bwd))/mul;jit(step)/phase_sparse_apply/add"}}
  %reduce.4 = f32[4,8]{{1,0}} reduce(%mul.2), metadata={{op_name="reduce_sum"}}
  %sqrt.1 = f32[4,8]{{1,0}} sqrt(%reduce.4), metadata={{op_name="jit(step)/sqrt"}}
  ROOT %add.9 = f32[4,8]{{1,0}} add(%sqrt.1, %x), metadata={{op_name="jit(step)/phase_sparse_apply/vmap(rows_scatter)/add"}}
}}
"""


# ------------------------------------------------------ tokens of an op_name


@pytest.mark.parametrize("op_name, want", [
    ("jit(s)/phase_lookup/vmap(engine_probe)/while/body/scatter",
     ("phase_lookup", "engine_probe", "", "")),
    ("jit(s)/transpose(jvp(phase_dense_fwd_bwd))/mul",
     ("phase_dense_fwd_bwd", "", "", "")),
    # the outermost phase and the innermost stage
    ("jit(s)/phase_route_next/vmap(engine_insert)/engine_probe/while/cond/lt",
     ("phase_route_next", "engine_probe", "", "")),
    ("jit(s)/shard_map/phase_lookup_exchange/phase_exchange_chunk2/"
     "vmap(engine_gather)/rows_gather/gather",
     ("phase_lookup_exchange", "engine_gather", "rows_gather", "")),
    # an exchange scope outside every trainer's phase is a phase of its own
    ("jit(eval)/shard_map/phase_hier_intra_chunk0/all_to_all",
     ("phase_hier_intra_chunk0", "", "", "")),
    ("jit(s)/phase_sparse_apply/vmap(rows_scatter)/vmap(apply_rows_sr)/"
     "while/body/closed_call/pallas_call",
     ("phase_sparse_apply", "", "rows_scatter", "apply_rows_sr")),
    # fused names: the first one speaks
    ("jit(s)/phase_lookup/vmap(engine_route)/eq;jit(s)/phase_sentinel/add",
     ("phase_lookup", "engine_route", "", "")),
    # no prefix match: `phase_lookups` and `engine_prober` are other words
    ("jit(s)/jit(phase_lookups)/engine_prober/rows_gathered/add",
     ("", "", "", "")),
])
def test_scope_of_unwraps_the_transforms(op_name, want):
    assert tuple(phase_reduce.scope_of(op_name, VOCAB)) == want


# ------------------------------------------------- a module written by hand


@pytest.fixture(scope="module")
def module():
    return phase_reduce.module_scopes(HLO, VOCAB)


def test_instructions_keep_their_scopes_and_sourceless_ones_inherit(module):
    s = {k: tuple(v) for k, v in module.scopes.items()}
    probe = ("phase_lookup", "engine_probe", "", "")
    gather = ("phase_lookup", "engine_gather", "rows_gather", "gather_rows")
    assert s["scatter.1"] == s["while.1"] == s["lt.1"] == probe
    # a fusion the compiler built: from the computation it calls
    assert s["fusion.9"] == probe
    # a copy the compiler made: from its operand
    assert s["copy.3"] == gather
    # a zero-fill with no operand to inherit from: from the loop it feeds
    assert s["broadcast.5"] == gather
    # the loop's own scatter of the kernel's result: from its operands
    assert s["dynamic-update-slice.2"] == gather
    assert s["dot.1"] == ("phase_dense_fwd_bwd", "", "", "")
    assert s["mul.2"] == ("phase_dense_fwd_bwd", "", "", "")
    # a name the compiler gave is no name: it inherits
    assert s["reduce.4"] == ("phase_dense_fwd_bwd", "", "", "")
    # a name of jax's own with no phase in it stays unphased
    assert s["sqrt.1"] == ("", "", "", "")
    assert s["add.9"] == ("phase_sparse_apply", "", "rows_scatter", "")


def test_kernels_and_the_probe_loops_body(module):
    # a Pallas call, and the `kCustom` fusion the compiler wraps one in
    # together with the write of its result (the trace times the fusion)
    assert module.kernels == {"closed_call.7", "closed_call.8",
                              "closed_call.9"}
    # direct members of the body of the `while` under engine_probe, not of
    # the fusion inside it, and not of the loop round the row kernel
    probe = {k for k, v in module.loop_bodies.items() if v == "while.1"}
    assert probe == {"p", "gte.1", "scatter.1", "fusion.9", "tuple.1"}
    # every loop's body is known, each by its `while`
    assert set(module.loop_bodies.values()) == {"while.1", "while.2"}
    assert module.loop_bodies["closed_call.7"] == "while.2"


def events(passes):
    """One device's `XLA Ops` events of two steps of the module above:
    (start, dur, instruction, module); the whiles enclose their bodies."""
    out, t = [], 0

    def op(name, dur, inside=()):
        nonlocal t
        start = t
        t += 1
        for child in inside:
            op(*child)
        t = max(t, start + dur)
        out.append((start, t - start, name, "jit_step(1)"))
        t += 1

    for n in passes:
        op("while.1", 0, [c for _ in range(n) for c in
                          (("scatter.1", 30), ("fusion.9", 10))])
        op("broadcast.5", 50)
        op("while.2", 0, [c for _ in range(4) for c in
                          (("dynamic-slice.3", 100), ("closed_call.7", 40),
                           ("dynamic-update-slice.2", 100))])
        op("copy.3", 20)
        op("dot.1", 200)
        op("sqrt.1", 7)
        op("add.9", 60)
    return out


def test_reduce_events_splits_by_phase_stage_rows_and_counts_passes(module):
    host = [("deeprec.train_step", 0, 1500, 0),
            ("deeprec.train_step", 5000, 2500, 1),
            ("deeprec.stage_batch", 100, 300, -1)]
    red = phase_reduce.reduce_events(
        {"jit_step(1)": module}, {"/device:TPU:0": events([3, 5])}, host, 1,
        VOCAB)
    ns = 1e-9
    assert red["groups_seen"] == {"phase", "stage", "rows", "kernel"}
    # self times: the phases and the unphased rest are the busy time
    assert sum(red["by_phase_s"].values()) == pytest.approx(red["busy_s"])
    assert red["by_phase_s"]["unphased"] == pytest.approx(2 * 7 * ns)
    assert red["by_phase_s"]["phase_sparse_apply"] == pytest.approx(120 * ns)
    assert red["by_phase_s"]["phase_dense_fwd_bwd"] == pytest.approx(400 * ns)
    # the probe: 8 passes of 40 ns and the loops' own self time
    probe = red["by_stage_s"]["engine_probe"]
    assert 8 * 40 * ns <= probe <= (8 * 40 + 8 * 4 + 4) * ns
    assert red["loop_passes"]["engine_probe"] == 8
    # the loop round the row kernel: 2 x 4 passes, booked to each scope it
    # stands under
    assert red["loop_passes"]["rows_gather"] == red["loop_passes"][
        "engine_gather"] == 8
    # under rows_*: the Pallas calls, and everything else (the wrapper:
    # slices, update-slices, the zero-fill, the copy, the loop itself)
    assert red["rows_s"]["kernel"] == pytest.approx(
        red["kernels_s"]) == pytest.approx(2 * 4 * 40 * ns)
    wrapper = red["rows_s"]["wrapper"]
    assert wrapper >= 2 * (50 + 4 * 200 + 20 + 60) * ns
    assert red["by_kernel_s"] == {
        "phase_lookup/gather_rows": pytest.approx(320 * ns)}
    assert red["host_spans"]["deeprec.stage_batch"]["count"] == 1
    assert [n for n, *_ in red["train_steps"]] == [0, 1]
    got = {name: phase_reduce.read_as(red, 2, reads)
           for name, reads in phase_reduce.metric_reads().items()}
    # the two kinds a later metric may use: any scope by its name, and a
    # kernel by its `name=` wherever it stands
    for stage, seconds in red["by_stage_s"].items():
        assert phase_reduce.read_as(red, 2, {"scope": stage}) \
            == pytest.approx(seconds * 1e3 / 2)
    assert phase_reduce.read_as(red, 2, {"scope": "rows_scatter"}) \
        == pytest.approx(2 * 60 * ns * 1e3 / 2)
    assert phase_reduce.read_as(red, 2, {"kernel": "gather_rows"}) \
        == pytest.approx(red["kernels_s"] * 1e3 / 2)
    assert phase_reduce.read_as(red, 2, {"kernel": "apply_rows_sr"}) == 0.0
    assert phase_reduce.read_as(red, 2, {"loop": "rows_gather"}) == 4
    assert phase_reduce.read_as(red, 2, {"span": "deeprec.stage_batch"}) \
        == pytest.approx(300e-6 / 2)
    assert phase_reduce.read_as(red, 2, {"span": "deeprec.maintain"}) is None
    with pytest.raises(ValueError, match="unknown kind"):
        phase_reduce.read_as(red, 2, {"file": "table.py"})
    assert got["probe_passes_per_step"] == 4
    assert got["train_step_host_ms_per_step"] == pytest.approx(2e-3)
    assert got["unphased_device_ms_per_step"] == pytest.approx(7e-6)
    assert got["row_wrapper_device_ms_per_step"] == pytest.approx(
        wrapper * 1e3 / 2)
    assert got["row_calls_device_ms_per_step"] == pytest.approx(
        red["kernels_s"] * 1e3 / 2)
    assert got["insert_device_ms_per_step"] == 0.0
    assert set(got) == {m for m in NEW_METRICS}


NEW_METRICS = (
    "route_device_ms_per_step", "probe_device_ms_per_step",
    "probe_passes_per_step", "insert_device_ms_per_step",
    "gather_device_ms_per_step", "sparse_apply_device_ms_per_step",
    "row_wrapper_device_ms_per_step", "unphased_device_ms_per_step",
    "train_step_host_ms_per_step", "row_calls_device_ms_per_step")


# ---------------------------------------------- the trace recorded on the chip


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(trace directory laid out as the harness writes it, the file)."""
    root = tmp_path_factory.mktemp("trace")
    where = root / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    path = where / "phases.xplane.pb"
    with lzma.open(os.path.join(DATA, "phases.xplane.pb.xz")) as src:
        path.write_bytes(src.read())
    return str(root), str(path)


def test_the_recorded_trace_reduces_to_what_was_pinned(recorded):
    red = phase_reduce.reduce_file(recorded[1], 1)
    with open(os.path.join(DATA, "phases.expected.json")) as f:
        want = json.load(f)
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    for key in ("by_phase_s", "by_stage_s", "rows_s"):
        assert red[key] == pytest.approx(want[key]), key
    assert red["loop_passes"]["engine_probe"] == want["probe_passes"]
    assert [n for n, *_ in red["train_steps"]] == want["step_nums"]
    assert red["host_spans"]["deeprec.stage_batch"]["count"] == 3


def test_the_identities_hold_on_the_recorded_trace(recorded):
    red = phase_reduce.reduce_file(recorded[1], 1)
    ops, modules, host = trace_reduce.read_events(recorded[1])
    old = trace_reduce.reduce_events(ops, modules, host, 1)
    # the phases and the unphased rest are the busy time the harness reads
    assert red["busy_s"] == pytest.approx(old["busy_s"])
    assert sum(red["by_phase_s"].values()) == pytest.approx(old["busy_s"])
    # every Pallas call stands under a rows_* scope, and the reduction by
    # layer knows the same calls by their kernels' names: the gather's too,
    # which the compiler fuses with the write of its result and names
    # `closed_call.N` (a reader that went by the instruction's name gave it
    # to the engine's files: PERF.md section 6, PR 26)
    assert red["rows_s"]["kernel"] == pytest.approx(red["kernels_s"])
    gathers = red["by_kernel_name_s"]["gather_rows"]
    assert gathers == pytest.approx(sum(
        v for k, v in red["by_kernel_s"].items()
        if k.endswith("/gather_rows"))) and gathers > 0
    assert red["rows_s"]["kernel"] == pytest.approx(
        old["by_layer_s"]["row kernels"])
    assert red["rows_s"]["wrapper"] > 0
    # the program's step leaves nothing unphased but the few operations
    # outside the step program (the trace holds no other program's)
    assert red["by_phase_s"].get("unphased", 0.0) < 0.02 * red["busy_s"]
    # the stages and the apply are the engine the old reduction sees, less
    # the combiner (under phase_dense_fwd_bwd) and the lookup's glue
    engine = sum(old["by_layer_s"].get(k, 0.0)
                 for k in ("embedding engine", "row kernels"))
    inside = sum(red["by_stage_s"].values()) + red["by_phase_s"][
        "phase_sparse_apply"]
    assert inside == pytest.approx(engine, rel=0.1)


def test_the_harness_gets_its_readings_from_one_parse(recorded, monkeypatch):
    calls = []
    reduce_file = phase_reduce.reduce_file
    monkeypatch.setattr(phase_reduce, "TRACE_DIR", recorded[0])
    monkeypatch.setattr(phase_reduce, "reduce_file",
                        lambda *a: calls.append(a) or reduce_file(*a))
    monkeypatch.setattr(phase_reduce, "_MEMO", {})
    busy = reduce_file(recorded[1], 1)["busy_s"]
    calls.clear()
    ctx = {"trace": {"busy_s": busy}, "traced_steps": 3, "chips": 1}
    got = {name: harness.load_layer_metric(name).read(ctx)
           for name in NEW_METRICS}
    assert len(calls) == 1
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["probe_passes_per_step"] >= 1
    assert got["train_step_host_ms_per_step"] > 0
    # another run's trace (a busy time that is not the harness's): nothing
    stale = dict(ctx, trace={"busy_s": busy * 1.5})
    assert phase_reduce.for_run(stale) is None
    # an untraced run: nothing
    assert phase_reduce.for_run(dict(ctx, trace=None)) is None


def test_a_program_without_the_scopes_gives_no_reading(tmp_path, monkeypatch):
    """The trace PR 25 recorded, of the program before it named its stages
    and row funnels and wrote its host spans: the readers of those return
    None and the line leaves the metrics out; its phases and its kernels'
    names it did write."""
    where = tmp_path / "plugins" / "profile" / "parent"
    where.mkdir(parents=True)
    os.symlink(os.path.join(DATA, "recorded.xplane.pb"),
               where / "recorded.xplane.pb")
    monkeypatch.setattr(phase_reduce, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(phase_reduce, "_MEMO", {})
    with open(os.path.join(DATA, "recorded.expected.json")) as f:
        busy = json.load(f)["busy_s"]
    ctx = {"trace": {"busy_s": busy}, "traced_steps": 4, "chips": 1}
    red = phase_reduce.reduce_file(str(where / "recorded.xplane.pb"), 1)
    assert red["busy_s"] == pytest.approx(busy)
    assert red["groups_seen"] == {"phase", "kernel"}
    spoken = {"sparse_apply_device_ms_per_step", "unphased_device_ms_per_step"}
    for name in NEW_METRICS:
        value = harness.load_layer_metric(name).read(ctx)
        assert (value is not None) == (name in spoken), name
    # and with no trace on disk at all
    monkeypatch.setattr(phase_reduce, "TRACE_DIR", str(tmp_path / "none"))
    assert phase_reduce.for_run(ctx) is None


# ------------------------------------------- the vocabulary is files, merged


def test_the_vocabulary_is_the_first_file_and_every_file_of_phases(tmp_path):
    with open(os.path.join(os.path.dirname(HERE), "phases.json")) as f:
        first = json.load(f)
    assert first.pop("reads") == {} and first.pop("note")
    assert VOCAB == {**first, "groups": {}}
    assert [g.name for g in phase_reduce.groups_of(VOCAB)] == [
        "phase", "stage", "rows", "kernel"]
    # a directory of data files beside the benchmark's adds a file that
    # declares a group of its own; nothing else moves
    (tmp_path / "phases").mkdir()
    for name, body in [
            ("50-mixer", {"note": "a group of its own", "groups": {
                "mixer": {"pick": "innermost", "names": ["seq_mix"]}}}),
            ("60-more", {"stages": ["engine_evict"], "groups": {
                "mixer": {"pick": "innermost", "names": ["seq_mix2"]}}})]:
        (tmp_path / "phases" / f"{name}.json").write_text(json.dumps(body))
    more = phase_reduce.load_vocabulary(str(tmp_path))
    assert more.pop("groups") == {"mixer": {
        "pick": "innermost", "names": ["seq_mix", "seq_mix2"]}}
    assert more.pop("stages") == first.pop("stages") + ["engine_evict"]
    assert more == first
    (tmp_path / "phases" / "70-clash.json").write_text(json.dumps(
        {"groups": {"mixer": {"pick": "outermost", "names": ["x"]}}}))
    with pytest.raises(ValueError, match="picked innermost elsewhere"):
        phase_reduce.load_vocabulary(str(tmp_path))


def test_a_further_group_is_picked_as_it_says():
    vocab = dict(VOCAB, groups={
        "mixer": {"pick": "innermost", "names": ["mix_a", "mix_b"]},
        "block": {"pick": "outermost", "names": ["block_0", "block_1"]},
        # a file may also add names to a group that has a key of its own
        "stage": {"pick": "innermost", "names": ["engine_evict"]}})
    scope = phase_reduce.scope_of(
        "jit(s)/phase_dense_fwd_bwd/block_0/mix_a/block_1/jvp(mix_b)/add",
        vocab)
    assert tuple(scope) == ("phase_dense_fwd_bwd", "", "", "", "mix_b",
                            "block_0")
    assert (scope.mixer, scope.block, scope.phase) == (
        "mix_b", "block_0", "phase_dense_fwd_bwd")
    assert phase_reduce.scope_of("jit(s)/phase_lookup/engine_evict/x",
                                 vocab).stage == "engine_evict"
    with pytest.raises(AttributeError):
        scope.nothing
    with pytest.raises(ValueError, match="pick"):
        phase_reduce.groups_of(dict(VOCAB, groups={
            "g": {"pick": "first", "names": ["a"]}}))


def test_a_scope_of_a_further_group_is_timed_and_inherited():
    vocab = dict(VOCAB, groups={
        "mixer": {"pick": "innermost", "names": ["seq_mix"]}})
    hlo = """HloModule jit_step

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %cumsum.1 = f32[8]{0} reduce-window(%x), metadata={op_name="jit(step)/phase_dense_fwd_bwd/jvp(seq_mix)/cumsum"}
  %copy.2 = f32[8]{0} copy(%cumsum.1)
  ROOT %dot.3 = f32[8]{0} multiply(%copy.2, %x), metadata={op_name="jit(step)/phase_dense_fwd_bwd/dot_general"}
}
"""
    module = phase_reduce.module_scopes(hlo, vocab)
    assert module.scopes["cumsum.1"].mixer == "seq_mix"
    assert module.scopes["copy.2"].mixer == "seq_mix"   # inherited
    assert module.scopes["dot.3"].mixer == ""
    ops = {"/device:TPU:0": [(0, 30, "cumsum.1", "m"), (40, 5, "copy.2", "m"),
                             (50, 100, "dot.3", "m")]}
    red = phase_reduce.reduce_events({"m": module}, ops, [], 1, vocab)
    assert red["by_scope_s"]["seq_mix"] == pytest.approx(35e-9)
    assert phase_reduce.read_as(red, 1, {"scope": "seq_mix"}) \
        == pytest.approx(35e-6)
    assert red["by_phase_s"] == {"phase_dense_fwd_bwd":
                                 pytest.approx(135e-9)}
