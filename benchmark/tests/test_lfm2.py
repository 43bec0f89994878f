"""The `lfm2` family's files: its configuration against the catalog row,
its work counts checked by hand, its mix's budgets and fill, train steps of
a tiny stack through `Trainer` against the reference and the reference's
planted faults, its cell's path rehearsed end to end through `run_cell` on
the CPU at a size a CPU holds (the manifest, configuration, mix and limits
of the rehearsal are written to a temporary directory; the builder,
reference, work module and generator are the benchmark's own), its readers
on its counters, its limits, and the cell's step compiled for a described
v5e."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, harness
from benchmark.builders import lfm2 as builder
from benchmark.generators import tokens
from benchmark.reference import lfm2 as reference
from benchmark.work import lfm2 as work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "lfm2-8b-a1b.seq8k"
# the catalog row's `config` (LiquidAI/LFM2-8B-A1B, config.json)
ROW = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
       "intermediate_size": 7168,
       "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                       "conv", "full_attention", "conv", "conv", "conv",
                       "full_attention", "conv", "conv", "conv",
                       "full_attention", "conv", "conv", "conv",
                       "full_attention", "conv", "conv", "full_attention",
                       "conv", "conv"],
       "max_position_embeddings": 128000, "model_type": "lfm2_moe",
       "moe_intermediate_size": 1792, "norm_eps": 1e-05,
       "norm_topk_prob": True, "num_attention_heads": 32,
       "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
       "num_hidden_layers": 24, "num_key_value_heads": 8,
       "rope_theta": 1000000, "routed_scaling_factor": 1,
       "use_expert_bias": True, "vocab_size": 65536}


@pytest.fixture(scope="module")
def cell():
    manifest = harness.load_manifest(ROOT)
    entry = harness.find_cell(manifest, CELL)
    config = harness.load_config(manifest, entry["config"], ROOT)
    mix, generator = harness.load_mix(entry["traffic"])
    assert generator is tokens
    return config, mix


# ------------------------------------------------------------------- counts


def test_the_configuration_is_the_catalog_rows(cell):
    config, mix = cell
    reduced = config["reduced"]
    assert reduced == ["num_hidden_layers", "num_dense_layers",
                       "layer_types", "num_experts", "vocab_size"]
    assert config["published"] == {k: ROW[k] for k in reduced}
    assert {k: config[k] for k in ROW if k not in reduced} == {
        k: v for k, v in ROW.items() if k not in reduced}
    # a chip's share of a 4-chip layer: published layers 0 and 2-5, experts
    # by 4, the vocabulary by 4
    dep = config["deployment"]
    assert dep["layers_held"] == [0, 2, 3, 4, 5]
    assert config["layer_types"] == [ROW["layer_types"][i]
                                     for i in dep["layers_held"]]
    assert {k: config[k] for k in reduced if k != "layer_types"} == {
        "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
        "vocab_size": 16384}
    assert (dep["chips_per_layer"], dep["router_outputs"]) == (4, 32)
    assert work.layer_counts(config) == (4, 1, 1, 4)
    assert work.head_dim(config) == 64
    assert config["router_renorm_eps"] == 1e-6
    assert config["bias_update_rate"] == 0.001
    assert "2412.19437" in config["bias_update_rate_note"]
    assert {"head", "head_dim", "bias_update_rate", "initialiser",
            "learning_rates", "capacity", "budgets"} <= set(config["assumed"])
    assert len(config["departures"]) == 4
    assert mix["batch"] == 2 and mix["seq_len"] == 8192
    assert mix["vocab"] == config["vocab_size"]


def test_the_counts_by_hand(cell):
    """Parameters, and multiply-adds a position forward, worked out by hand
    from the configuration's widths (PERF.md section 4 has the same
    arithmetic)."""
    config, mix = cell
    conv = 2048 * 6144 + 3 * 2048 + 2048 ** 2
    attention = 2 * 2048 ** 2 + 2 * 2048 * 512 + 2 * 64
    expert = 3 * 2048 * 1792
    dense_ffn = 3 * 2048 * 7168
    assert (conv, attention, expert) == (16_783_360, 10_485_888, 11_010_048)
    norms = 2 * 2048
    layer0 = conv + dense_ffn + norms
    attn_layer = attention + 8 * expert + 2048 * 32 + norms
    conv_layer = conv + 8 * expert + 2048 * 32 + norms
    assert (layer0, attn_layer, conv_layer) == (60_827_648, 98_635_904,
                                                104_933_376)
    total = layer0 + attn_layer + 3 * conv_layer + 2048 * 16384 + 2048
    assert total == 507_820_160 == work.dense_params(config)
    assert work.selection_bias_entries(config) == 4 * 32
    macs = work.forward_macs_per_token(config, mix)
    pairs = 8192 * 8193 // 2
    assert macs == {
        "conv_projections": 4 * (2048 * 6144 + 2048 * 2048),
        "attn_projections": 2 * 2048 ** 2 + 2 * 2048 * 512,
        "attn_scores": 32 * 2 * 64 * pairs / 8192,
        "mlp": dense_ffn,
        "router": 4 * 2048 * 32,
        "experts": 4 * (4 * 8 / 32) * expert,
        "head": 2048 * 16384}
    flops = work.flops_per_example(config, mix)
    assert flops == 6.0 * sum(macs.values())
    assert 1.297e9 < flops < 1.298e9                # 21.26 TFLOP a step
    # the short convolutions' two products are the largest block
    assert macs["conv_projections"] == max(macs.values())
    assert work.dense_min_bytes_per_step(config, mix) > 12.0 * total
    assert work.engine_bytes_per_unique(config) == 8 + 4 * 2048 * 4 + 24


def test_the_parts_counts_are_the_least_work(cell):
    """The gates and taps at the algorithm's least bytes, the attention
    over the causal pairs, the experts at the measured pairs."""
    config, mix = cell
    positions = 2 * 8192
    flops, least = work.shortconv_gate_work_per_step(config, mix)
    assert flops == 3 * 4 * positions * 2048 * (2 + 2 * 3)
    # forward: B, C, x f32 read, the gated output bf16 written; backward:
    # its gradient (bf16) and B, C, x read, their gradients (f32) written
    assert least == 4 * positions * 2048 * ((12 + 2) + (2 + 12 + 12))
    assert least / 819e9 > 100 * flops / 197e12      # bound by bandwidth
    pairs = 8192 * 8193 // 2
    flops, least = work.flash_attn_work_per_step(config, mix)
    assert flops == 6 * 2 * 32 * 2 * 64 * pairs
    assert least == positions * 4 * (2 * 32 * 64 + 2 * 8 * 64)
    flops, least = work.experts_work_per_step(config, mix, 4 * 16384)
    assert flops == 6 * 4 * 16384 * 11_010_048
    assert least == 12 * 4 * 8 * 11_010_048 \
        + 4 * 16384 * 4 * (2 * 2048 + 3 * 1792)
    assert work.router_even_load_per_step(config, mix) == 4 * 2048


def test_labels_the_fill_and_the_budgets_at_this_mix(cell):
    _, mix = cell
    b = tokens.make_batch(mix, 3_000_000_019, 4)
    assert b["tok"].shape == b["label"].shape == (2, 8192)
    assert (b["tok"][:, 1:] == b["label"][:, :-1]).all()
    assert 0 <= b["tok"].min() and b["label"].max() < mix["vocab"] == 16_384
    assert tokens.examples(mix) == 16384
    seen = set()
    for j in range(tokens.fill_steps(mix)):
        ids = tokens.fill_batch(mix, 11, j)["tok"].reshape(-1)
        assert len(np.unique(ids)) <= mix["unique_budget"]
        seen.update(ids.tolist())
    assert seen == set(range(tokens.filled_rows(mix)))
    # no step of the window passes the budget either (tools/budget.py)
    for k in range(20):
        assert len(np.unique(tokens.draw_ids(mix, 3_000_000_019, k))) \
            <= mix["unique_budget"]
    # whole blocks of 128, and no more pairs than a layer can route
    assert mix["pair_budget"] % 128 == 0
    assert mix["pair_budget"] <= 16384 * 4


# ------------------------------------------------- tiny, through Trainer

def _tiny():
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        full = json.load(f)
    return dict(
        full, name="tiny-lfm2",
        layer_types=["conv", "full_attention", "conv", "conv"],
        num_hidden_layers=4, num_dense_layers=1, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
        moe_intermediate_size=24, num_experts=2, vocab_size=48, emb_dim=64,
        capacity=128, reduced=[],
        deployment=dict(full["deployment"], router_outputs=8,
                        first_expert_held=2),
        dense_optimizer=dict(full["dense_optimizer"], lr=1e-3))


TINY_MIX = {"generator": "tokens", "batch": 2, "seq_len": 32, "vocab": 48,
            "zipf_a": 1.1, "unique_budget": 40, "pair_budget": 128}
# set from this file's own readings: the program reads under 1e-6 on every
# number, each fault at least 10x over a limit it fails
TINY_LIMITS = {"loss1_gap": 1e-5, "grad_gap": 2e-5, "grad_median_gap": 1e-6,
               "change_gap": 0.5}
BIAS = ("layers.1.moe.bias", "layers.2.moe.bias", "layers.3.moe.bias")
GAMMA = 0.001


@pytest.fixture(scope="module")
def three_steps():
    tiny, mix = _tiny(), dict(TINY_MIX, name="tiny-seq")
    p = builder.Program(tiny, mix)
    p.model.compute_dtype = jnp.float32
    state = p.fresh_state(5)
    k = iter(range(harness.CHECK_STEPS))

    def next_batch():
        host = tokens.make_batch(mix, 5, next(k))
        return host, p.put(host)

    state, got, batches = harness.check_steps(p, state, next_batch, tiny,
                                              reference)
    counters = dict(zip(p.COUNTERS, np.asarray(p.counters(state)).tolist()))
    bias = {k: np.asarray(v) for k, v in p.dense_params(state).items()
            if k in BIAS}
    return got, batches, reference.run(tiny, batches, 5), counters, bias


def test_three_train_steps_equal_the_reference(three_steps):
    """The tiny stack on `Trainer` (the model's own loss, Adagrad rows, the
    dense Adam, the rule's leaves) against the reference's `run`, as the
    benchmark compares a cell: the losses, every leaf's first gradient and
    its change; the bias leaves after three steps are the reference's to
    the bit, moved by whole steps of gamma."""
    got, _, want, counters, bias = three_steps
    assert got["grad"].keys() == want["grad"].keys()
    numbers = correct.compare(got, want)
    for name in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert numbers[name]["value"] < 1e-5, numbers
    assert numbers["grad_gap"]["value"] < 1e-5, numbers
    assert numbers["change_gap"]["value"] < 1e-3, numbers
    assert correct.verdict(numbers, TINY_LIMITS)[0]
    assert counters["moe_pairs"] > 0
    assert counters["moe_overflow"] == counters["dedup_overflow"] == 0
    for name in BIAS:
        assert got["grad"][name] == want["grad"][name] == 0.0
        np.testing.assert_array_equal(bias[name], np.asarray(
            want["bias"][name], np.float32))
        moves = np.round(bias[name] / GAMMA).astype(int)
        assert np.abs(moves).max() >= 1
        np.testing.assert_allclose(bias[name], moves * np.float32(GAMMA),
                                   rtol=1e-6)


@pytest.mark.parametrize("fault", ["control_fp8", "fault_half_positions",
                                   "fault_bf16_residual",
                                   "fault_no_out_gate"])
def test_a_fault_of_the_reference_fails_the_limits(three_steps, fault):
    """float8 operands, half the positions left out of the loss, the
    residual stream rounded to bf16 after every layer, and the output gate
    left out each read as not correct against limits the program passes."""
    _, batches, want, _, _ = three_steps
    read = correct.compare(reference.run(
        _tiny(), batches, 5, **reference.CONTROLS[fault]), want)
    assert not correct.verdict(read, TINY_LIMITS)[0], read


@pytest.fixture()
def rehearsal(tmp_path):
    manifest = harness.load_manifest(ROOT)
    manifest["configs"] = [{"name": "tiny-lfm2", "source": "test",
                            "file": "configs/tiny-lfm2.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny-lfm2.seq32",
                              "config": "tiny-lfm2", "traffic": "tiny-seq",
                              "chips": 1, "why": "test"}]
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(tmp_path / sub)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp_path / "configs" / "tiny-lfm2.json").write_text(json.dumps(_tiny()))
    (tmp_path / "traffic" / "tiny-seq.json").write_text(json.dumps(TINY_MIX))
    (tmp_path / "limits" / "tiny-lfm2.seq32.json").write_text(json.dumps(
        {"limits": {"grad_gap": 0.1, "grad_median_gap": 0.02,
                    "change_gap": 0.5}}))
    return str(tmp_path)


def test_the_cells_path_runs_correct_on_the_cpu(rehearsal, tmp_path):
    """Untraced, through `run_cell`: the builder on `Trainer`, the model's
    own loss and its rule's leaf, the fill through the timed step, the
    eight counters, the reference after the window."""
    line = json.loads(json.dumps(harness.run_cell(
        "tiny-lfm2.seq32", 2 ** 31 + 11, 1.0, False,
        t_start=time.perf_counter(), require_tpu=False, root=rehearsal,
        data=rehearsal, trace_dir=str(tmp_path / "trace"))))
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["compared"]["fill_gap"] == {"value": 0.0, "limit": 0.0}
    occ = line["occupancy"]
    assert occ["window_start_rows"] == occ["filled_rows_wanted"] == 48
    assert set(reference.CONTROLS) == {
        "control_fp8", "fault_half_positions", "fault_bf16_residual",
        "fault_bf16_residual_bf16_ops", "fault_no_out_gate", "witness_bf16"}


def test_the_reference_imports_no_other_familys_module():
    with open(os.path.join(BENCH, "reference", "lfm2.py")) as f:
        text = f.read()
    assert "deeprec_tpu" not in text.split('"""', 2)[2]
    assert "from benchmark" not in text and "import benchmark" not in text


# ------------------------------------------------------------------ readers


def test_the_counters_readers_read_this_familys_run(cell):
    """The accepted readers of the expert layer's counters on a window of
    this family's counters and ITS configuration's keys: none raises, each
    finds its number."""
    from benchmark.layer_metrics import (expert_load_max_over_mean,
                                         routed_pairs_per_step,
                                         router_load_max_over_mean)

    config, mix = cell
    names = builder.Program.COUNTERS
    assert "moe_overflow" in builder.Program.FAIL_COUNTERS
    rise = dict.fromkeys(names, 0)
    # ten steps, four expert layers: 16,384 pairs a layer, the fullest of a
    # layer's eight held experts at twice the mean (2,048), the fullest of
    # all 32 outputs at three times it
    rise.update(moe_pairs=10 * 4 * 16_384, moe_max_load=10 * 4 * 4_096,
                moe_all_max_load=10 * 4 * 6_144)
    first = np.full(len(names), 7, np.int32)
    ctx = {"counter_names": names, "steps": 10, "config": config,
           "mix": mix, "work": work,
           "counters": np.stack([first, first + np.asarray(
               [rise[n] for n in names], np.int32)])}
    assert routed_pairs_per_step.read(ctx) == 4 * 16_384
    assert expert_load_max_over_mean.read(ctx) == 2.0
    assert router_load_max_over_mean.read(ctx) == 3.0


def test_the_new_readers_read_nothing_where_the_program_says_nothing():
    """A program without the short-convolution stack's scopes (the parent's,
    the other token cells'): each new reader returns None and does not
    raise."""
    from benchmark.layer_metrics import (shortconv_device_ms_per_step,
                                         shortconv_gate_device_ms_per_step,
                                         shortconv_gate_roofline)

    ctx = {"trace": None, "traced_steps": 0, "counter_names": ("moe_pairs",),
           "counters": np.zeros((2, 1), np.int32), "config": {},
           "work": None, "peaks": None, "steps": 10,
           "examples_per_step": 16384}
    for reader in (shortconv_device_ms_per_step,
                   shortconv_gate_device_ms_per_step,
                   shortconv_gate_roofline):
        assert reader.read(ctx) is None


# ------------------------------------------------------------------- limits


def test_the_cells_limits_hold_the_recorded_seeds_and_fail_the_control():
    """The limits file's own readings, judged again: every recorded seed of
    the program passes every limit; each limit that the file says lies
    between two readings lies between the program's largest and the
    smallest of the control or fault it names (the gradients' the float8
    control's, the later losses' the half-positions fault's, the median
    change's the no-output-gate fault's); the first loss's limit holds the
    program with three times of room; the change is held between the
    reading and 1, what a state left unchanged reads; the control and every
    fault the file lists as caught fail a limit on every seed they were
    read on, and those it lists as not caught read under the program's
    largest on the numbers they would have to fail."""
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        record = json.load(f)
    limits = correct.load_limits(CELL)
    assert limits == record["limits"] and len(record["per_seed"]) >= 10
    for seed, read in record["per_seed"].items():
        assert all(read[n] <= lim for n, lim in limits.items()), seed
    r = record["readings"]
    assert record["between"] == {
        "grad_gap": "control_fp8", "grad_median_gap": "control_fp8",
        "loss2_gap": "fault_half_positions",
        "loss3_gap": "fault_half_positions",
        "change_median_gap": "fault_no_out_gate"}
    for name, upper in record["between"].items():
        assert r[name]["program_max"] < limits[name] \
            < r[name][upper + "_min"], name
    assert 3 * r["loss1_gap"]["program_max"] < limits["loss1_gap"]
    assert r["change_gap"]["program_max"] < limits["change_gap"] < 1.0
    for kind in ["control_fp8"] + record["faults_caught"]:
        for seed, read in record["controls"][kind].items():
            assert any(read[n] > lim for n, lim in limits.items()), (kind,
                                                                      seed)
    for kind in record["faults_not_caught"]:
        for name in ("grad_gap", "grad_median_gap", "loss1_gap"):
            assert r[name][kind + "_min"] < r[name]["program_max"], kind
    assert set(record["controls"]) == set(reference.CONTROLS)


# ------------------------------------------------------- the stream's dtype


def assert_f32_residual_stream(model, dense, x):
    """The residual stream the step differentiates (`hidden_states`, from
    the rows x [B, T, d] f32 to the last layer's output) is float32 and
    nothing else touches it between layers: each layer's remat takes the
    one before's output as it came, and inside it the stream leaves as
    `h + feed-forward` with `h = x + mixer`, both adds float32 at [B, T, d].
    A stream rounded to bfloat16 anywhere between the layers fails this,
    which no limit of the cell's comparison can see (the limits file's
    note)."""
    from deeprec_tpu.training.trainer import ModelInputs

    jaxpr = jax.make_jaxpr(lambda p, x: model.hidden_states(p, ModelInputs(
        pooled={}, seq={"tok": (x, None)}, dense={}))[0])(dense, x).jaxpr
    layers = [e for e in jaxpr.eqns if e.primitive.name == "remat2"]
    assert len(layers) == model.layers
    stream = jaxpr.invars[-1]
    for i, e in enumerate(layers):
        assert e.invars[-1] is stream, i
        inner = e.params["jaxpr"]
        adds = {q.outvars[0]: q for q in inner.eqns
                if q.primitive.name == "add"}
        out = adds.get(inner.outvars[0])
        assert out is not None, (i, "the layer's output is no add")
        h = [adds[v] for v in out.invars
             if v in adds and inner.invars[-1] in adds[v].invars]
        assert len(h) == 1, (i, "no x + mixer under the output's add")
        for q in (out, h[0]):
            for v in q.invars + q.outvars:
                assert v.aval.dtype == jnp.float32, (i, q)
                assert v.aval.shape == x.shape, (i, q)
        stream = e.outvars[0]
    assert jaxpr.outvars[0] is stream


def test_the_residual_stream_check_passes_the_model_and_fails_a_bf16_one():
    """At the tiny size: the stack as it is passes; the same stack with
    its stream rounded to bfloat16 after every layer, or carried in
    bfloat16, fails."""
    p = builder.Program(_tiny(), dict(TINY_MIX, name="tiny-seq"))
    m = p.model
    dense = jax.eval_shape(p.trainer.init, np.int32(1)).dense
    x = jax.ShapeDtypeStruct((2, 32, 64), jnp.float32)
    assert_f32_residual_stream(m, dense, x)
    layer = m._layer

    def rounded(i, p, x):
        y, c = layer(i, p, x)
        return y.astype(jnp.bfloat16).astype(jnp.float32), c

    def carried(i, p, x):
        y, c = layer(i, p, x.astype(jnp.bfloat16))
        return y.astype(jnp.bfloat16).astype(jnp.float32), c

    for fault in (rounded, carried):
        m._layer = fault
        with pytest.raises(AssertionError):
            assert_f32_residual_stream(m, dense, x)


# ------------------------------------------------------- the cell's program


def test_the_cells_step_compiles_for_a_described_v5e(monkeypatch):
    """The cell's whole train step (five layers at the published widths, 8
    of 32 experts at the mix's pair budget, two sequences of 8,192) lowered
    and compiled for a v5e that is described and not attached: the flash
    kernels at head dim 64 and the grouped products are in it, the
    residual stream it differentiates is float32 between the layers
    (`assert_f32_residual_stream`), and set-up's peak (arguments +
    temporaries + the check's f32 copy of the dense leaves) stays under
    the 15.0e9 B that are safe (PERF.md section 4)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deeprec_tpu.utils import backend, scopes

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    dev = SingleDeviceSharding(topo.devices[0])
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    c = harness.load_cell(CELL)
    program = c.builder.Program(c.config, c.mix)
    tr = program.trainer
    sd = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=dev)
    shapes = jax.eval_shape(tr.init, np.int32(1))
    dense = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes.dense))
    assert dense == 507_820_160 + 128
    assert_f32_residual_stream(program.model, shapes.dense,
                               jax.ShapeDtypeStruct(
                                   (c.mix["batch"], c.mix["seq_len"],
                                    c.config["hidden_size"]), jnp.float32))
    batch = jax.eval_shape(tr.stage_batch, c.generator.make_batch(
        c.mix, 1, 0))
    compiled = tr._train_step.lower(
        jax.tree.map(sd, shapes), jax.tree.map(sd, batch),
        jax.ShapeDtypeStruct((), np.float32)).compile()
    hlo = compiled.as_text()
    for name in (scopes.KERNEL_FLASH_FWD, scopes.KERNEL_GROUPED_MATMUL):
        assert name in hlo, name
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + 4 * dense)
    assert peak < 15.0e9, peak
