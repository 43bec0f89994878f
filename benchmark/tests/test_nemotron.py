"""The `nemotron` family's files: its configuration against the catalog
row, its work counts checked by hand, its mix's budgets and fill, its cell's
path rehearsed end to end through `run_cell` on the CPU at a size a CPU
holds (the manifest, configuration, mix and limits of the rehearsal are
written to a temporary directory; the builder, reference, work module and
generator are the benchmark's own), and its readers on its counters."""
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.generators import tokens
from benchmark.work import nemotron as work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "nemotron3-super.seq8k"


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
    published = {"num_hidden_layers": 88, "mamba_num_heads": 128,
                 "n_groups": 8, "num_attention_heads": 32,
                 "num_key_value_heads": 2, "n_routed_experts": 512,
                 "vocab_size": 131072}
    assert config["reduced"] == list(published)
    assert config["published"] == published
    # a chip's share of a 64-chip layer: one period, heads and vocabulary
    # by 8, experts by 64
    assert {k: config[k] for k in published} == {
        "num_hidden_layers": 11, "mamba_num_heads": 16, "n_groups": 1,
        "num_attention_heads": 4, "num_key_value_heads": 1,
        "n_routed_experts": 8, "vocab_size": 16384}
    assert work.kinds(config) == "MEMEMEM*EME"
    assert work.layer_counts(config) == (5, 1, 5)
    widths = {"hidden_size": 4096, "mamba_head_dim": 64,
              "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
              "head_dim": 128, "moe_latent_size": 1024,
              "moe_intermediate_size": 2688,
              "moe_shared_expert_intermediate_size": 5376,
              "num_experts_per_tok": 22, "routed_scaling_factor": 5,
              "n_shared_experts": 1, "norm_topk_prob": True,
              "mlp_hidden_act": "relu2", "use_conv_bias": True,
              "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
              "num_nextn_predict_layers": 1}
    assert {k: config[k] for k in widths} == widths
    dep = config["deployment"]
    assert (dep["chips_per_layer"], dep["router_outputs"]) == (64, 512)
    assert config["bias_update_rate"] == 0.001
    assert "2412.19437" in config["bias_update_rate_note"]
    assert {"no_rotary", "router", "bias_update_rate", "seq_len",
            "learning_rates", "initialiser"} <= set(config["assumed"])
    assert len(config["departures"]) == 4
    assert mix["seq_len"] == 8192 and mix["vocab"] == config["vocab_size"]
    # the keys the accepted readers of the experts' load and of their live
    # hidden units read
    assert config["num_experts"] == config["n_routed_experts"]
    assert config["moe_ffn_hidden_size"] == config["moe_intermediate_size"]


def test_the_counts_by_hand(cell):
    """Parameters, and multiply-adds a position forward, worked out by hand
    from the configuration's widths (PERF.md section 4 has the same
    arithmetic)."""
    config, mix = cell
    w_in = 4096 * (2 * 16 * 64 + 2 * 128 + 16)           # z, x, B, C, dt
    assert w_in == 4096 * 2320
    mamba = w_in + 5 * (1024 + 256) + 3 * 16 + 1024 + 1024 * 4096
    assert mamba == 13_704_496
    attention = 4096 * 4 * 128 + 2 * 4096 * 128 + 4 * 128 * 4096
    assert attention == 5_242_880
    expert = 2 * 1024 * 2688
    expert_layer = (4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
                    + 8 * expert)
    assert (expert, expert_layer) == (5_505_024, 98_566_656)
    head = 4096 * 16384
    total = (5 * mamba + attention + 5 * expert_layer + head
             + 12 * 4096)                                  # 11 norms + final
    assert total == 633_756_656 == work.dense_params(config)
    macs = work.forward_macs_per_token(config, mix)
    pairs = 8192 * 8193 // 2
    assert macs == {
        "mamba_projections": 5 * (w_in + 1024 * 4096),
        "ssd_scan": 5 * 2 * 16 * 128 * 64,
        "attn_projections": attention,
        "attn_scores": 4 * 2 * 128 * pairs / 8192,
        "router": 5 * 4096 * 512,
        "latent_projections": 5 * 2 * 4096 * 1024,
        "experts": 5 * (22 * 8 / 512) * expert,
        "shared_expert": 5 * 2 * 4096 * 5376,
        "head": head}
    flops = work.flops_per_example(config, mix)
    assert flops == 6.0 * sum(macs.values())
    assert 2.57e9 < flops < 2.58e9                 # 21.06 TFLOP a step
    assert work.dense_min_bytes_per_step(config, mix) > 12.0 * total
    assert work.engine_bytes_per_unique(config) == 8 + 4 * 4096 * 4 + 24


def test_the_kernels_counts_are_the_least_work(cell):
    """The scan as its recurrence (a state write and a state read, N x P
    each, a head a token), the attention over the causal pairs, the experts
    at the measured pairs."""
    config, mix = cell
    flops, least = work.ssd_scan_work_per_step(config, mix)
    assert flops == 6 * 5 * 8192 * 2 * 16 * 128 * 64
    ins, out = 2 * (16 * 64 + 2 * 128) + 4 * 16, 4 * 16 * 64
    assert least == 5 * 8192 * (3 * ins + 2 * out)
    # bytes-bound at the chip's peaks
    assert least / 819e9 > flops / 197e12
    pairs = 8192 * 8193 // 2
    flops, least = work.flash_attn_work_per_step(config, mix)
    assert flops == 6 * 4 * 2 * 128 * pairs
    assert least == 8192 * 4 * (2 * 4 * 128 + 2 * 128)
    flops, least = work.experts_work_per_step(config, mix, 5 * 2816)
    assert flops == 6 * 5 * 2816 * 5_505_024
    assert least == 12 * 5 * 8 * 5_505_024 \
        + 5 * 2816 * 4 * (2 * 1024 + 2 * 2688)
    assert work.router_even_load_per_step(config, mix) == 5 * 352


def test_labels_the_fill_and_the_budgets_at_this_mix(cell):
    _, mix = cell
    b = tokens.make_batch(mix, 3_000_000_019, 4)
    assert b["tok"].shape == b["label"].shape == (1, 8192)
    assert (b["tok"][:, 1:] == b["label"][:, :-1]).all()
    assert 0 <= b["tok"].min() and b["label"].max() < mix["vocab"] == 16_384
    assert tokens.examples(mix) == 8192
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
    # whole blocks of 128, over an even router's 2,816 and no more pairs
    # than there are
    assert mix["pair_budget"] % 128 == 0
    assert 2816 < mix["pair_budget"] <= 8192 * 8


# ---------------------------------------------------------------- rehearsal

def _tiny():
    with open(os.path.join(BENCH, "configs",
                           "nemotron3-super-120b-a12b.json")) as f:
        full = json.load(f)
    return dict(
        full, name="tiny-mamba", hybrid_override_pattern="MEM*E",
        num_hidden_layers=5, hidden_size=32, mamba_num_heads=4,
        mamba_head_dim=8, n_groups=2, ssm_state_size=8, chunk_size=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        n_routed_experts=2, num_experts=2, num_experts_per_tok=4,
        moe_intermediate_size=16, moe_ffn_hidden_size=16, moe_latent_size=12,
        moe_shared_expert_intermediate_size=24, vocab_size=48, emb_dim=32,
        capacity=128, reduced=[],
        deployment=dict(full["deployment"], router_outputs=16,
                        first_expert_held=4),
        dense_optimizer=dict(full["dense_optimizer"], lr=1e-3))


TINY_MIX = {"generator": "tokens", "batch": 2, "seq_len": 32, "vocab": 48,
            "zipf_a": 1.1, "unique_budget": 40, "pair_budget": 256}


@pytest.fixture()
def rehearsal(tmp_path):
    manifest = harness.load_manifest(ROOT)
    manifest["configs"] = [{"name": "tiny-mamba", "source": "test",
                            "file": "configs/tiny-mamba.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny-mamba.seq32",
                              "config": "tiny-mamba", "traffic": "tiny-seq",
                              "chips": 1, "why": "test"}]
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(tmp_path / sub)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp_path / "configs" / "tiny-mamba.json").write_text(
        json.dumps(_tiny()))
    (tmp_path / "traffic" / "tiny-seq.json").write_text(json.dumps(TINY_MIX))
    (tmp_path / "limits" / "tiny-mamba.seq32.json").write_text(json.dumps(
        {"limits": {"grad_gap": 0.1, "grad_median_gap": 0.02,
                    "change_gap": 0.5}}))
    return str(tmp_path)


def test_the_cells_path_runs_correct_on_the_cpu(rehearsal, tmp_path):
    """Untraced, through `run_cell`: the builder on `Trainer`, the model's
    own loss and its rule's leaf, the fill through the timed step, the
    eight counters, the reference after the window; and the planted faults
    read not correct."""
    line = json.loads(json.dumps(harness.run_cell(
        "tiny-mamba.seq32", 2 ** 31 + 11, 1.0, False,
        t_start=time.perf_counter(), require_tpu=False, root=rehearsal,
        data=rehearsal, trace_dir=str(tmp_path / "trace"))))
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["compared"]["fill_gap"] == {"value": 0.0, "limit": 0.0}
    occ = line["occupancy"]
    assert occ["window_start_rows"] == occ["filled_rows_wanted"] == 48

    from benchmark import correct
    from benchmark.reference import nemotron as reference

    limits = correct.load_limits("tiny-mamba.seq32", rehearsal)
    mix = dict(TINY_MIX, name="tiny-seq")
    batches = [tokens.make_batch(mix, 5, k)
               for k in range(harness.CHECK_STEPS)]
    tiny = _tiny()
    ref = reference.run(tiny, batches, 5)
    assert correct.verdict(correct.compare(ref, ref), limits)[0]
    for fault in ("fault_half_positions", "fault_no_skip"):
        read = reference.run(tiny, batches, 5, **reference.CONTROLS[fault])
        assert not correct.verdict(correct.compare(read, ref), limits)[0]
    assert set(reference.CONTROLS) == {
        "control_fp8", "fault_half_positions", "fault_bf16_state",
        "fault_no_skip", "witness_bf16"}


def test_the_reference_imports_no_other_familys_module():
    with open(os.path.join(BENCH, "reference", "nemotron.py")) as f:
        text = f.read()
    assert "deeprec_tpu" not in text.split('"""', 2)[2]
    assert "from benchmark" not in text and "import benchmark" not in text


def test_the_counters_readers_read_this_familys_run(cell):
    """The accepted readers of the expert layer's counters on a window of
    this family's counters and ITS configuration's keys: none raises, each
    finds its number."""
    from benchmark.builders.nemotron import Program
    from benchmark.layer_metrics import (expert_hidden_live_share,
                                         expert_load_max_over_mean,
                                         routed_pairs_per_step,
                                         router_load_max_over_mean)

    config, mix = cell
    names = Program.COUNTERS
    assert "moe_overflow" in Program.FAIL_COUNTERS
    rise = dict.fromkeys(names, 0)
    # ten steps, five expert layers: 2,816 pairs a layer, the fullest of a
    # layer's eight held experts at twice the mean (352), the fullest of
    # all 512 outputs at three times it, half the pairs' 2,688 hidden
    # units live
    rise.update(moe_pairs=10 * 5 * 2_816, moe_max_load=10 * 5 * 704,
                moe_all_max_load=10 * 5 * 1_056,
                moe_hidden_live=10 * 5 * 2_816 * 1_344)
    first = np.full(len(names), 7, np.int32)
    ctx = {"counter_names": names, "steps": 10, "config": config,
           "mix": mix, "work": work,
           "counters": np.stack([first, first + np.asarray(
               [rise[n] for n in names], np.int32)])}
    assert routed_pairs_per_step.read(ctx) == 5 * 2_816
    assert expert_load_max_over_mean.read(ctx) == 2.0
    assert router_load_max_over_mean.read(ctx) == 3.0
    assert expert_hidden_live_share.read(ctx) == 0.5


def test_the_new_readers_read_nothing_where_the_program_says_nothing():
    """A program without the Mamba stack's scopes (the parent's, the other
    token cells'): each new reader returns None and does not raise."""
    from benchmark.layer_metrics import (
        mamba_device_ms_per_step, moe_latent_device_ms_per_step,
        ssd_scan_device_ms_per_step, ssd_scan_roofline)

    ctx = {"trace": None, "traced_steps": 0, "counter_names": ("moe_pairs",),
           "counters": np.zeros((2, 1), np.int32), "config": {},
           "work": None, "peaks": None, "steps": 10,
           "examples_per_step": 8192}
    for reader in (mamba_device_ms_per_step, moe_latent_device_ms_per_step,
                   ssd_scan_device_ms_per_step, ssd_scan_roofline):
        assert reader.read(ctx) is None


# ------------------------------------------------------------------- limits


def test_the_cells_limits_hold_the_recorded_seeds_and_fail_the_control():
    """The limits file's own readings, judged again: every recorded seed of
    the program passes every limit it was read on; the gradient limits lie
    between the program's largest reading and the smallest of the float8
    control and of both faults; the loss limits hold the program with
    three times of room and fail both faults; the second loss's lies
    between its readings, the program's largest and the float8 control's
    smallest, with room on both sides; the change is held between the
    reading and 1, what a state left unchanged reads."""
    from benchmark import correct

    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        record = json.load(f)
    limits = correct.load_limits(CELL)
    assert limits == record["limits"] and len(record["per_seed"]) >= 12
    for seed, read in record["per_seed"].items():
        assert all(read[n] <= lim for n, lim in limits.items() if n in read)
    r = record["readings"]
    for name in ("grad_gap", "grad_median_gap"):
        assert r[name]["program_max"] < limits[name] < min(
            r[name]["control_fp8_min"], r[name]["fault_half_positions_min"],
            r[name]["fault_no_skip_min"]), name
    loss2 = r["loss2_gap"]
    assert 2 * loss2["program_max"] < limits["loss2_gap"] \
        < loss2["control_fp8_min"] / 2
    assert 3 * r["loss1_gap"]["program_max"] < limits["loss1_gap"] < min(
        r["loss1_gap"]["fault_half_positions_min"],
        r["loss1_gap"]["fault_no_skip_min"])
    assert r["change_gap"]["program_max"] < limits["change_gap"] < 1.0
    assert limits["change_gap"] < r["change_gap"]["fault_no_skip_min"]
