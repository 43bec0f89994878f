"""The `moonlight` family's files: its configuration against the catalog
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
from benchmark.work import moonlight as work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "moonlight-16b.seq8k"


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
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 27,
                                   "n_routed_experts": 64,
                                   "vocab_size": 163840}
    # the guide's floors: a leading dense layer and at least four expert
    # layers, eight experts, an eighth of the vocabulary
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 8, 163840 // 8)
    assert work.layer_kinds(config) == (1, 5)
    published = {
        "hidden_size": 2048, "intermediate_size": 11264, "kv_lora_rank": 512,
        "moe_intermediate_size": 1408, "num_attention_heads": 16,
        "num_key_value_heads": 16, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "q_lora_rank": None,
        "num_experts_per_tok": 6, "n_shared_experts": 2,
        "first_k_dense_replace": 1, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 2.446,
        "rms_norm_eps": 1e-5, "rope_theta": 50000, "seq_aux": True,
        "num_nextn_predict_layers": 0, "max_position_embeddings": 8192}
    assert {k: config[k] for k in published} == published
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["deployment"]["router_outputs"] == 64
    assert config["bias_update_rate"] == 0.001
    assert "2412.19437" in config["bias_update_rate_note"]
    assert len(config["departures"]) == 4 and "rotary" in config["assumed"]
    assert mix["seq_len"] == config["max_position_embeddings"] == 8192
    assert mix["vocab"] == config["vocab_size"]
    # the key the accepted reader of the experts' load reads
    assert config["num_experts"] == config["n_routed_experts"]


def test_the_counts_by_hand(cell):
    """Parameters, and multiply-adds a position forward, worked out by hand
    from the configuration's widths (PERF.md section 4 has the same
    arithmetic)."""
    config, mix = cell
    attention = (2048 * 16 * 192 + 2048 * (512 + 64) + 512
                 + 512 * 16 * 256 + 16 * 128 * 2048)
    assert attention == 13_763_072
    dense_layer = attention + 2 * 2048 + 3 * 2048 * 11264
    assert dense_layer == 82_973_184
    expert = 3 * 2048 * 1408
    expert_layer = (attention + 2 * 2048 + 2048 * 64 + 64 + 2 * expert
                    + 8 * expert)
    assert (expert, expert_layer) == (8_650_752, 100_405_824)
    head = 2048 * 20480
    total = dense_layer + 5 * expert_layer + head + 2048
    assert total == 626_947_392 == work.dense_params(config)
    # the issue's five layers: one expert layer fewer
    assert total - expert_layer == 526_541_568
    macs = work.forward_macs_per_token(config, mix)
    pairs = 8192 * 8193 // 2
    assert pairs == 33_558_528 == work.score_pairs(mix)
    assert macs == {
        "attn_projections": 6 * (attention - 512),
        "attn_scores": 6 * 16 * (192 + 128) * pairs / 8192,
        "mlp": 3 * 2048 * 11264,
        "router": 5 * 2048 * 64,
        "experts": 5 * 0.75 * expert,
        "shared_experts": 5 * 2 * expert,
        "head": head}
    assert work.held_experts_per_token(config) == 0.75
    flops = work.flops_per_example(config, mix)
    assert flops == 6.0 * sum(macs.values())
    assert 2.63e9 < flops < 2.64e9                 # 21.6 TFLOP a step
    assert work.dense_min_bytes_per_step(config, mix) > 12.0 * total
    assert work.engine_bytes_per_unique(config) == 8 + 4 * 2048 * 4 + 24


def test_the_kernels_counts_are_the_least_work(cell):
    """Latent attention: two products forward and four backward over the
    causal pairs, the score side at 192 and the value side at 128: 2 x
    pairs x 16 x 960 FLOPs a layer, whatever width a kernel pads to. The
    experts at the measured pairs; the shared experts at every token."""
    config, mix = cell
    pairs = 8192 * 8193 // 2
    flops, least = work.latent_attn_work_per_step(config, mix)
    assert flops == 6 * 2 * pairs * 16 * 960
    assert 1.03e12 < flops / 6 < 1.04e12
    # q, k_n, ONE rotary key, v, o and their gradients, bf16
    assert least == 6 * 8192 * 4 * (16 * 192 + 16 * 128 + 64 + 2 * 16 * 128)
    assert work.flash_attn_work_per_step is work.latent_attn_work_per_step
    # compute-bound by a wide margin at the chip's peaks
    assert flops / 197e12 > 10 * least / 819e9
    flops, least = work.experts_work_per_step(config, mix, 5 * 6144)
    assert flops == 6 * 5 * 6144 * 8_650_752
    assert least == 12 * 5 * 8 * 8_650_752 \
        + 5 * 6144 * 4 * (2 * 2048 + 3 * 1408)
    assert work.router_even_load_per_step(config, mix) == 5 * 768
    flops, least = work.shared_experts_work_per_step(config, mix)
    assert flops == 6 * 5 * 8192 * 2 * 8_650_752
    assert least == 5 * (12 * 2 * 8_650_752
                         + 8192 * 4 * (2 * 2048 + 3 * 2816))


def test_labels_the_fill_and_the_budgets_at_this_mix(cell):
    _, mix = cell
    b = tokens.make_batch(mix, 3_000_000_019, 4)
    assert b["tok"].shape == b["label"].shape == (1, 8192)
    assert (b["tok"][:, 1:] == b["label"][:, :-1]).all()
    assert 0 <= b["tok"].min() and b["label"].max() < mix["vocab"] == 20_480
    assert tokens.examples(mix) == 8192
    seen = set()
    for j in range(tokens.fill_steps(mix)):
        ids = tokens.fill_batch(mix, 11, j)["tok"].reshape(-1)
        assert len(np.unique(ids)) <= mix["unique_budget"]
        seen.update(ids.tolist())
    assert seen == set(range(tokens.filled_rows(mix)))
    assert tokens.fill_steps(mix) == 8
    # no step of the window passes the budget either (tools/budget.py)
    for k in range(20):
        assert len(np.unique(tokens.draw_ids(mix, 3_000_000_019, k))) \
            <= mix["unique_budget"]
    # whole blocks of 128, and no more pairs than there are
    assert mix["pair_budget"] % 128 == 0
    assert 6144 < mix["pair_budget"] <= 8192 * 6


# ---------------------------------------------------------------- rehearsal

TINY = {
    "name": "tiny-latent", "builder": "moonlight", "reference": "moonlight",
    "work": "moonlight", "hidden_size": 32, "intermediate_size": 48,
    "kv_lora_rank": 16, "max_position_embeddings": 64,
    "moe_intermediate_size": 16, "n_group": 1, "n_routed_experts": 2,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "topk_group": 1, "topk_method": "noaux_tc",
    "vocab_size": 48, "bias_update_rate": 0.001,
    "deployment": {"router_outputs": 16, "first_expert_held": 4,
                   "first_layer_held": 0},
    "emb_dim": 32, "capacity": 128, "table_dtype": "float32",
    "embedding_init": {"kind": "stateless_normal", "mean": 0.0,
                       "stddev": 0.02},
    "sparse_optimizer": {"name": "adagrad", "lr": 0.05,
                         "initial_accumulator_value": 0.1},
    "dense_optimizer": {"name": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.95,
                        "eps": 1e-8},
    "num_experts": 2, "reduced": [],
}
TINY_MIX = {"generator": "tokens", "batch": 2, "seq_len": 32, "vocab": 48,
            "zipf_a": 1.1, "unique_budget": 40, "pair_budget": 256}


@pytest.fixture()
def rehearsal(tmp_path):
    manifest = harness.load_manifest(ROOT)
    manifest["configs"] = [{"name": "tiny-latent", "source": "test",
                            "file": "configs/tiny-latent.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny-latent.seq32",
                              "config": "tiny-latent", "traffic": "tiny-seq",
                              "chips": 1, "why": "test"}]
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(tmp_path / sub)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp_path / "configs" / "tiny-latent.json").write_text(json.dumps(TINY))
    (tmp_path / "traffic" / "tiny-seq.json").write_text(json.dumps(TINY_MIX))
    (tmp_path / "limits" / "tiny-latent.seq32.json").write_text(json.dumps(
        {"limits": {"grad_gap": 0.1, "grad_median_gap": 0.02,
                    "change_gap": 0.5}}))
    return str(tmp_path)


def test_the_cells_path_runs_correct_on_the_cpu(rehearsal, tmp_path):
    """Untraced, through `run_cell`: the builder on `Trainer`, the model's
    own loss and its rule's leaf, the fill through the timed step, the
    eight counters, the reference after the window; and the two planted
    faults read not correct."""
    line = json.loads(json.dumps(harness.run_cell(
        "tiny-latent.seq32", 2 ** 31 + 11, 1.0, False,
        t_start=time.perf_counter(), require_tpu=False, root=rehearsal,
        data=rehearsal, trace_dir=str(tmp_path / "trace"))))
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["compared"]["fill_gap"] == {"value": 0.0, "limit": 0.0}
    occ = line["occupancy"]
    assert occ["window_start_rows"] == occ["filled_rows_wanted"] == 48

    from benchmark import correct
    from benchmark.reference import moonlight as reference

    limits = correct.load_limits("tiny-latent.seq32", rehearsal)
    mix = dict(TINY_MIX, name="tiny-seq")
    batches = [tokens.make_batch(mix, 5, k)
               for k in range(harness.CHECK_STEPS)]
    ref = reference.run(TINY, batches, 5)
    assert correct.verdict(correct.compare(ref, ref), limits)[0]
    for fault in ("fault_half_positions", "fault_no_routed_scale"):
        read = reference.run(TINY, batches, 5, **reference.CONTROLS[fault])
        assert not correct.verdict(correct.compare(read, ref), limits)[0]
    assert set(reference.CONTROLS) == {
        "control_fp8", "fault_half_positions", "fault_no_routed_scale",
        "witness_bf16"}


def test_the_reference_imports_no_other_familys_module():
    with open(os.path.join(BENCH, "reference", "moonlight.py")) as f:
        text = f.read()
    assert "deeprec_tpu" not in text.split('"""', 2)[2]
    assert "from benchmark" not in text and "import benchmark" not in text


def test_the_counters_readers_read_this_familys_run(cell):
    """The accepted readers of the expert layer's counters, and the new
    one, on a window of this family's counters and ITS configuration's
    keys: none raises, each finds its number."""
    from benchmark.builders.moonlight import Program
    from benchmark.layer_metrics import (expert_load_max_over_mean,
                                         routed_pairs_per_step,
                                         router_load_max_over_mean)

    config, mix = cell
    names = Program.COUNTERS
    assert "moe_overflow" in Program.FAIL_COUNTERS
    rise = dict.fromkeys(names, 0)
    # ten steps, five expert layers: 6,144 pairs a layer, the fullest of a
    # layer's eight held experts at twice the mean (768), the fullest of
    # all 64 outputs at three times it
    rise.update(moe_pairs=10 * 5 * 6_144, moe_max_load=10 * 5 * 1_536,
                moe_all_max_load=10 * 5 * 2_304)
    first = np.full(len(names), 7, np.int32)
    ctx = {"counter_names": names, "steps": 10, "config": config,
           "mix": mix, "work": work,
           "counters": np.stack([first, first + np.asarray(
               [rise[n] for n in names], np.int32)])}
    assert routed_pairs_per_step.read(ctx) == 5 * 6_144
    assert expert_load_max_over_mean.read(ctx) == 2.0
    assert router_load_max_over_mean.read(ctx) == 3.0


def test_the_new_readers_read_nothing_where_the_program_says_nothing():
    """A program without the latent stack's scopes or the
    `moe_all_max_load` counter (the parent's, the other token cells'): each
    new reader returns None and does not raise."""
    from benchmark.layer_metrics import (
        attn_latent_device_ms_per_step, attn_latent_roofline,
        mlp_device_ms_per_step, moe_shared_device_ms_per_step,
        router_bias_update_device_ms_per_step, router_load_max_over_mean)

    ctx = {"trace": None, "traced_steps": 0, "counter_names": ("moe_pairs",),
           "counters": np.zeros((2, 1), np.int32), "config": {},
           "work": None, "peaks": None, "steps": 10,
           "examples_per_step": 8192}
    for reader in (attn_latent_device_ms_per_step, attn_latent_roofline,
                   mlp_device_ms_per_step, moe_shared_device_ms_per_step,
                   router_bias_update_device_ms_per_step,
                   router_load_max_over_mean):
        assert reader.read(ctx) is None


# ------------------------------------------------------------------- limits


def test_the_cells_limits_hold_the_recorded_seeds_and_fail_the_control():
    """The limits file's own readings, judged again: every recorded seed of
    the program passes every compared limit; each limit that has an upper
    reading (the first loss among them: the loss limit the lower precision
    must fail) lies between the program's largest reading and the smallest
    of the float8 control and of both faults."""
    from benchmark import correct

    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        record = json.load(f)
    limits = correct.load_limits(CELL)
    assert limits == record["limits"] and len(record["per_seed"]) >= 16
    for seed, read in record["per_seed"].items():
        assert all(read[n] <= lim for n, lim in limits.items()), seed
    for name in ("loss1_gap", "grad_gap", "grad_median_gap"):
        r = record["readings"][name]
        assert r["program_max"] == max(
            read[name] for read in record["per_seed"].values())
        assert r["program_max"] < limits[name] < min(
            r["control_fp8_min"], r["fault_half_positions_min"],
            r["fault_no_routed_scale_min"]), name
    # held between the reading and 1, what a state left unchanged reads
    assert record["readings"]["change_gap"]["program_max"] \
        < limits["change_gap"] < 1.0
