"""The `qwen3next` family's files: its work counts checked by hand, its
generator's bytes pinned, and its cell's path rehearsed end to end through
`run_cell` on the CPU at a size a CPU holds (the manifest, configuration,
mix and limits of the rehearsal are written to a temporary directory; the
builder, reference, work module and generator are the benchmark's own)."""
import hashlib
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.generators import tokens
from benchmark.work import qwen3next as work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "qwen3next-a3b.seq8k"


@pytest.fixture(scope="module")
def cell():
    manifest = harness.load_manifest(ROOT)
    entry = harness.find_cell(manifest, CELL)
    config = harness.load_config(manifest, entry["config"], ROOT)
    mix, generator = harness.load_mix(entry["traffic"])
    assert generator is tokens
    return config, mix


# ------------------------------------------------------------------- counts


def test_the_counts_by_hand(cell):
    """Multiply-adds a position, forward, worked out by hand from the
    configuration's widths (PERF.md section 4 has the same arithmetic)."""
    config, mix = cell
    got = work.forward_macs_per_token(config, mix)
    # gated delta net: 2048 x 12288 + 2048 x 64 + 4096 x 2048, and the
    # convolution's 4 taps on 8192 channels, three such layers
    assert got["gdn_projections"] == 3 * (25_165_824 + 131_072 + 8_388_608
                                          + 32_768)
    # the rule: 32 heads x 3 products of 128 x 128
    assert got["gdn_rule"] == 3 * 32 * 3 * 128 * 128 == 4_718_592
    # attention: 2048 x 8192 (query and gate), 2 x 2048 x 512, 4096 x 2048
    assert got["attn_projections"] == 16_777_216 + 2_097_152 + 8_388_608
    # scores: Q K^T and P V over (8192 + 1) / 2 keys, 16 heads of 256
    assert got["attn_scores"] == 2 * 16 * 256 * 4096.5 == 33_558_528
    # expert block: router 2048 x 512, shared expert 3 x 2048 x 512, its
    # gate 2048, and 10 x 32 / 512 = 0.625 routed experts of 3 x 2048 x 512
    assert got["moe"] == 4 * (1_048_576 + 3_145_728 + 2_048
                              + 0.625 * 3_145_728)
    assert got["head"] == 2048 * 18_992 == 38_895_616
    flops = work.flops_per_example(config, mix)
    assert flops == 6 * sum(got.values())
    assert 1.38e9 < flops < 1.385e9
    # the parameters here: 586.8 M, as the program's own tree counts them
    assert work.dense_params(config) == 586_771_520
    assert work.held_experts_per_token(config) == 0.625
    assert work.engine_bytes_per_unique(config) == 8 + 4 * 4 * 2048 + 24


def test_the_kernels_counts_are_the_least_work(cell):
    config, mix = cell
    flops, least = work.gdn_rule_work_per_step(config, mix)
    assert flops == 6 * 4_718_592 * 8192
    # a position: q, k at 16 key heads and v at 32 value heads in bf16 three
    # times, the gates three times, o and its gradient once each
    assert least == 8192 * 3 * (3 * 2 * (2 * 2048 + 4096) + 3 * 256
                                + 2 * 16_384)
    flops, least = work.flash_attn_work_per_step(config, mix)
    assert flops == 6 * 33_558_528 * 8192
    assert least == 8192 * 4 * (2 * 4096 + 2 * 512)
    flops, least = work.experts_work_per_step(config, mix, 20_480.0)
    assert flops == 6 * 20_480 * 3_145_728
    assert least == 12 * 4 * 32 * 3_145_728 + 20_480 * 4 * (4096 + 1536)
    # at a sixteenth of the deployed load the weights' bytes bind
    assert least / 819e9 > flops / 197e12


# ---------------------------------------------------------------- generator


def test_the_mix_makes_the_bytes_it_made(cell):
    _, mix = cell
    h = hashlib.sha256()
    for seed in (7, 3_000_000_019):
        for k in (0, 1, 5):
            b = tokens.make_batch(mix, seed, k)
            for key in sorted(b):
                h.update(key.encode())
                h.update(str(b[key].dtype).encode())
                h.update(str(b[key].shape).encode())
                h.update(b[key].tobytes())
        b = tokens.fill_batch(mix, seed, 2)
        for key in sorted(b):
            h.update(b[key].tobytes())
    assert h.hexdigest() == ("bcf8402fec1c41303f08a9a90e39d580"
                             "a52ae66192b0509a1f66646d3d560aaa")


def test_labels_are_the_tokens_that_follow(cell):
    _, mix = cell
    b = tokens.make_batch(mix, 3_000_000_019, 4)
    assert b["tok"].shape == b["label"].shape == (1, 8192)
    assert b["tok"].dtype == b["label"].dtype == np.int32
    assert (b["tok"][:, 1:] == b["label"][:, :-1]).all()
    assert 0 <= b["tok"].min() and b["label"].max() < mix["vocab"] == 18_992
    assert tokens.examples(mix) == 8192
    again = tokens.make_batch(mix, 3_000_000_019, 4)
    assert all((b[k] == again[k]).all() for k in b)
    other = tokens.make_batch(mix, 3_000_000_020, 4)
    assert (b["tok"] != other["tok"]).any()


def test_the_fill_holds_the_vocabulary_within_the_budget(cell):
    _, mix = cell
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


# ---------------------------------------------------------------- rehearsal

TINY = {
    "name": "tiny-hybrid", "builder": "qwen3next", "reference": "qwen3next",
    "work": "qwen3next", "full_attention_interval": 4, "head_dim": 16,
    "hidden_size": 32, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 8, "moe_intermediate_size": 16,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 16, "vocab_size": 48,
    "deployment": {"router_outputs": 16, "first_expert_held": 4},
    "emb_dim": 32, "capacity": 128, "table_dtype": "float32",
    "delta_rule_chunk": 8,
    "embedding_init": {"kind": "stateless_normal", "mean": 0.0,
                       "stddev": 0.02},
    "sparse_optimizer": {"name": "adagrad", "lr": 0.05,
                         "initial_accumulator_value": 0.1},
    "dense_optimizer": {"name": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.95,
                        "eps": 1e-8},
    "reduced": [],
}
TINY_MIX = {"generator": "tokens", "batch": 2, "seq_len": 32, "vocab": 48,
            "zipf_a": 1.1, "unique_budget": 40, "pair_budget": 256}


@pytest.fixture()
def rehearsal(tmp_path):
    manifest = harness.load_manifest(ROOT)
    manifest["configs"] = [{"name": "tiny-hybrid", "source": "test",
                            "file": "configs/tiny-hybrid.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny-hybrid.seq32",
                              "config": "tiny-hybrid", "traffic": "tiny-seq",
                              "chips": 1, "why": "test"}]
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(tmp_path / sub)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp_path / "configs" / "tiny-hybrid.json").write_text(json.dumps(TINY))
    (tmp_path / "traffic" / "tiny-seq.json").write_text(json.dumps(TINY_MIX))
    (tmp_path / "limits" / "tiny-hybrid.seq32.json").write_text(json.dumps(
        {"limits": {"grad_median_gap": 0.02, "change_gap": 0.5}}))
    return str(tmp_path)


def test_the_cells_path_runs_correct_on_the_cpu(rehearsal, tmp_path):
    """Untraced, through `run_cell`: the builder on `Trainer`, the model's
    own loss, the fill through the timed step, the seven counters, the
    reference after the window; and the planted fault reads not correct."""
    line = json.loads(json.dumps(harness.run_cell(
        "tiny-hybrid.seq32", 2 ** 31 + 11, 1.0, False,
        t_start=time.perf_counter(), require_tpu=False, root=rehearsal,
        data=rehearsal, trace_dir=str(tmp_path / "trace"))))
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["compared"]["fill_gap"] == {"value": 0.0, "limit": 0.0}
    occ = line["occupancy"]
    assert occ["window_start_rows"] == occ["filled_rows_wanted"] == 48
    rate = line["metrics"]["train_examples_per_s"]["value"]
    assert rate == pytest.approx(line["attempted"] * 64, rel=0.25)

    from benchmark import correct
    from benchmark.reference import qwen3next as reference

    limits = correct.load_limits("tiny-hybrid.seq32", rehearsal)
    mix = dict(TINY_MIX, name="tiny-seq")
    batches = [tokens.make_batch(mix, 5, k)
               for k in range(harness.CHECK_STEPS)]
    ref = reference.run(TINY, batches, 5)
    assert correct.verdict(correct.compare(ref, ref), limits)[0]
    fault = reference.run(TINY, batches, 5, half_positions=True)
    assert not correct.verdict(correct.compare(fault, ref), limits)[0]
    assert set(reference.CONTROLS) == {"control_fp8",
                                       "fault_half_positions",
                                       "witness_bf16"}
