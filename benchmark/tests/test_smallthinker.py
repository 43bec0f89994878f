"""The `smallthinker` family's files: its work counts checked by hand, its
generator's bytes pinned at the cell's mix, and its cell's path rehearsed
end to end through `run_cell` on the CPU at a size a CPU holds (the
manifest, configuration, mix and limits of the rehearsal are written to a
temporary directory; the builder, reference, work module and generator are
the benchmark's own)."""
import hashlib
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.generators import tokens
from benchmark.work import smallthinker as work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "smallthinker-21b.seq16k"


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
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 52,
                                   "moe_num_primary_experts": 64,
                                   "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 8, 18992)
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["deployment"]["router_outputs"] == 64
    assert len(config["rope_layout"]) == 52 \
        and config["rope_layout"] == config["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    assert mix["seq_len"] == config["max_position_embeddings"] == 16384
    assert mix["vocab"] == config["vocab_size"]
    assert work.window_layers(config) == (3, 1)
    # the key the accepted reader of the experts' load reads
    assert config["num_experts"] == config["moe_num_primary_experts"]


def test_the_counts_by_hand(cell):
    """Multiply-adds a position, forward, worked out by hand from the
    configuration's widths (PERF.md section 4 has the same arithmetic)."""
    config, mix = cell
    # pairs the masks let through: the causal half, and inside the window
    assert work.score_pairs(mix, None) == 16384 * 16385 // 2 == 134_225_920
    assert work.score_pairs(mix, 4096) == 16384 * 4096 - 4096 * 4095 // 2 \
        == 58_722_304
    assert work.score_pairs(mix, 16384) == work.score_pairs(mix, None)
    got = work.forward_macs_per_token(config, mix)
    # 2560 x 3584 (28 heads of 128), 2 x 2560 x 512, 3584 x 2560, 4 layers
    assert got["attn_projections"] == 4 * 20_971_520
    # Q K^T and P V, 28 heads of 128: 8192.5 keys a query in the global
    # layer, 3584.125 in each of the three window layers
    assert got["attn_scores_global"] == 2 * 28 * 128 * 8192.5 == 58_723_840
    assert got["attn_scores_window"] == 3 * 2 * 28 * 128 * 3584.125 \
        == 3 * 25_691_008
    assert got["router"] == 4 * 2560 * 64
    # 6 x 8 / 64 = 0.75 routed experts of 3 x 2560 x 768
    assert got["experts"] == 4 * 0.75 * 5_898_240
    assert got["head"] == 2560 * 18_992 == 48_619_520
    flops = work.flops_per_example(config, mix)
    assert flops == 6 * sum(got.values())
    assert 1.715e9 < flops < 1.725e9
    share = (got["attn_scores_global"] + got["attn_scores_window"]) \
        / sum(got.values())
    assert 0.47 < share < 0.48
    # the parameters here, as the program's own tree counts them
    assert work.dense_params(config) == 4 * (21_140_480 + 8 * 5_898_240) \
        + 2560 + 48_619_520 == 321_927_680
    assert work.held_experts_per_token(config) == 0.75
    assert work.engine_bytes_per_unique(config) == 8 + 4 * 4 * 2560 + 24


def test_the_kernels_counts_are_the_least_work(cell):
    config, mix = cell
    flops, least = work.flash_attn_work_per_step(config, mix)
    assert flops == 6 * 2 * 28 * 128 * (134_225_920 + 3 * 58_722_304)
    # a position a layer: q and o at 28 heads, k and v at 4, and their
    # gradients, in bf16
    assert least == 4 * 16384 * 4 * (2 * 3584 + 2 * 512)
    w_flops, w_least = work.window_attn_work_per_step(config, mix)
    assert w_flops == 6 * 2 * 28 * 128 * 3 * 58_722_304
    assert w_least == 3 * 16384 * 4 * (2 * 3584 + 2 * 512)
    # the window layers' share of the in-mask area: 3 x 0.4375 of 1 + ...
    assert 0.567 < w_flops / flops < 0.568
    flops, least = work.experts_work_per_step(config, mix, 49_152.0)
    assert flops == 6 * 49_152 * 5_898_240
    assert least == 12 * 4 * 8 * 5_898_240 + 49_152 * 4 * (5120 + 2304)
    # at 1,536 tokens an expert the products bind, not the weights' bytes
    assert flops / 197e12 > least / 819e9


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
    assert h.hexdigest() == PINNED


PINNED = ("e13e73930d28c33cf7933e6f374fee21"
          "b6bee01ef5ac4b22f2086d4ed6366ea3")


def test_labels_and_the_fill_at_this_mix(cell):
    _, mix = cell
    b = tokens.make_batch(mix, 3_000_000_019, 4)
    assert b["tok"].shape == b["label"].shape == (1, 16384)
    assert (b["tok"][:, 1:] == b["label"][:, :-1]).all()
    assert 0 <= b["tok"].min() and b["label"].max() < mix["vocab"] == 18_992
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
    # whole blocks of 128, and no more pairs than there are
    assert mix["pair_budget"] % 128 == 0
    assert mix["pair_budget"] <= 16384 * 6


# ---------------------------------------------------------------- rehearsal

TINY = {
    "name": "tiny-window", "builder": "smallthinker",
    "reference": "smallthinker", "work": "smallthinker", "head_dim": 16,
    "hidden_size": 32, "max_position_embeddings": 64,
    "moe_ffn_hidden_size": 16, "moe_num_active_primary_experts": 4,
    "moe_num_primary_experts": 4, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_hidden_layers": 4, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1], "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "vocab_size": 48,
    "deployment": {"router_outputs": 16, "first_expert_held": 4},
    "emb_dim": 32, "capacity": 128, "table_dtype": "float32",
    "embedding_init": {"kind": "stateless_normal", "mean": 0.0,
                       "stddev": 0.02},
    "sparse_optimizer": {"name": "adagrad", "lr": 0.05,
                         "initial_accumulator_value": 0.1},
    "dense_optimizer": {"name": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.95,
                        "eps": 1e-8},
    "num_experts": 4, "reduced": [],
}
TINY_MIX = {"generator": "tokens", "batch": 2, "seq_len": 32, "vocab": 48,
            "zipf_a": 1.1, "unique_budget": 40, "pair_budget": 256}


@pytest.fixture()
def rehearsal(tmp_path):
    manifest = harness.load_manifest(ROOT)
    manifest["configs"] = [{"name": "tiny-window", "source": "test",
                            "file": "configs/tiny-window.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": "tiny-window.seq32",
                              "config": "tiny-window", "traffic": "tiny-seq",
                              "chips": 1, "why": "test"}]
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(tmp_path / sub)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp_path / "configs" / "tiny-window.json").write_text(json.dumps(TINY))
    (tmp_path / "traffic" / "tiny-seq.json").write_text(json.dumps(TINY_MIX))
    (tmp_path / "limits" / "tiny-window.seq32.json").write_text(json.dumps(
        {"limits": {"grad_median_gap": 0.02, "change_gap": 0.5}}))
    return str(tmp_path)


def test_the_cells_path_runs_correct_on_the_cpu(rehearsal, tmp_path):
    """Untraced, through `run_cell`: the builder on `Trainer`, the model's
    own loss, the fill through the timed step, the eight counters, the
    reference after the window; and the two planted faults read not
    correct."""
    line = json.loads(json.dumps(harness.run_cell(
        "tiny-window.seq32", 2 ** 31 + 11, 1.0, False,
        t_start=time.perf_counter(), require_tpu=False, root=rehearsal,
        data=rehearsal, trace_dir=str(tmp_path / "trace"))))
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["compared"]["fill_gap"] == {"value": 0.0, "limit": 0.0}
    occ = line["occupancy"]
    assert occ["window_start_rows"] == occ["filled_rows_wanted"] == 48

    from benchmark import correct
    from benchmark.reference import smallthinker as reference

    limits = correct.load_limits("tiny-window.seq32", rehearsal)
    mix = dict(TINY_MIX, name="tiny-seq")
    batches = [tokens.make_batch(mix, 5, k)
               for k in range(harness.CHECK_STEPS)]
    ref = reference.run(TINY, batches, 5)
    assert correct.verdict(correct.compare(ref, ref), limits)[0]
    for fault in ("fault_half_positions", "fault_no_window"):
        read = reference.run(TINY, batches, 5, **reference.CONTROLS[fault])
        assert not correct.verdict(correct.compare(read, ref), limits)[0]
    assert set(reference.CONTROLS) == {"control_fp8", "fault_half_positions",
                                       "fault_no_window", "witness_bf16"}


def test_the_counters_readers_read_this_familys_run(cell):
    """The accepted readers of the expert layer's counters, and the new
    one, on a window of this family's counters and ITS configuration's
    keys: none raises, each finds its number."""
    from benchmark.builders.smallthinker import Program
    from benchmark.layer_metrics import (expert_hidden_live_share,
                                         expert_load_max_over_mean,
                                         routed_pairs_per_step)

    config, _ = cell
    names = Program.COUNTERS
    rise = dict.fromkeys(names, 0)
    # ten steps, four layers: 12,288 pairs a layer, the fullest of a
    # layer's eight experts at twice the mean, half the hidden units live
    rise.update(moe_pairs=10 * 4 * 12_288, moe_max_load=10 * 4 * 3_072,
                moe_hidden_live=10 * 4 * 12_288 * 384)
    first = np.full(len(names), 7, np.int32)
    ctx = {"counter_names": names, "steps": 10, "config": config,
           "counters": np.stack([first, first + np.asarray(
               [rise[n] for n in names], np.int32)])}
    assert routed_pairs_per_step.read(ctx) == 4 * 12_288
    assert expert_load_max_over_mean.read(ctx) == 2.0
    assert expert_hidden_live_share.read(ctx) == 0.5


def test_the_new_readers_read_nothing_where_the_program_says_nothing():
    """A program without the window scopes or the `moe_hidden_live`
    counter (the parent's, the other token cell's): each new reader
    returns None and does not raise."""
    from benchmark.layer_metrics import (attn_global_device_ms_per_step,
                                         attn_window_device_ms_per_step,
                                         attn_window_roofline,
                                         expert_hidden_live_share)

    ctx = {"trace": None, "traced_steps": 0, "counter_names": ("moe_pairs",),
           "counters": np.zeros((2, 1), np.int32), "config": {},
           "work": None, "peaks": None}
    for reader in (attn_global_device_ms_per_step,
                   attn_window_device_ms_per_step, attn_window_roofline,
                   expert_hidden_live_share):
        assert reader.read(ctx) is None
    ctx = {"counter_names": ("moe_pairs", "moe_hidden_live"),
           "counters": np.asarray([[10, 5], [110, 5 + 800]], np.int32),
           "config": {"moe_ffn_hidden_size": 16}}
    assert expert_hidden_live_share.read(ctx) == 0.5
