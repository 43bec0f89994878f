"""The DLRM family's work counts (benchmark/work/dlrm.py) against numbers worked out by hand, at the widths
of the benchmark's configuration (full-rank cross, dim 128) and at DeepRec's
modelzoo DLRM widths (pairwise dot, dim 16: data/configs/mid-dlrm.json)."""
import json
import os

import pytest

from benchmark.layer_metrics import dense_roofline
from benchmark.work import dlrm as work

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = {"dlrmdcn-fullrank-d128": os.path.join(os.path.dirname(HERE),
                                                 "configs"),
           "mid-dlrm": os.path.join(HERE, "data", "configs")}


def config(name):
    with open(os.path.join(CONFIGS[name], name + ".json")) as f:
        return json.load(f)


# dot, dim 16: bottom 13-512-256-64-16 = 6,656 + 131,072 + 16,384 + 1,024 =
# 155,136 multiply-adds; top 367-512-256-1 = 187,904 + 131,072 + 256 =
# 319,232; 351 pairs x 16 = 5,616. Sum 479,984; x 6 = 2,879,904 FLOPs.
# cross, dim 128: bottom 13-512-256-128 = 6,656 + 131,072 + 32,768 = 170,496; three
# cross layers 3,456^2 = 35,831,808; top 3456-1024-1024-512-256-1 =
# 3,538,944 + 1,048,576 + 524,288 + 131,072 + 256 = 5,243,136.
# Sum 41,245,440; x 6 = 247,472,640 FLOPs.
@pytest.mark.parametrize("name, flops", [
    ("mid-dlrm", 2_879_904), ("dlrmdcn-fullrank-d128", 247_472_640)])
def test_flops_per_example(name, flops):
    assert work.flops_per_example(config(name), {"batch": 1}) == flops


# weights 12 B each: 474,368 (dot), 41,245,440 (cross). Activations
# 4 B x batch x sum(3 in + 2 out): dot 4,231 + 4,943 = 9,174;
# cross 4,135 + 51,840 + 24,450 = 80,425.
@pytest.mark.parametrize("name, batch, nbytes", [
    ("mid-dlrm", 2048, 12 * 474_368 + 4 * 2048 * 9_174),
    ("dlrmdcn-fullrank-d128", 8192, 12 * 41_245_440 + 4 * 8192 * 80_425)])
def test_dense_min_bytes(name, batch, nbytes):
    assert work.dense_min_bytes_per_step(config(name),
                                         {"batch": batch}) == nbytes


# key gather + claim 8 B, row read + written 2 x 4 D, Adagrad accumulator
# read + written 2 x 4 D, fused metadata 2 x 12 B.
@pytest.mark.parametrize("name, nbytes", [
    ("mid-dlrm", 8 + 128 + 128 + 24),
    ("dlrmdcn-fullrank-d128", 8 + 1024 + 1024 + 24)])
def test_engine_bytes_per_unique(name, nbytes):
    assert work.engine_bytes_per_unique(config(name)) == nbytes


def test_which_bound_applies():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ms, bound = dense_roofline.least_ms(work, config("mid-dlrm"),
                                        {"batch": 2048}, 2048, peaks)
    assert bound == "bandwidth" and ms == pytest.approx(0.09871, rel=1e-3)
    ms, bound = dense_roofline.least_ms(
        work, config("dlrmdcn-fullrank-d128"), {"batch": 8192}, 8192, peaks)
    assert bound == "compute" and ms == pytest.approx(10.291, rel=1e-3)
