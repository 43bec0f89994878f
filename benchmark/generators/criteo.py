"""The generator `criteo`: Criteo-shaped training batches, one id a field.

A traffic mix is a data file `traffic/<mix>.json` that names its generator
(`"generator": "criteo"`); the harness and the tools find this module by
that name (`harness.load_mix`) and reach it through the generators'
contract only: `check`, `make_batch`, `fill_steps`, `fill_batch`,
`filled_rows`, `examples` (PERF.md section 3). The laws are a copy of the program's
`data/synthetic.py::SyntheticCriteo` (bounded zipf by inverse CDF, uniform,
lognormal dense features, a noisy logistic label over hidden per-id weights)
with two changes that make it a yardstick. Batch `k` is a pure function of
`(seed, k)`, ids included, so any thread can make any batch and every seed
draws its own ids under the same law. And the hidden per-id weights are
hashed from the id instead of read from a `[fields, vocab]` matrix that took
a second and 200 MB to draw at set-up.

A mix also has fill batches (`fill_steps`, `fill_batch`): set-up drives them
through the timed step so that the window meets tables that hold every id of
the vocabulary, as a long-lived job's do, and not tables that are all but
empty. Fill batch j gives every field the next `min(unique_budget, batch)`
ids of its vocabulary, repeated to the batch's length, so no step passes the
budget.

Keys of a mix file of this generator:
  batch          examples per step
  num_cat        categorical fields (one id each: bags of 1)
  num_dense      numeric fields
  vocab          ids per field; field c draws from [c*vocab, (c+1)*vocab)
  id_law         "zipf" | "uniform"
  zipf_a         exponent of the bounded zipf (id_law == "zipf")
  unique_budget  what Trainer(unique_budget=) gets: see tools/budget.py
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

_REQUIRED = ("batch", "num_cat", "num_dense", "vocab", "id_law",
             "unique_budget")


def check(mix: Dict) -> None:
    """Raise ValueError where the mix lacks a key this generator needs or
    holds a value it cannot draw from."""
    name = mix.get("name")
    missing = [k for k in _REQUIRED if k not in mix]
    if missing:
        raise ValueError(f"traffic mix {name!r} lacks {missing}")
    if mix["id_law"] not in ("zipf", "uniform"):
        raise ValueError(f"traffic mix {name!r}: unknown id_law "
                         f"{mix['id_law']!r}")
    if mix["id_law"] == "zipf" and "zipf_a" not in mix:
        raise ValueError(f"traffic mix {name!r} lacks ['zipf_a']")
    if not 0 < mix["unique_budget"] <= mix["batch"]:
        raise ValueError(f"traffic mix {name!r}: a unique_budget of "
                         f"{mix['unique_budget']} for a batch of "
                         f"{mix['batch']}")


def examples(mix: Dict) -> int:
    """Examples a step: one row of the batch is one example."""
    return int(mix["batch"])


def filled_rows(mix: Dict) -> int:
    """Rows the tables hold once the fill batches went through: every id
    of every field's vocabulary."""
    return int(mix["num_cat"]) * int(mix["vocab"])


def _mix32(x: np.ndarray) -> np.ndarray:
    """murmur3 finalizer over uint32 (the hidden label weights' hash)."""
    with np.errstate(over="ignore"):
        x = x.astype(np.uint32)
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x85EBCA6B)
        x ^= x >> np.uint32(13)
        x *= np.uint32(0xC2B2AE35)
        x ^= x >> np.uint32(16)
    return x


def zipf_ranks(u: np.ndarray, vocab: int, a: float) -> np.ndarray:
    """Bounded zipf(a) ranks in [0, vocab) from uniforms, by inverse CDF."""
    if abs(a - 1.0) < 1e-6:
        ranks = np.floor(np.exp(u * np.log(vocab)))
    else:
        v = vocab ** (1.0 - a)
        ranks = np.floor((u * (v - 1.0) + 1.0) ** (1.0 / (1.0 - a)))
    return np.clip(ranks.astype(np.int64), 1, vocab) - 1


def draw_ids(mix: Dict, seed: int, k: int) -> np.ndarray:
    """[num_cat, batch] ids of batch k of the run `seed`, each row within
    its field's vocab."""
    rng = np.random.default_rng([int(seed), int(k), 1])
    shape = (mix["num_cat"], mix["batch"])
    vocab = int(mix["vocab"])
    if mix["id_law"] == "uniform":
        return rng.integers(0, vocab, size=shape, dtype=np.int64)
    return zipf_ranks(rng.random(shape), vocab, float(mix["zipf_a"]))


def _fill_width(mix: Dict) -> int:
    return min(int(mix["unique_budget"]), int(mix["batch"]))


def fill_steps(mix: Dict) -> int:
    """Batches that insert the whole vocabulary."""
    return -(-int(mix["vocab"]) // _fill_width(mix))


def fill_batch(mix: Dict, seed: int, j: int) -> Dict[str, np.ndarray]:
    """Fill batch j: ids j*U .. (j+1)*U - 1 of every field's vocabulary (U
    the fill's width), tiled to the batch; features and labels as in any
    other batch, from an index space of their own."""
    U = _fill_width(mix)
    row = (j * U + np.arange(mix["batch"], dtype=np.int64) % U) % mix["vocab"]
    return make_batch(mix, seed, 2 ** 30 + j,
                      np.broadcast_to(row, (mix["num_cat"], mix["batch"])))


def make_batch(mix: Dict, seed: int, k: int,
               ids: Optional[np.ndarray] = None) -> Dict[str, np.ndarray]:
    """Batch k of the run `seed`: I1.. [B,1] f32, C1.. [B] int32 (field c
    offset by c*vocab so tables see disjoint keys), label [B] f32. `ids`
    [num_cat, B] replaces the law's draw (the fill's batches)."""
    if ids is None:
        ids = draw_ids(mix, seed, k)
    n_cat, n_dense, B = mix["num_cat"], mix["num_dense"], mix["batch"]
    vocab = int(mix["vocab"])
    rng = np.random.default_rng([int(seed), int(k), 2])
    dense = rng.lognormal(0.0, 1.0, size=(B, n_dense)).astype(np.float32)
    keys = ids + (np.arange(n_cat, dtype=np.int64) * vocab)[:, None]
    # hidden weight of an id: unit-variance uniform hashed from its key
    w = (_mix32(keys ^ 0x9E3779B9) >> np.uint32(8)).astype(np.float32)
    w = (w * np.float32(2.0 / (1 << 24)) - 1.0) * np.float32(3.0 ** 0.5)
    dense_w = np.random.default_rng(12345).normal(0, 0.5, n_dense)
    logit = 0.3 * w.sum(axis=0) + 0.3 * (np.log1p(dense) @ dense_w)
    prob = 1.0 / (1.0 + np.exp(-(logit - logit.mean())))
    out: Dict[str, np.ndarray] = {
        "label": (rng.random(B) < prob).astype(np.float32)}
    for i in range(n_dense):
        out[f"I{i + 1}"] = dense[:, i:i + 1]
    for c in range(n_cat):
        out[f"C{c + 1}"] = keys[c].astype(np.int32)
    return out
