"""The generator `tokens`: packed token sequences under a bounded zipf law,
each position labelled with the token that follows it.

Keys of a mix file of this generator:
  batch          sequences a step
  seq_len        positions a sequence; a position is one example
  vocab          token ids, drawn from [0, vocab): the chip's slice
  zipf_a         exponent of the bounded zipf (rank r with weight r^-a)
  unique_budget  what Trainer(unique_budget=) gets: tools/budget.py
  pair_budget    the expert layer's static budget of (token, expert) pairs
                 held here, a layer: read by the builder, not by this file

Batch `k` is a pure function of `(seed, k)`: `tok` int32 [B, S] and `label`
int32 [B, S], the next token (the sequence is drawn one token longer than
it is fed). Sequences are drawn without document boundaries. A fill batch
gives the table the next `min(unique_budget, B x S)` ids of the vocabulary,
tiled over the positions, so that no step passes the budget and after
`fill_steps` of them the table holds every id.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

_REQUIRED = ("batch", "seq_len", "vocab", "zipf_a", "unique_budget")


def check(mix: Dict) -> None:
    missing = [k for k in _REQUIRED if k not in mix]
    if missing:
        raise ValueError(f"traffic mix {mix.get('name')!r} lacks {missing}")
    if not 0 < mix["unique_budget"] <= examples(mix):
        raise ValueError(f"traffic mix {mix.get('name')!r}: a unique_budget "
                         f"of {mix['unique_budget']} for {examples(mix)} "
                         "positions")


def examples(mix: Dict) -> int:
    """Examples a step: every position of every sequence predicts a token,
    so examples a second are tokens a second."""
    return int(mix["batch"]) * int(mix["seq_len"])


def filled_rows(mix: Dict) -> int:
    return int(mix["vocab"])


def _fill_width(mix: Dict) -> int:
    return min(int(mix["unique_budget"]), examples(mix))


def fill_steps(mix: Dict) -> int:
    return -(-int(mix["vocab"]) // _fill_width(mix))


def draw_tokens(mix: Dict, seed: int, k: int) -> np.ndarray:
    """[B, S + 1] token ids of batch k of the run `seed`: a bounded zipf by
    its inverse CDF, rank r the id r - 1."""
    rng = np.random.default_rng([int(seed), int(k), 1])
    u = rng.random((int(mix["batch"]), int(mix["seq_len"]) + 1))
    vocab, a = int(mix["vocab"]), float(mix["zipf_a"])
    v = vocab ** (1.0 - a)
    ranks = np.floor((u * (v - 1.0) + 1.0) ** (1.0 / (1.0 - a)))
    return np.clip(ranks.astype(np.int64), 1, vocab) - 1


def draw_ids(mix: Dict, seed: int, k: int) -> np.ndarray:
    """[1, B x S]: the ids the table is asked for in batch k (what
    tools/budget.py counts)."""
    return draw_tokens(mix, seed, k)[:, :-1].reshape(1, -1)


def make_batch(mix: Dict, seed: int, k: int,
               tokens: Optional[np.ndarray] = None) -> Dict[str, np.ndarray]:
    if tokens is None:
        tokens = draw_tokens(mix, seed, k)
    return {"tok": tokens[:, :-1].astype(np.int32),
            "label": tokens[:, 1:].astype(np.int32)}


def fill_batch(mix: Dict, seed: int, j: int) -> Dict[str, np.ndarray]:
    U, B, S = _fill_width(mix), int(mix["batch"]), int(mix["seq_len"])
    fed = ((j * U + np.arange(B * S, dtype=np.int64) % U)
           % mix["vocab"]).reshape(B, S)
    # the token after a sequence's last position is only ever a label
    return make_batch(mix, seed, 2 ** 30 + j,
                      np.concatenate([fed, fed[:, :1]], axis=1))
