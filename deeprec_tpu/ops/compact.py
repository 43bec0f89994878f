"""Scatter-free masked-row compaction at a static budget.

A prefix-sum / searchsorted index compaction: the general device-side
primitive for "collect the rows where mask is True without a sort and
without a data-dependent shape". The incremental-checkpoint exporter
(training/checkpoint.py) compacts dirty rows with it ON DEVICE, and the
multi-tier migration extractor (embedding/multi_tier.py) sizes its
exports with `quantize_rows` — the device->host transfer then scales with
the selected fraction, not the table capacity, which is the whole point of
taking checkpoint/migration traffic off the training stall path.

Contract:

  * `size` is STATIC. `rank_compact(mask, size)` returns the indices of
    the first `size` True positions of `mask` in ASCENDING index order
    (-1 padding past the count) — the same ordering `np.nonzero` gives the
    legacy host-side exporter, so compacted exports are byte-identical to
    the host-masked ones after truncation.
  * Everything is cumsum + searchsorted + gathers. That form was chosen on
    a CPU, where a scatter measured ~50x a gather. On a TPU the
    `searchsorted` is the cost: log2(C) dependent passes of one scalar
    gather a query (10 ns a query a pass on a v5e, PERF.md, PR 36), which
    is why the train step's dedup no longer compacts through here
    (ops/dedup.py packs its unique ids with a sort). A save runs it once
    a table, off the step's path.
  * `quantize_rows` buckets a measured count to a power of two so drift
    in the dirty fraction re-traces at most log2(C) times per table, the
    same never-recompile posture as the dedup budget grid.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def quantize_rows(n: int, capacity: int, floor: int = 64) -> int:
    """Static row budget for a measured count `n`: next power of two, at
    least `floor` (tiny exports share one executable), never beyond
    `capacity` (a full table needs no padding)."""
    e = max(next_pow2(max(int(n), 1)), floor)
    return min(e, int(capacity)) if capacity else e


def rank_compact(
    mask: jnp.ndarray, size: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense indices of `mask`'s True positions, at static length `size`.

    Returns `(idx [size] int32, n [] int32, rank [C] int32)`:
      * `idx[j]` is the index of the (j+1)-th True position (ascending),
        -1 once j >= n; positions past `size` are silently truncated —
        size the budget from a count read when that matters.
      * `n` is the total True count (NOT clipped to `size`).
      * `rank` is the inclusive prefix sum (`rank[i]` = number of True
        positions at or before i) — callers that need the inverse map
        reuse it for free.
    """
    rank = jnp.cumsum(mask.astype(jnp.int32))
    n = rank[-1]
    j = jnp.arange(1, size + 1, dtype=jnp.int32)
    sel = jnp.searchsorted(rank, j, side="left").astype(jnp.int32)
    idx = jnp.where(j <= n, sel, -1)
    return idx, n, rank
