"""Device dedup at a static unique budget, by sorting.

With the default U = N every op downstream of the dedup — probe, embedding
gather, freq/version/dirty scatters, `_init_rows`, the backward
segment-sum — runs at batch size rather than unique-id size; on
zipf-skewed recsys batches that is a multi-x waste. The budget (`size`)
cuts that width, and `dedup_at_budget` finds the unique ids at it with
three lane-parallel sorts and a prefix sum: a sort by (mixed hash, id)
puts equal ids side by side, a prefix sum over the groups' first elements
ranks them, a sort on the permutation carries every position's rank back
to input order, and a sort on "is a group's first element" packs the
unique ids to the front; a group's count is the distance to the next
group's start. No loop, no scratch table, no per-id gather or scatter.

Why sorts (PERF.md section 6, PR 36; one v5e chip, 26 tables x 8,192 ids
under `vmap`, device time from a trace): the three sorts and the prefix
sums take 0.40 ms, whatever the budget and however many ids repeat. What
stood here until PR 36 — an open-addressing claim race over a scratch of
4 (N + 1) slots (`lax.while_loop` of gather / claim-scatter / re-gather)
and a prefix-sum + 17-pass `searchsorted` compaction of that scratch —
took 36.8 ms on zipf ids and 90.9 ms on uniform ones, over half of both
DLRM cells' steps, every access of it a per-id scalar one at 5-13 ns. That
form had been chosen on a CPU, where it beat `jnp.unique` 1.45-1.89x and
where the sort form is about 3x slower than it; no deployment trains
there. Within the sort form, on the same chip: with `inverse` brought
back by a permutation scatter instead of the second sort the whole dedup
takes 1.28 ms for 0.40, with the unique ids packed by scatters instead of
the third sort 2.32 ms; the id as second key of the first sort costs
0.002 ms, stable sorts 0.15 ms more (none is needed: every tie is between
equal ids).

Budget contract (`dedup_at_budget`):

  * `size` is STATIC — the returned arrays are `uids [size]`,
    `counts [size]`, plus `inverse [N]` and a scalar `overflow`.
  * `uids[0]` is RESERVED for the sentinel: padding positions and ids that
    did not win a budget slot point their `inverse` at 0, where
    `valid=False` downstream serves the admission-blocked default and the
    gradient mask drops their update — exactly the per-step degradation
    contract of the budgeted all2all (`ShardedTable`, `a2a_overflow`). At
    most `size - 1` real unique ids fit.
  * The real ids stand at `uids[1..n]` in the order of their mixed hash
    (ties by id): the same order on every backend, alone and under `vmap`.
    A batch over its budget loses the ids whose hash is largest — a
    pseudo-random set, never "the largest ids".
  * `overflow` counts the distinct ids past the budget — the same
    transient-counter contract as `insert_fails` / `a2a_overflow`; consume
    it at host cadence (`Trainer.update_budgets`) to widen the budget.

Everything is shape-static and built from vmap/scan-safe primitives, so it
runs unchanged inside the stacked-bundle `vmap`, the K-step `lax.scan`
dispatch loop and `shard_map`.
"""
from __future__ import annotations

import logging
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeprec_tpu.utils import hashing, scopes

logger = logging.getLogger("deeprec_tpu.dedup")

# Tables that already logged the U=N fallback (log once per table name).
_logged_full_fallback: set = set()


def _mult8(n: int) -> int:
    return max(8, ((int(n) + 7) // 8) * 8)


def resolve_size(budget: int, n: int) -> int:
    """uids-array size for a requested budget of `budget` real ids over a
    flattened batch of `n` positions: +1 for the reserved sentinel slot,
    rounded up to a VPU-friendly multiple of 8, and never beyond the
    no-overflow size (which is `n` real ids + the sentinel slot)."""
    full = _mult8(n + 1)
    return min(_mult8(max(int(budget), 1) + 1), full)


def log_full_fallback(name: str, n: int) -> None:
    """Record (once per table) that a lookup fell back to U = N — the
    full-batch `jnp.unique` whose downstream waste the budget exists to cut.
    Visible so the silent default never hides the cost again."""
    if name in _logged_full_fallback:
        return
    _logged_full_fallback.add(name)
    try:  # same counter family as the Pallas dispatch rejections
        from deeprec_tpu.obs.metrics import default_registry

        default_registry().counter(
            "deeprec_pallas_fallback",
            help="Pallas kernel dispatches that fell back to XLA, by cause",
            labels={"kernel": "dedup", "reason": "no_budget"},
        ).inc()
    except Exception:  # obs must never break the lookup path
        pass
    logger.info(
        "table %s: no unique budget resolved — dedup falls back to U=N=%d "
        "(every downstream op at batch size). Set "
        "TableConfig.unique_budget / SparseFeature.unique_budget or "
        "Trainer(unique_budget=...) to dedup at a budget.",
        name, n,
    )


def dedup_at_budget(
    flat: jnp.ndarray,
    size: int,
    *,
    sentinel,
    weights: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Deduplicate `flat` [N] into at most `size - 1` unique ids by sorting.

    Args:
      flat: [N] ids with padding already collapsed onto `sentinel`.
      size: static length of the returned unique arrays; index 0 is the
        reserved sentinel bucket (see module docstring).
      sentinel: the reserved never-a-real-id key (python int or scalar).
      weights: optional [N] int per-position weights for `counts`
        (default 1 each — occurrence counts). Sentinel positions never
        contribute.

    Returns `(uids [size], inverse [N] int32, counts [size] int32,
    overflow [] int32)` where `uids[inverse]` reconstructs every budgeted
    position and `inverse == 0` marks padding/overflow positions. Real
    ids stand at `uids[1..n]` in the order of their mixed hash.
    """
    N = flat.shape[0]
    sent = jnp.asarray(sentinel, flat.dtype)
    valid = flat != sent
    pos = jnp.arange(N, dtype=jnp.int32)

    # 1. Equal ids adjacent, the sentinel strictly last. The hash leads so
    # that a batch over its budget loses pseudo-random ids, not always the
    # largest (on a frequency-ranked vocabulary: the same cold ones); the
    # id is the second key because two ids may share a hash (folded int64
    # ids, and the one id whose hash is clamped below the sentinel's key).
    top = jnp.uint32(0xFFFFFFFF)
    h = hashing.mix32(hashing.fold64(flat))
    key = jnp.where(valid, jnp.minimum(h, top - 1), top)
    operands = (key, flat, pos)
    if weights is not None:
        operands += (jnp.where(valid, weights.astype(jnp.int32), 0),)
    _, ids_s, perm, *w_s = jax.lax.sort(operands, num_keys=2, is_stable=False)

    # 2. A group's head is its first element; a head's rank is its uid slot.
    real = ids_s != sent
    head = real & (ids_s != jnp.concatenate([sent[None], ids_s[:-1]]))
    rank = jnp.cumsum(head.astype(jnp.int32))
    overflow = jnp.maximum(rank[-1] - jnp.int32(size - 1), 0)

    # 3. Every position's slot, back in input order.
    slot = jnp.where(real & (rank < size), rank, 0)
    _, inverse = jax.lax.sort((perm, slot), num_keys=1, is_stable=False)

    # 4. The heads to the front, in order, each with the count (or weight)
    # that stands before its group: a group's count is the next head's
    # start less its own, and the last group's next start is the total.
    if w_s:
        starts, total = (jnp.cumsum(w_s[0]) - w_s[0],), w_s[0]
    else:  # the real ids are a prefix: a head's start is its position
        starts, total = (), valid
    total = jnp.sum(total, dtype=jnp.int32)
    ckey, ids_c, *start_c = jax.lax.sort(
        (jnp.where(head, pos, pos + N), ids_s) + starts,
        num_keys=1, is_stable=False,
    )
    is_head = ckey < N
    start_c = jnp.where(is_head, start_c[0] if start_c else ckey, total)

    def first(n, x, fill):  # `resolve_size` allows up to 8 slots over N
        return jnp.pad(x[:n], (0, max(n - N, 0)), constant_values=fill)

    uids = jnp.concatenate(
        [sent[None], first(size - 1, jnp.where(is_head, ids_c, sent), sent)])
    counts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.diff(first(size, start_c, total))])
    return uids, inverse, counts, overflow.astype(jnp.int32)


@scopes.scoped(scopes.ENGINE_ROUTE)
def route_ids(
    ids: jnp.ndarray,
    *,
    pad_value,
    sentinel,
    unique_size: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """The apply-independent ROUTING half of a lookup: flatten, collapse
    padding onto the sentinel, dedup (`dedup_at_budget` at `unique_size`,
    `sort_unique` at None). A pure function of the id batch — it reads NO table
    state — which is what lets the pipelined trainers hoist it (and, for
    sharded tables, the id exchange built on it) a full step ahead of the
    tables it will hit (docs/perf.md "in-step pipelining").

    Returns `(uids [U], inverse [ids.shape], counts [U], valid [U],
    overflow)` — overflow is None on the legacy sort path, a scalar int32
    under a budget. Shared by the single-table lookup front-end
    (`EmbeddingTable._route_ids`) and both sharded exchange paths
    (`ShardedTable.route`), which used to duplicate it.
    """
    flat = ids.reshape(-1)
    sent = jnp.asarray(sentinel, flat.dtype)
    flat = jnp.where(flat == jnp.asarray(pad_value, flat.dtype), sent, flat)
    if unique_size is None:
        uids, inverse, counts = sort_unique(
            flat, flat.shape[0], sentinel=sentinel
        )
        overflow = None
    else:
        uids, inverse, counts, overflow = dedup_at_budget(
            flat, unique_size, sentinel=sentinel
        )
    valid = uids != sent
    return uids, inverse.reshape(ids.shape), counts, valid, overflow


def sort_unique(
    flat: jnp.ndarray, size: int, *, sentinel
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`jnp.unique` at a static size with the table's sentinel/counts
    conventions — the U=N path of eval and serving lookups, and the
    reference curve for `tools/bench_dedup.py`. Note its budget semantics
    are WEAKER than `dedup_at_budget`: past-`size` uniques are silently
    truncated with an undefined inverse, which is why `dedup_at_budget`
    (defined overflow) is the one budgets route through."""
    sent = jnp.asarray(sentinel, flat.dtype)
    uids, inverse, counts = jnp.unique(
        flat, size=size, fill_value=sent, return_inverse=True,
        return_counts=True,
    )
    valid = uids != sent
    counts = jnp.where(valid, counts, 0).astype(jnp.int32)
    return uids, inverse.astype(jnp.int32), counts


def auto_budget_fraction(ema_fraction: float, *, slack: float = 1.5,
                         grid: int = 16) -> float:
    """Quantize an EMA'd measured unique fraction into the budget grid:
    apply the safety slack, then round UP to the next 1/`grid` bucket so
    step-to-step EMA drift inside a bucket never recompiles the step."""
    f = min(1.0, max(0.0, ema_fraction) * slack)
    return min(1.0, math.ceil(f * grid - 1e-9) / grid)
