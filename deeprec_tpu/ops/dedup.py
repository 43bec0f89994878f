"""Hash-based device dedup at a static unique budget.

`jnp.unique(size=U)` is sort-based: O(N log N) compare/exchange passes over
the full flattened batch, and with the default U = N every downstream op —
probe, embedding gather, freq/version/dirty scatters, `_init_rows`, the
backward segment-sum — runs at batch size rather than unique-id size. On
zipf-skewed recsys batches that is a multi-x waste (docs/perf.md charges
~25% of the CPU step to "probe bookkeeping, unique, combiners").

This module replaces the sort with the same vectorized open-addressing
claim-race probe the embedding table already uses for its own slots
(the claim loop of `EmbeddingTable._probe`): every position gathers its scratch-slot
candidate, first-comers claim empty slots via a batched scatter, losers of
a claim race advance one probe offset. The loop is a `lax.while_loop` of
pure gathers/scatters — O(N · expected-probes) with expected-probes ~1-2
at the <=50% scratch load the sizing below guarantees. No sort anywhere.

Budget contract (`hash_dedup`):

  * `size` is STATIC — the returned arrays are `uids [size]`,
    `counts [size]`, plus `inverse [N]` and a scalar `overflow`.
  * `uids[0]` is RESERVED for the sentinel: padding positions and ids that
    did not win a budget slot point their `inverse` at 0, where
    `valid=False` downstream serves the admission-blocked default and the
    gradient mask drops their update — exactly the per-step degradation
    contract of the budgeted all2all (`ShardedTable`, `a2a_overflow`). At
    most `size - 1` real unique ids fit.
  * `overflow` counts the distinct ids compacted out past the budget plus
    any positions whose probe never resolved (near-impossible at the
    default scratch sizing) — the same transient-counter contract as
    `insert_fails` / `a2a_overflow`; consume it at host cadence
    (`Trainer.update_budgets`) to widen the budget.

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


def next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


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
    full-batch sort-unique whose downstream waste the budget exists to cut.
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
        "(sort-based, every downstream op at batch size). Set "
        "TableConfig.unique_budget / SparseFeature.unique_budget or "
        "Trainer(unique_budget=...) to engage the hash dedup engine.",
        name, n,
    )


def scratch_size(n: int) -> int:
    """Scratch-table size for an N-position dedup: the next power of two
    >= 4·(N+1), so even an all-distinct batch loads the table at <=25% and
    linear-probe chains stay short. The loop cost is per-ITERATION (one
    claim scatter over all N lanes — the dominant primitive on every
    backend), so a wider scratch that removes one probe round pays for its
    extra int32 rows many times over (measured: 5 -> 4 rounds at N=53k)."""
    return next_pow2(4 * (int(n) + 1))


def hash_dedup(
    flat: jnp.ndarray,
    size: int,
    *,
    sentinel,
    weights: Optional[jnp.ndarray] = None,
    max_probes: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Deduplicate `flat` [N] into at most `size - 1` unique ids, O(N).

    Args:
      flat: [N] ids with padding already collapsed onto `sentinel`.
      size: static length of the returned unique arrays; index 0 is the
        reserved sentinel bucket (see module docstring).
      sentinel: the reserved never-a-real-id key (python int or scalar).
      weights: optional [N] int per-position weights for `counts`
        (default 1 each — occurrence counts). Sentinel positions never
        contribute.
      max_probes: probe-chain bound; unresolved positions count as
        overflow.

    Returns `(uids [size], inverse [N] int32, counts [size] int32,
    overflow [] int32)` where `uids[inverse]` reconstructs every budgeted
    position and `inverse == 0` marks padding/overflow positions.
    """
    N = flat.shape[0]
    sent = jnp.asarray(sentinel, flat.dtype)
    S = scratch_size(N)
    mask_s = jnp.uint32(S - 1)
    h = hashing.mix32(hashing.fold64(flat))
    valid = flat != sent

    scratch0 = jnp.full((S,), sent, flat.dtype)
    slot0 = jnp.full((N,), -1, jnp.int32)

    def cond(carry):
        step, pending, *_ = carry
        return jnp.logical_and(step < max_probes, jnp.any(pending))

    def body(carry):
        step, pending, slot, scratch = carry
        pos = ((h + jnp.uint32(step)) & mask_s).astype(jnp.int32)  # [N]
        k = scratch[pos]
        hit = pending & (k == flat)
        slot = jnp.where(hit, pos, slot)
        pending = pending & ~hit
        # Claim race on empty scratch slots: scatter all claimants, the
        # re-gather reveals the one winner; losers advance a probe offset.
        # (The fused step kernel — ops/fused_lookup.fused_sparse_forward —
        # replaces this whole O(N)-lane scatter round with a sequential
        # in-VMEM slot write per id, so the ~50x-a-gather cost below never
        # appears on the fused path.)
        want = pending & (k == sent)
        claim_pos = jnp.where(want, pos, S)  # S = out of bounds -> dropped
        scratch = scratch.at[claim_pos].set(flat, mode="drop")
        won = want & (scratch[pos] == flat)
        slot = jnp.where(won, pos, slot)
        pending = pending & ~won
        return step + 1, pending, slot, scratch

    _, failed, slot, scratch = jax.lax.while_loop(
        cond, body, (jnp.int32(0), valid, slot0, scratch0)
    )

    # Budget compaction: the j-th occupied scratch slot (slot order) takes
    # dense index j in 1..size-1; the rest compact out as overflow.
    # Deliberately scatter-free — the shared prefix-sum + searchsorted
    # compaction (ops/compact.py, also behind the incremental-checkpoint
    # dirty export) — because scatter is the expensive primitive here (an
    # [S]-lane scatter measured ~50x a gather on CPU); the one remaining
    # scatter is the [N]-lane counts segment-add.
    from deeprec_tpu.ops.compact import rank_compact

    occ = scratch != sent  # [S]
    sel, n_occ, rank = rank_compact(occ, size - 1)
    uids_tail = jnp.where(
        sel >= 0, scratch.at[sel].get(mode="clip"), sent
    )
    uids = jnp.concatenate([jnp.full((1,), sent, flat.dtype), uids_tail])

    pos_ok = valid & (slot >= 0)
    r = rank.at[jnp.where(pos_ok, slot, 0)].get(mode="clip")  # lane's rank
    budgeted = pos_ok & (r < size)
    inverse = jnp.where(budgeted, r, 0).astype(jnp.int32)

    w = (
        jnp.ones((N,), jnp.int32)
        if weights is None
        else weights.astype(jnp.int32)
    )
    counts = (
        jnp.zeros((size,), jnp.int32)
        .at[jnp.where(budgeted, inverse, size)]
        .add(w, mode="drop")
    )
    overflow = (
        jnp.maximum(n_occ - jnp.int32(size - 1), 0) + jnp.sum(failed)
    ).astype(jnp.int32)
    return uids, inverse, counts, overflow


@scopes.scoped(scopes.ENGINE_ROUTE)
def route_ids(
    ids: jnp.ndarray,
    *,
    pad_value,
    sentinel,
    unique_size: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """The apply-independent ROUTING half of a lookup: flatten, collapse
    padding onto the sentinel, dedup (hash engine at `unique_size`, legacy
    sort at None). A pure function of the id batch — it reads NO table
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
        uids, inverse, counts, overflow = hash_dedup(
            flat, unique_size, sentinel=sentinel
        )
    valid = uids != sent
    return uids, inverse.reshape(ids.shape), counts, valid, overflow


def sort_unique(
    flat: jnp.ndarray, size: int, *, sentinel
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The legacy sort-based dedup (`jnp.unique` at a static size) with the
    table's sentinel/counts conventions — kept as the U=N fallback and as
    the reference curve for `tools/bench_dedup.py`. Note its budget
    semantics are WEAKER than `hash_dedup`: past-`size` uniques are
    silently truncated with an undefined inverse, which is why the hash
    engine (defined overflow) is the one budgets route through."""
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
