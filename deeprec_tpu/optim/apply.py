"""Applying sparse gradients to a table — the KvResourceSparseApply* executor.

Pipeline (mirrors DeepRec's backward path, SURVEY.md §3.1): autodiff produces
gradients w.r.t. the *unique* gathered embeddings; this module gathers the
matching value/slot rows, runs the optimizer row-function, masks out invalid /
filter-blocked keys, and scatters everything back. One fused pass over [U, D].

U is whatever the dedup produced: the full flattened batch on the legacy
path, or the static unique BUDGET under the budgeted dedup
(ops/dedup.py) — the whole gather->update->scatter pass shrinks with it.
Budget-overflowed ids never reach here as rows: their positions point at
the reserved sentinel entry (uids[0], valid=False), which the `ok` mask
below drops exactly like a filter-blocked key.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp

from deeprec_tpu.embedding.table import EmbeddingTable, TableState, UniqueLookup
from deeprec_tpu.optim.sparse import SCALAR_PREFIX, SparseOptimizer


def ensure_slots(
    table: EmbeddingTable, state: TableState, opt: SparseOptimizer
) -> TableState:
    """Create the optimizer's slot arrays for this table (idempotent).

    The analog of slot-variable creation in DeepRec's optimizers
    (python/training/adam_async.py etc.), with slots packed next to values.
    """
    C, D = state.capacity, state.dim
    slots = dict(state.slots)
    for name, (shape, init) in opt.slot_specs(D).items():
        if name in slots:
            continue
        if name.startswith(SCALAR_PREFIX):
            slots[name] = jnp.full((1, 1), init, jnp.float32)
        else:
            # Per-row slots share the packed small-dim layout policy of the
            # values array (ops/packed.py, gated by cfg.packed): a [C, 1]
            # accumulator padded to 128 lanes would waste 128x HBM on TPU.
            (w,) = tuple(shape)
            P = table.pack_width(w, C)
            slots[name] = jnp.full((C // P, P * w), init, jnp.float32)
    return state.replace(slots=slots)


def apply_gradients(
    table: EmbeddingTable,
    state: TableState,
    opt: SparseOptimizer,
    res: UniqueLookup,
    grad_u: jnp.ndarray,  # [U, D] grads w.r.t. res.embeddings
    *,
    step: jnp.ndarray | int = 0,
    lr: Optional[jnp.ndarray | float] = None,
    grad_averaging: bool = False,
    reuse_rows: bool = False,
    stamp_meta: bool = True,
) -> TableState:
    """Update the touched rows of `state` in one compute→scatter pass.

    Traffic diet (docs/perf.md "traffic diet"), opted into by the trainer
    hot paths via `reuse_rows=True, stamp_meta=False`: the value rows this
    apply needs were already gathered by the same-step train lookup and
    ride in `res.rows` — reusing them deletes a whole [U, D] gather, and
    the lookup's fused metadata scatter already stamped version/dirty for
    every touched row, so the apply-side pair is redundant too.

    The diet is only valid when nothing wrote the touched value rows
    between the lookup that produced `res` and this apply, and when a
    same-step TRAIN lookup stamped the rows' metadata. The trainers
    enforce that precondition (and the shared-table / async paths where it
    fails keep these safe defaults — see Trainer._bundle_reuse_rows and
    AsyncShardedTrainer._apply_one); standalone callers get the legacy
    re-gather + re-stamp behavior, correct for every call pattern
    (repeated applies of one `res`, interleaved scatter_update, ...).
    """
    step = jnp.asarray(step, jnp.int32)
    lr = jnp.asarray(opt.lr if lr is None else lr, jnp.float32)

    ok = (res.slot_ix >= 0) & res.valid & res.admitted  # [U]
    safe_ix = jnp.where(ok, res.slot_ix, 0)
    drop_ix = jnp.where(ok, res.slot_ix, state.capacity)

    grad = grad_u.astype(jnp.float32)
    if grad_averaging:
        grad = grad / jnp.maximum(res.counts.astype(jnp.float32), 1.0)[:, None]

    if reuse_rows and res.rows.size:
        value = res.rows.astype(jnp.float32)
    else:
        value = table._gather(state.values, safe_ix, state.capacity).astype(
            jnp.float32
        )
    from deeprec_tpu.ops.packed import gather_rows_any, scatter_rows_any

    row_slots: Dict[str, jnp.ndarray] = {}
    for name, arr in state.slots.items():
        if name.startswith(SCALAR_PREFIX):
            row_slots[name] = arr  # [1, 1] per-table scalar, passed through
        else:
            row_slots[name] = gather_rows_any(
                arr, safe_ix, state.capacity,
                use_pallas=table.use_pallas,
                pair_kernels=table.pair_kernels,
            )

    new_value, new_slots = opt.update(value, row_slots, grad, res.counts, step, lr)

    # The values write-back goes through apply_rows_sr (packed-layout
    # aware): bf16 tables get stochastic rounding (plain round-to-nearest
    # silently drops updates smaller than ulp/2), f32 tables an exact
    # masked scatter; the Pallas DMA kernel serves tables opted into it.
    values = table._scatter(
        state.values, jnp.where(ok, res.slot_ix, -1), new_value,
        state.capacity, seed=step,
    )
    slots = dict(state.slots)
    for name, rows in new_slots.items():
        if name.startswith(SCALAR_PREFIX):
            slots[name] = rows
        else:
            slots[name] = scatter_rows_any(
                state.slots[name], jnp.where(ok, res.slot_ix, -1), rows,
                state.capacity, seed=step,
                use_pallas=table.use_pallas,
                pair_kernels=table.pair_kernels,
            )
    if stamp_meta:
        from deeprec_tpu.embedding.table import META_DIRTY, META_VERSION

        meta = state.meta.at[META_VERSION, drop_ix].set(step, mode="drop")
        meta = meta.at[META_DIRTY, drop_ix].set(1, mode="drop")
        return state.replace(values=values, slots=slots, meta=meta)
    return state.replace(values=values, slots=slots)


def apply_bag_gradients(
    table: EmbeddingTable,
    state: TableState,
    opt: SparseOptimizer,
    res,  # ops.fused_lookup.FusedBags from a matching bag_forward
    grad_out: jnp.ndarray,  # [B, D] grads w.r.t. res.out
    row_ix: jnp.ndarray,  # [B, L] resolved slot indices fed to bag_forward
    *,
    combiner: str = "mean",
    step: jnp.ndarray | int = 0,
    lr: Optional[jnp.ndarray | float] = None,
    grad_averaging: bool = False,
    interpret: bool = False,
    stamp_meta: bool = True,
) -> TableState:
    """The fused-step analog of apply_gradients: one pass segment-sums the
    per-bag grads [B, D] into unique-row space and applies the optimizer
    update fused into the scatter (ops/fused_lookup.fused_sparse_backward),
    so per-row grads never materialize outside the kernel.

    `res` must come from `table.bag_forward(state, row_ix, ...)` with the
    SAME combiner; `row_ix` is the [B, L] resolved slot indices (< 0 = pad)
    that produced it. Requires a fusable optimizer (no scalar slots, all
    slots [dim]-shaped — fused_lookup.fusable_optimizer) and the unpacked
    row layout; callers outside that envelope use apply_gradients.
    """
    from deeprec_tpu.ops import fused_lookup as fl
    from deeprec_tpu.ops.packed import is_unpacked

    if not fl.fusable_optimizer(opt, state.dim):
        raise NotImplementedError(
            f"apply_bag_gradients: optimizer {type(opt).__name__} has "
            "scalar or non-[dim] slots; use apply_gradients"
        )
    if not is_unpacked(state.values, state.capacity):
        raise NotImplementedError(
            "apply_bag_gradients: packed small-dim layouts keep the "
            "split-phase apply_gradients path"
        )
    values, slots = fl.fused_sparse_backward(
        state.values, dict(state.slots), grad_out, row_ix, res, opt,
        combiner=combiner, step=step, lr=lr, seed=step,
        grad_averaging=grad_averaging, interpret=interpret,
        use_pallas=table.fused_step,
    )
    if stamp_meta:
        from deeprec_tpu.embedding.table import META_DIRTY, META_VERSION

        # uids[0] is the reserved sentinel (-1) and overflow rows stay
        # negative — route both to the dropped C lane.
        drop_ix = jnp.where(res.uids >= 0, res.uids, state.capacity)
        meta = state.meta.at[META_VERSION, drop_ix].set(step, mode="drop")
        meta = meta.at[META_DIRTY, drop_ix].set(1, mode="drop")
        return state.replace(values=values, slots=slots, meta=meta)
    return state.replace(values=values, slots=slots)
