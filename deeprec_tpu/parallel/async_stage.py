"""Async embedding stage: stale-by-one decoupling of the embedding exchange
from dense compute.

DeepRec's AsyncEmbeddingStage (reference
tensorflow/python/training/async_embedding_stage.py, enabled by
config.proto:328 do_async_embedding) splits the graph at the embedding
boundary and runs the lookup subgraph in a pipeline stage, so the PS
round-trip for batch t+1 overlaps the dense compute of batch t; the model
consumes embeddings that are one step stale.

The TPU translation keeps the pipeline INSIDE one jitted step instead of
splitting the graph across threads. Each async step, in data-flow order:

  1. dense fwd/bwd on the CARRIED embeddings of batch t-1 (from AsyncState)
  2. collective lookup/exchange for batch t against the step-start tables
     — data-independent of (1), so XLA overlaps the all2all/allgather with
     the dense matmuls; this is the latency hiding the reference buys with
     its stage thread
  3. sparse-apply of batch t-1's gradients (after (1) and (2))
  4. dense optimizer update

Semantics (documented staleness, matching the reference):
  * the model sees embeddings fetched one step earlier;
  * sparse gradients are applied one step late, after the next batch's
    inserts (safe: inserts only claim empty slots, so the carried slot_ix
    stay valid — eviction/maintain() invalidates pending state and must be
    followed by `bootstrap()` on the next batch).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deeprec_tpu.parallel.trainer import ShardedTrainer
from deeprec_tpu.training.trainer import PipelineCarry, TrainState
from deeprec_tpu.utils import scopes

# The stale-by-one carry IS the generic pipeline carry (training/trainer.py):
# TrainState + one batch's prefetched lookup. The exact pipelined scan
# threads the same structure through its scan carry; the async stage is the
# degenerate (stale) version that finishes the lookup BEFORE the previous
# apply instead of after it.
AsyncState = PipelineCarry


class AsyncShardedTrainer(ShardedTrainer):
    """ShardedTrainer with the stale-by-one async embedding stage.

    Usage:
        astate = trainer.bootstrap(trainer.init(0), first_batch)
        for batch in batches:                    # feed batch t
            astate, mets = trainer.train_step_async(astate, batch)
        # mets at step t refer to batch t-1 (pipeline latency of one step)

    After maintain()/evict_tables() on astate.inner, call bootstrap() again:
    those rebuild tables and invalidate the carried slot indices.
    """

    def _make_jits(self):
        super()._make_jits()
        on = self._on_mesh
        self._bootstrap_jit = jax.jit(on(self._bootstrap_body, fills_carry=True))  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps
        self._async_step = jax.jit(on(self._async_body), donate_argnums=0)  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps
        self._async_steps = jax.jit(on(self._async_scan, stacked=True), donate_argnums=0)  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps

    def _apply_one(self, b, state, res, grad, step, lr):
        # The stale-by-one apply consumes batch t-1's lookup result AFTER
        # batch t's lookup (and, across the scan, after t-1's own apply on
        # overlapping rows): the carried forward residual predates writes to
        # the same rows, so the apply must RE-GATHER (reuse_rows=False) —
        # and re-stamp version/dirty (stamp_meta=True), since the rows'
        # lookup-time stamps are a step old and a checkpoint's dirty-clear
        # may have landed in between.
        return self.sharded[b.name].apply_gradients(
            state, self.sparse_opt, res, grad, step=step, lr=lr,
            grad_averaging=self.grad_averaging,
            reuse_rows=False, stamp_meta=True,
        )

    # --------------------------------------------------------- bootstrap

    def bootstrap(self, state: TrainState, first_batch) -> AsyncState:
        """Fill the pipeline: lookup/exchange first_batch with no dense
        compute. The first train_step_async then consumes it."""
        return self._bootstrap_jit(state, first_batch)[0]

    def _bootstrap_body(self, state: TrainState, batch):
        """Per shard (inside `_on_mesh`): (the filled carry, no metrics).
        keep_rows=False: the stale apply never reuses the forward residual
        (reuse_rows=False above), so the carried results drop the
        owner-side [O, D] row buffer instead of hauling it across
        dispatches and through the K-step scan carry."""
        return self._pipe_prologue(state, batch, keep_rows=False), {}

    # ------------------------------------------------------------- step

    def train_step_async(self, astate: AsyncState, batch, lr=None):
        with self._step_span():
            lr = jnp.asarray(
                self.sparse_opt.lr if lr is None else lr, jnp.float32
            )
            return self._async_step(astate, batch, lr)

    def train_steps_async(self, astate: AsyncState, batches, lr=None):
        """K inner async steps per staged dispatch — the multi-step device
        loop composed with the stale-by-one embedding stage. `batches` is a
        list/tuple of K batch dicts (stacked + mesh-placed here) or a
        pre-placed [K, ...] pytree. Returns (astate, metrics[K]); metrics
        at inner step t refer to batch t-1, as in `train_step_async`."""
        from deeprec_tpu.parallel.mesh import shard_batch
        from deeprec_tpu.training.trainer import stack_batches

        with self._step_span():
            if isinstance(batches, (list, tuple)):
                batches = shard_batch(
                    self.mesh, stack_batches(batches), axis=self.axis,
                    stacked=True,
                )
            lr = jnp.asarray(
                self.sparse_opt.lr if lr is None else lr, jnp.float32
            )
            return self._async_steps(astate, batches, lr)

    def _async_body(self, astate: AsyncState, batch_t, lr):
        """One async step on per-shard values (runs inside `_on_mesh`).
        Shared by the single-step path and the K-step scan."""
        state = astate.inner
        step = state.step

        # (1) dense fwd/bwd on the STALE embeddings (batch t-1)
        g_dense, g_embs, mets = self._fwd_bwd(
            state.dense, astate.views, astate.batch
        )

        # (2) exchange/lookup for batch t — reads the step-start tables,
        # no data dependency on (1): XLA overlaps it with the matmuls.
        # Expressed through the split-phase lookup; finish runs BEFORE the
        # stale apply below (that pre-apply gather IS the documented
        # staleness — the exact pipelined scan moves it after the apply).
        # keep_rows=False: the carried results never reuse the residual.
        tables = self._tables_in(state.tables)
        with scopes.scope(self._lookup_phase):
            routes_t = self._route_all(batch_t, True)
            tables, pending_t = self._resolve_all(
                tables, routes_t, step, True
            )
            views_t, res_t = self._finish_all(
                tables, pending_t, batch_t, True, keep_rows=False
            )

        # (3) stale-apply batch t-1's sparse grads
        with scopes.scope(scopes.PHASE_SPARSE_APPLY):
            tables = self._apply_all(
                tables, astate.bundle_res, g_embs, step, lr
            )

        # (4) dense update
        return (
            AsyncState(inner=self._dense_update(state, g_dense, tables,
                                                mets),
                       batch=batch_t, views=views_t, bundle_res=res_t),
            mets,
        )

    def _async_scan(self, astate: AsyncState, batches, lr):
        """K async steps per dispatch: lax.scan of `_async_body` inside one
        mapped region, threading the pipelined AsyncState (carried batch,
        views and lookup results of step t-1) through the scan carry — the
        stale-by-one semantics of every inner step are exactly those of K
        sequential `train_step_async` calls. Batches carry a leading
        unsharded [K] axis (`shard_batch(..., stacked=True)`)."""

        def body(astate, batch_t):
            return self._async_body(astate, batch_t, lr)

        return jax.lax.scan(body, astate, batches)
