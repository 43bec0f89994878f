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

from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from deeprec_tpu.parallel.trainer import ShardedTrainer
from deeprec_tpu.training import metrics as M
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
        self._bootstrap_jit = jax.jit(self._bootstrap_impl)  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps
        self._async_step = jax.jit(self._async_impl, donate_argnums=0)  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps
        self._async_steps = jax.jit(self._async_steps_impl, donate_argnums=0)  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps

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
        return self._bootstrap_jit(state, first_batch)

    def _bootstrap_impl(self, state: TrainState, batch):
        state_spec, batch_spec = self._specs_for(state, batch)
        views_spec, res_spec, _ = self._carry_specs()

        @partial(
            jax.shard_map,
            mesh=self.mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, views_spec, res_spec),
            check_vma=False,
        )
        def run(state, batch):
            tables = self._squeeze_all(state.tables)
            # Split-phase lookup (route -> resolve -> finish) with
            # keep_rows=False: the stale apply never reuses the forward
            # residual (reuse_rows=False above), so the carried results
            # drop the owner-side [O, D] row buffer instead of hauling it
            # across dispatches and through the K-step scan carry.
            with scopes.scope(scopes.PHASE_LOOKUP_EXCHANGE):
                routes = self._route_all(batch, True)
                tables, pending = self._resolve_all(
                    tables, routes, state.step, True
                )
                views, bundle_res = self._finish_all(
                    tables, pending, batch, True, keep_rows=False
                )
            new_state = TrainState(
                step=state.step,
                tables={
                    bname: self._unsqueeze(bname, ts)
                    for bname, ts in tables.items()
                },
                dense=state.dense,
                opt_state=state.opt_state,
            )
            return new_state, views, bundle_res

        new_state, views, bundle_res = run(state, batch)
        return AsyncState(
            inner=new_state, batch=batch, views=views, bundle_res=bundle_res
        )

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
        """One async step on per-shard values (runs INSIDE shard_map).
        Shared by the single-step path and the K-step scan."""
        state = astate.inner
        step = state.step
        views = astate.views
        prev_batch = astate.batch

        # (1) dense fwd/bwd on the STALE embeddings (batch t-1)
        def loss_fn(dense, embs):
            inputs = self._build_inputs(embs, views, prev_batch)
            out = self.model.apply(dense, inputs, train=True)
            loss, out = self._loss_from_logits(out, prev_batch)
            return loss, out

        with scopes.scope(scopes.PHASE_DENSE_FWD_BWD):
            embs = {n: v[0].astype(jnp.float32) for n, v in views.items()}
            (loss, out), (g_dense, g_embs) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True
            )(state.dense, embs)
            g_dense = jax.lax.pmean(g_dense, self.axis)
            mets = {"loss": jax.lax.pmean(loss, self.axis)}
            if not isinstance(out, dict):
                probs = jax.nn.sigmoid(out)
                mets["accuracy"] = jax.lax.pmean(
                    M.accuracy(probs, prev_batch["label"]), self.axis
                )
            else:
                mets["accuracy"] = jnp.zeros(())

        # (2) exchange/lookup for batch t — reads the step-start tables,
        # no data dependency on (1): XLA overlaps it with the matmuls.
        # Expressed through the split-phase lookup; finish runs BEFORE the
        # stale apply below (that pre-apply gather IS the documented
        # staleness — the exact pipelined scan moves it after the apply).
        # keep_rows=False: the carried results never reuse the residual.
        tables = self._squeeze_all(state.tables)
        with scopes.scope(scopes.PHASE_LOOKUP_EXCHANGE):
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
        with scopes.scope(scopes.PHASE_DENSE_APPLY):
            updates, opt_state = self.dense_opt.update(
                g_dense, state.opt_state, state.dense
            )
            dense = optax.apply_updates(state.dense, updates)
            step = step + 1

        new_inner = TrainState(
            step=step,
            tables={
                bname: self._unsqueeze(bname, ts)
                for bname, ts in tables.items()
            },
            dense=dense,
            opt_state=opt_state,
        )
        return (
            AsyncState(inner=new_inner, batch=batch_t, views=views_t,
                       bundle_res=res_t),
            mets,
        )

    def _astate_spec(self, state_spec):
        views_spec, res_spec, prev_batch_spec = self._carry_specs()
        return AsyncState(
            inner=state_spec, batch=prev_batch_spec, views=views_spec,
            bundle_res=res_spec,
        )

    def _async_impl(self, astate: AsyncState, batch_t, lr):
        state_spec, batch_spec = self._specs_for(astate.inner, batch_t)
        astate_spec = self._astate_spec(state_spec)
        out_metric_spec = {"loss": P(), "accuracy": P()}

        @partial(
            jax.shard_map,
            mesh=self.mesh,
            in_specs=(astate_spec, batch_spec, P()),
            out_specs=(astate_spec, out_metric_spec),
            check_vma=False,
        )
        def run(astate, batch_t, lr):
            return self._async_body(astate, batch_t, lr)

        return run(astate, batch_t, lr)

    def _async_steps_impl(self, astate: AsyncState, batches, lr):
        """K async steps per dispatch: lax.scan of `_async_body` inside one
        shard_map, threading the pipelined AsyncState (carried batch, views
        and lookup results of step t-1) through the scan carry — the
        stale-by-one semantics of every inner step are exactly those of K
        sequential `train_step_async` calls. Batches carry a leading
        unsharded [K] axis (`shard_batch(..., stacked=True)`)."""
        state_spec, _ = self._specs_for(astate.inner, {})
        astate_spec = self._astate_spec(state_spec)
        batch_spec = jax.tree.map(lambda _: P(None, self.axis), batches)
        out_metric_spec = {"loss": P(), "accuracy": P()}

        @partial(
            jax.shard_map,
            mesh=self.mesh,
            in_specs=(astate_spec, batch_spec, P()),
            out_specs=(astate_spec, out_metric_spec),
            check_vma=False,
        )
        def run(astate, batches, lr):
            def body(astate, batch_t):
                return self._async_body(astate, batch_t, lr)

            return jax.lax.scan(body, astate, batches)

        return run(astate, batches, lr)
