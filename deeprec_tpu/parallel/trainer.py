"""SPMD trainer: whole-train-step shard_map over the `data` axis.

One compiled program per step does: batch-parallel dense forward/backward
(grads psum'd over ICI), hash-sharded table lookups (all_gather ids +
reduce-scatter embeddings) and owner-side fused sparse applies. This replaces
DeepRec's worker/PS process split (SURVEY.md §3.2) — there is no separate
parameter process; the "PS" is the sharded table arrays resident in each
chip's HBM, and the "RPC" is compiled collectives.

Bundled (GroupEmbedding) tables vmap the collective lookup over the table
axis, so the ids of N tables ride ONE batched all_gather and their embeddings
ONE batched reduce-scatter — the same batching trick as DeepRec's grouped SOK
lookup (docs/docs_en/Group-Embedding.md).

Usable identically on a real TPU mesh or on N virtual CPU devices
(XLA_FLAGS=--xla_force_host_platform_device_count=N) — the reference tests
distributed behavior with in-process fake clusters the same way (SURVEY §4).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeprec_tpu.embedding.table import EmbeddingTable
from deeprec_tpu.optim.apply import ensure_slots
from deeprec_tpu.parallel import placement as placement_lib
from deeprec_tpu.parallel.mesh import DATA_AXIS
from deeprec_tpu.parallel.placement import BundlePlan
from deeprec_tpu.parallel.sharded import ShardedTable
from deeprec_tpu.training.trainer import (
    PipelineCarry,
    Trainer,
    TrainState,
    stack_batches,
)
from deeprec_tpu.utils import scopes


def _local_cfg(cfg, num_shards: int):
    assert cfg.capacity % num_shards == 0, (
        f"table {cfg.name}: capacity {cfg.capacity} not divisible by mesh size"
    )
    return dataclasses.replace(cfg, capacity=cfg.capacity // num_shards)


class ShardedTrainer(Trainer):
    """Drop-in Trainer over a device mesh: tables hash-sharded, batch split."""

    def __init__(
        self,
        model,
        sparse_opt,
        dense_opt: Optional[optax.GradientTransformation] = None,
        mesh: Optional[Mesh] = None,
        axis: str = DATA_AXIS,
        grad_averaging: bool = False,
        comm: str = "allgather",  # "a2a" (budgeted, SOK path) | "hier" (2-D)
        remat: bool = False,
        a2a_slack: float = 2.0,
        unique_budget=None,
        pipeline_mode: str = "off",
        pipeline_chunks: int = 4,
        placement: str = "uniform",
        placement_hot_budget: int = 64,
        replan: Optional["placement_lib.ReplanConfig"] = None,
        hier_group_factor: Optional[float] = None,
    ):
        from deeprec_tpu.parallel.costmodel import PlacementCostModel
        from deeprec_tpu.parallel.mesh import make_mesh, mesh_batch_axes

        self.mesh = mesh or make_mesh(axis=axis)
        # The axis spec every P()/collective in the step program uses: the
        # plain data axis of a 1-D mesh, or the (inter, intra) tuple of a
        # make_mesh_2d mesh — flat collectives over the tuple enumerate
        # devices in 1-D host-major rank order, so the allgather/a2a
        # programs (and hash ownership, and checkpoints) are identical
        # across mesh shapes. comm="hier" splits the exchange across the
        # two tiers instead (docs/multihost.md).
        self.axis = mesh_batch_axes(self.mesh)
        self.num_shards = self.mesh.devices.size
        names = tuple(self.mesh.axis_names)
        self.inter_size = self.mesh.shape[names[0]] if len(names) == 2 else None
        self.intra_size = self.mesh.shape[names[1]] if len(names) == 2 else None
        self.hier_group_factor = hier_group_factor
        if comm == "hier" and len(names) != 2:
            raise ValueError(
                "comm='hier' needs a 2-D mesh (make_mesh_2d); "
                f"got axes {names}"
            )
        # Skew-aware table placement (parallel/placement.py): "uniform"
        # keeps the legacy hash_shard routing; "plan" arms the
        # drift-driven replanner — maintain() runs maybe_replan() next to
        # update_budgets, which fires the cost-model placer only when the
        # live per-shard imbalance telemetry breaches the ReplanConfig
        # trigger (hysteresis + cooldown) AND the modeled gain amortizes
        # the modeled migration within the horizon. Plans always start
        # uniform; update_placement(force=True) also works under
        # "uniform" for one-shot manual placement.
        if placement not in ("uniform", "plan"):
            raise ValueError(
                f"placement must be 'uniform' or 'plan', got {placement!r}"
            )
        self.placement = placement
        self.placement_hot_budget = int(placement_hot_budget)
        self.replan_config = replan or placement_lib.ReplanConfig()
        self._drift = placement_lib.DriftDetector(self.replan_config)
        # Learned cost model (parallel/costmodel.py): trained from this
        # trainer's own (plan, measured per-shard bytes) windows, used by
        # build_plans to rank analytically-tied rotations; bit-identical
        # fallback until trained.
        self.cost_model = PlacementCostModel()
        self._plans: Dict[str, "BundlePlan"] = {}
        self.last_placement: Optional[Dict] = None
        self._window_reset_step = 0
        # (bundle, member) -> (step, sorted keys, freqs) at the last
        # placer run — the windowed-arrivals baseline (_member_traffics).
        self._freq_snaps: Dict = {}
        self._replan_stats: Dict[str, object] = {
            "replans": 0, "forced_replans": 0, "migration_rows": 0,
            "migration_bytes": 0.0, "deferred": 0,
            "last_gain_bytes_per_step": None,
        }
        super().__init__(model, sparse_opt, dense_opt, grad_averaging, remat,
                         unique_budget=unique_budget,
                         pipeline_mode=pipeline_mode,
                         pipeline_chunks=pipeline_chunks)
        # Re-point bundles at per-shard capacities + collective wrappers.
        # pipeline_mode="chunked" splits each table's value/grad exchanges
        # into pipeline_chunks column chunks (ShardedTable.exchange_chunks)
        # on EVERY train path (single-step and K-step scan) — bitwise
        # identical arithmetic, overlappable wire. "nested" (the 2-D-mesh
        # lookahead) keeps the chunked exchanges too: the inter-tier hop
        # of chunk k overlaps the intra-tier hop of chunk k+1.
        chunks = pipeline_chunks if pipeline_mode in ("chunked", "nested") else 1
        for bname, b in self.bundles.items():
            b.table = EmbeddingTable(_local_cfg(b.table.cfg, self.num_shards))
        self.sharded = {
            bname: ShardedTable(b.table, self.num_shards, self.axis,
                                comm=comm, a2a_slack=a2a_slack,
                                exchange_chunks=chunks,
                                intra=self.intra_size, inter=self.inter_size,
                                hier_group_factor=hier_group_factor)
            for bname, b in self.bundles.items()
        }

    def _stage_put(self, batch):
        # auto-stage (Trainer.stage) places batches with mesh sharding so
        # the staged transfer already lands split across devices
        from deeprec_tpu.parallel.mesh import shard_batch

        return shard_batch(self.mesh, batch, axis=self.axis)

    # ------------------------------------------------------------------ init

    def _init_state(self, seed: int) -> TrainState:
        from deeprec_tpu.parallel.mesh import put_global, put_tiled_global

        key = jax.random.PRNGKey(seed)
        dense = self.model.init(key)
        N = self.num_shards
        tables = {}
        for bname, b in self.bundles.items():
            local = ensure_slots(b.table, b.table.create(), self.sparse_opt)
            # layout: [T?, N, C_local, ...] — shard axis right before
            # capacity. The per-shard template tiles identically along the
            # lead axes; put_tiled_global never materializes the pod-scale
            # global value on one host.
            if b.stacked:
                lead = (len(b.features), N)
                spec = P(None, self.axis)
            else:
                lead = (N,)
                spec = P(self.axis)
            sh = NamedSharding(self.mesh, spec)
            tables[bname] = jax.tree.map(
                lambda a, lead=lead, s=sh: put_tiled_global(a, lead, s), local
            )
        repl = NamedSharding(self.mesh, P())
        put_repl = lambda t: jax.tree.map(lambda a: put_global(a, repl), t)
        return TrainState(
            step=put_global(jnp.zeros((), jnp.int32), repl),
            tables=tables,
            dense=put_repl(dense),
            opt_state=put_repl(self.dense_opt.init(dense)),
        )

    # -------------------------------------------------------------- internals

    def _table_spec(self, bname):
        b = self.bundles[bname]
        return P(None, self.axis) if b.stacked else P(self.axis)

    def _state_spec(self, state: TrainState) -> TrainState:
        return TrainState(
            step=P(),
            tables={
                bname: jax.tree.map(lambda _: self._table_spec(bname), ts)
                for bname, ts in state.tables.items()
            },
            dense=jax.tree.map(lambda _: P(), state.dense),
            opt_state=jax.tree.map(lambda _: P(), state.opt_state),
        )

    # The four hooks of the base trainer's step bodies (training/trainer.py):
    # the bodies run per shard inside `_on_mesh`'s one mapped region.

    _lookup_phase = scopes.PHASE_LOOKUP_EXCHANGE

    def _on_shard_axis(self, fn, tables):
        """`fn(leaf, axis)` over every table leaf, at its shard axis
        ([T?, N, C, ...]: right before capacity)."""
        return {
            bname: jax.tree.map(
                lambda a, ax=1 if self.bundles[bname].stacked else 0: fn(a, ax),
                ts,
            )
            for bname, ts in tables.items()
        }

    def _tables_in(self, tables):
        """Every bundle's per-shard view (the shard axis off): the first
        thing a step body does with the tables, so part of its lookup."""
        with scopes.scope(scopes.PHASE_LOOKUP_EXCHANGE):
            return self._on_shard_axis(jnp.squeeze, tables)

    def _tables_out(self, tables):
        return self._on_shard_axis(jnp.expand_dims, tables)

    def _replica_mean(self, tree):
        # Data-parallel dense grads and metrics: mean over replicas via
        # ICI allreduce.
        return jax.lax.pmean(tree, self.axis)

    def _on_mesh(self, body, stacked=False, evaluate=False,
                 fills_carry=False):
        """`body` on per-shard values inside ONE mapped region over the
        mesh: the state by `_state_spec`, the batch axis split (behind an
        unsharded [K]/[A] axis when `stacked`), lr and the metrics
        replicated (a prefix spec: a model-owned loss names its own metric
        keys). A state that is a PipelineCarry crosses the boundary by
        `_carry_spec` (parallel/async_stage.py); `fills_carry`: the body
        takes a TrainState and returns such a carry."""
        ax = self.axis
        batch_spec = P(None, ax) if stacked else P(ax)

        def run(state, batch, *lr):
            carried = isinstance(state, PipelineCarry)
            spec_in = self._state_spec(state.inner if carried else state)
            spec_out = spec_in
            if carried:
                spec_in = spec_out = self._carry_spec(spec_in)
            elif fills_carry:
                spec_out = self._carry_spec(spec_in)
            return jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(spec_in, batch_spec) + (P(),) * len(lr),
                out_specs=(P(), P(ax)) if evaluate else (spec_out, P()),
                check_vma=False,
            )(state, batch, *lr)

        run.__name__ = body.__name__  # the program is named for its body
        return run

    def _evict_bundle(self, b, ts, step):
        # leading dims: [T?, N, C]; evict each shard's local table
        fills = self._slot_fills(b)
        fn = lambda s: b.table.evict(s, step, slot_fills=fills)
        fn = jax.vmap(fn)  # over shards
        if b.stacked:
            fn = jax.vmap(fn)  # over grouped tables
        return fn(ts)

    # Per-bundle primitives: the only thing that differs from the base
    # Trainer is that lookup/apply go through the collective ShardedTable.
    # The unique budget resolves on the LOCAL batch — dedup-at-budget runs
    # before the exchange, so the a2a payload / allgather return shrink by
    # the same U/N factor as the compute.
    def _budget_capacity(self, b):
        # The bundle's cfg is the PER-SHARD capacity; local-batch uniques
        # are bounded by the global table (they hash across all shards).
        return b.table.cfg.capacity * self.num_shards

    def _lookup_one(self, b, state, ids, pad, salt, step, train, plan=None):
        U = self._budget_for_lookup(b, ids, train)
        return self.sharded[b.name].lookup_unique(
            state, ids, step=step, train=train, pad_value=pad, salt=salt,
            unique_size=U, plan=plan,
        )

    def _apply_one(self, b, state, res, grad, step, lr):
        # Sync sharded hot path: traffic-diet opt-in (see Trainer._apply_one).
        return self.sharded[b.name].apply_gradients(
            state, self.sparse_opt, res, grad, step=step, lr=lr,
            grad_averaging=self.grad_averaging,
            reuse_rows=self._bundle_reuse_rows(b), stamp_meta=False,
        )

    # Split-phase primitives (Trainer._route_all/_resolve_all/_finish_all
    # drive these): the collective versions — route carries the id
    # exchange, finish the embedding exchange.
    def _route_one(self, b, ids, pad, train, plan=None):
        U = self._budget_for_lookup(b, ids, train)
        return self.sharded[b.name].route(
            ids, pad_value=pad, unique_size=U, plan=plan
        )

    def _resolve_one(self, b, state, route, salt, step, train):
        return self.sharded[b.name].resolve(
            state, route, step=step, train=train, salt=salt
        )

    def _finish_one(self, b, state, pending, train, keep_rows=True):
        return self.sharded[b.name].finish(
            state, pending, train=train, keep_rows=keep_rows
        )

    def _carry_spec(self, state_spec) -> PipelineCarry:
        """Spec of a PipelineCarry that crosses the mapped region's
        boundary — the async stale-by-one stage (parallel/async_stage.py);
        the exact pipelined scan keeps its carry inside the region. Prefix
        specs (broadcast over a subtree): views/batch leaves shard the
        leading local axis; stacked bundles carry their table axis first."""
        ax = self.axis
        return PipelineCarry(
            inner=state_spec, batch=P(ax), views=P(ax),
            bundle_res={
                bname: P(None, ax) if b.stacked else P(ax)
                for bname, b in self.bundles.items()
            },
        )

    # --------------------------------------------------------- placement

    def _bundle_plan_leaves(self, b):
        """Active ShardPlan of this bundle as device constants for the
        route paths (stacked bundles: leading [T] member axis, mapped by
        the lookup vmap). Uniform plans return {} so the compiled program
        is identical to the pre-placement one. Plan changes rebuild the
        jit wrappers (update_placement) — the constants are baked into
        the traced program, exactly like the resolved unique budgets."""
        import numpy as np

        bp = self._plans.get(b.name)
        if bp is None or bp.is_uniform:
            return {}
        return bp.leaves(np.dtype(b.table.cfg.key_dtype), stacked=b.stacked)

    def _per_shard_stats(self, b, member_ts):
        """Owner-load breakdown per mesh position for dedup_stats: the
        counters ShardedTable.resolve accumulates, converted to modeled
        exchange bytes (ops/traffic.py) and their max/mean imbalance."""
        import numpy as np

        from deeprec_tpu.ops import traffic as T

        oa = np.asarray(jax.device_get(member_ts.owner_arrivals))
        ou = np.asarray(jax.device_get(member_ts.owner_unique))
        if oa.ndim != 1:
            return None
        cfg = b.table.cfg
        wire = 2 if cfg.exchange_dtype == "bfloat16" else 4
        rb = T.exchange_row_bytes(dim=cfg.dim, wire_bytes=wire)
        xb = [round(float(a) * rb, 1) for a in oa]
        return {
            "owner_unique": [int(x) for x in ou],
            "owner_arrivals": [int(x) for x in oa],
            "exchange_bytes": xb,
            "imbalance": round(T.shard_imbalance(xb), 4),
        }

    def _member_traffics(self, state, return_pulls: bool = False):
        """Placer inputs: one MemberTraffic per member table, weights
        modeled from the live freq counters (TableState.meta) — a key's
        arrivals/step is at most its occurrence rate and at most N (each
        source shard dedups before the exchange).

        Windowed weights: once a freq snapshot exists (stamped at every
        placer run, `_snapshot_freqs`), the arrival rate is the DELTA
        since the snapshot over the window's steps — so a replan chases
        the distribution the drift trigger actually fired on, not the
        lifetime average a rotated hot set would dilute for thousands of
        steps. First run (no snapshot) uses lifetime freq/steps.

        return_pulls=True additionally returns the raw (keys, freqs)
        host arrays per member so `_snapshot_freqs` can reuse them —
        these are the full table pulls, paid once per placer run."""
        import numpy as np

        from deeprec_tpu.embedding.table import empty_key
        from deeprec_tpu.ops import traffic as T

        N = self.num_shards
        steps = max(1, int(state.step))
        out = []
        pulls = {}
        for bname, b in self.bundles.items():
            cfg = b.table.cfg
            sent = empty_key(cfg)
            wire = 2 if cfg.exchange_dtype == "bfloat16" else 4
            row_bytes = T.exchange_row_bytes(dim=cfg.dim, wire_bytes=wire)
            ts = state.tables[bname]
            keys_np = np.asarray(jax.device_get(ts.keys))  # [T?, N, C]
            freq_np = np.asarray(jax.device_get(ts.freq))
            for m in (range(len(b.features)) if b.stacked else [0]):
                k = keys_np[m] if b.stacked else keys_np  # [N, C]
                fq = freq_np[m] if b.stacked else freq_np
                occ = k != sent
                k_live = k[occ]
                f_live = fq[occ].astype(np.float64)
                pulls[(bname, m)] = (k_live, f_live)
                snap = self._freq_snaps.get((bname, m))
                w_steps = steps
                # A snapshot taken at THIS step means an empty window —
                # no arrivals to weight by; fall back to lifetime rates
                # (back-to-back placer runs, e.g. a deferred evaluation
                # immediately re-run with a different horizon).
                if snap is not None and steps - snap[0] > 0:
                    snap_step, snap_keys, snap_freq = snap
                    w_steps = steps - snap_step
                    if snap_keys.size:
                        pos = np.searchsorted(snap_keys, k_live)
                        pos = np.clip(pos, 0, len(snap_keys) - 1)
                        hit = snap_keys[pos] == k_live
                        prev = np.where(hit, snap_freq[pos], 0.0)
                    else:
                        prev = np.zeros_like(f_live)
                    # eviction/row-reinit resets freq mid-window: clamp
                    f_live = np.maximum(f_live - prev, 0.0)
                out.append(placement_lib.MemberTraffic(
                    bundle=bname, member=m, keys=k_live,
                    weight=np.minimum(f_live / w_steps, float(N)),
                    row_bytes=row_bytes, sentinel=sent,
                ))
        if return_pulls:
            return out, pulls
        return out

    def _snapshot_freqs(self, step: int, pulls) -> None:
        """Stamp the per-key freq counters (sorted by key, host-side) so
        the NEXT placer run models arrivals over the window since this
        one — called once per update_placement, reusing the host arrays
        `_member_traffics(return_pulls=True)` already fetched (no second
        full-table device pull)."""
        import numpy as np

        for ref, (k_live, f_live) in pulls.items():
            order = np.argsort(k_live, kind="stable")
            self._freq_snaps[ref] = (
                int(step), k_live[order], f_live[order]
            )

    def update_placement(self, state, *, hot_budget=None,
                         min_gain: Optional[float] = None,
                         force: bool = False,
                         horizon_steps: Optional[int] = None):
        """The cost-model placer, end to end: estimate per-shard exchange
        load from the live freq/dedup counters + per-table dims
        (ops/traffic.py), greedily build a candidate ShardPlan per member
        (parallel/placement.py build_plans, learned-cost-model assisted
        once trained), and — when it models at least `min_gain`x less
        max/mean imbalance than the ACTIVE plan AND the modeled
        straggler-bytes gain amortizes the modeled migration bytes within
        `horizon_steps` (or force=True, which skips both bars) — migrate
        moved rows between shards and swap the plan at this step
        boundary. The old plan serves until the swap; migration moves
        rows verbatim (bit-identical per-key state) and a migration that
        cannot place every key aborts, keeping the old plan. Adoption
        rebuilds the jitted steps (plan constants resolve at trace time,
        the update_budgets stale-executable contract) and sets the
        per-destination a2a budget vector (`ShardedTable.plan_dest_hot`).

        Every run also feeds the learned cost model one observation per
        member: the ACTIVE plan's modeled per-shard bytes next to the
        window's measured per-shard bytes — the placer's own history is
        its training set.

        Returns (state, report) with a per-bundle report; the global
        model + amortization numbers land on `self.last_placement`."""
        import numpy as np

        from jax.sharding import NamedSharding

        from deeprec_tpu.ops import traffic as T
        from deeprec_tpu.utils.hashing import hash_shard_np

        cfg = self.replan_config
        hot_budget = (
            self.placement_hot_budget if hot_budget is None else hot_budget
        )
        min_gain = cfg.min_gain if min_gain is None else min_gain
        horizon = cfg.horizon_steps if horizon_steps is None else horizon_steps
        step_now = int(state.step)
        snap_steps = {
            ref: step_now - snap[0] for ref, snap in self._freq_snaps.items()
        }
        members_info, pulls = self._member_traffics(state, return_pulls=True)
        current = {
            (m.bundle, m.member): self._plans[m.bundle].member(m.member)
            for m in members_info
            if m.bundle in self._plans
        }
        # Learned-cost-model observation: the ACTIVE plan's modeled
        # per-shard bytes/step vs what the window measured (the per-shard
        # owner counters, normalized by the window's steps). Recorded
        # BEFORE planning so even a deferred run teaches the model. The
        # two sides span different windows (modeled: since the last
        # placer run; measured: since the last counter reset), so pairs
        # are recorded only when the windows roughly coincide — a
        # first-run LIFETIME modeled vector paired with one post-drift
        # measured window would teach a systematically wrong correction.
        # The calibration is over the TAIL load only: build_plans queries
        # the model with tail-only rotation candidates (hot keys are
        # assigned later, by LPT), so hot-routed keys are excluded from
        # the modeled X and their modeled contribution subtracted from
        # the measured y — training and prediction see the same feature
        # distribution.
        window_steps = max(1, step_now - self._window_reset_step)
        measured = self._measured_member_windows(state, window_steps)
        for m in members_info:
            ref = (m.bundle, m.member)
            if ref not in measured or len(m.keys) == 0:
                continue
            ss = snap_steps.get(ref)
            if ss is None or ss <= 0 or ss > 2 * window_steps:
                continue  # no/empty/over-long modeled window: skip
            plan = current.get(ref)
            owner = (
                plan.owner_np(m.keys) if plan is not None
                else hash_shard_np(m.keys, self.num_shards)
            )
            load = m.weight * m.row_bytes
            hot_mask = (
                np.isin(m.keys, np.asarray(plan.hot_keys, m.keys.dtype))
                if plan is not None and plan.hot_keys else
                np.zeros(len(m.keys), bool)
            )
            modeled_tail = np.bincount(
                owner[~hot_mask], weights=load[~hot_mask],
                minlength=self.num_shards,
            )
            modeled_hot = np.bincount(
                owner[hot_mask], weights=load[hot_mask],
                minlength=self.num_shards,
            )
            self.cost_model.record_window(
                self.cost_model.member_stats(m), modeled_tail,
                np.maximum(measured[ref] - modeled_hot, 0.0),
            )
        # Next placer run models arrivals over the window starting HERE
        # (freq values survive migration verbatim, so the snapshot is
        # valid whether or not this run adopts; reuses the host arrays
        # already pulled above — no second full-table device pull).
        self._snapshot_freqs(step_now, pulls)
        # Multi-tier bundles keep uniform routing: their demoted rows live
        # in per-(bundle, shard) tier stores the migration cannot move —
        # re-routing a demoted key would strand its trained values/slots
        # on the old shard's store and re-insert it from the initializer.
        # Their (immovable) load still shapes the plan as a baseline the
        # placer packs around.
        pinned = {
            bname for bname, b in self.bundles.items()
            if b.table.cfg.ev.storage.storage_type.value in (
                "hbm_dram", "hbm_dram_ssd"
            )
        }
        plannable = [m for m in members_info if m.bundle not in pinned]
        fixed = [m for m in members_info if m.bundle in pinned]
        candidate, model_rep = placement_lib.build_plans(
            self.num_shards, plannable, hot_budget=hot_budget,
            base_loads=placement_lib.modeled_loads(self.num_shards, fixed),
            cost_model=self.cost_model,
        )
        loads_current = placement_lib.modeled_loads(
            self.num_shards, members_info, current
        )
        loads_candidate = placement_lib.modeled_loads(
            self.num_shards, members_info, candidate
        )
        imb_current = T.shard_imbalance(loads_current)
        imb_candidate = T.shard_imbalance(loads_candidate)
        # Amortization: straggler bytes/step saved vs the one-shot
        # migration bytes (exchange_row_bytes over the rows that would
        # move) — the replan must pay for itself within the horizon.
        moved_map = placement_lib.plan_moved_rows(
            plannable, current, candidate
        )
        row_bytes_by_ref = {
            (m.bundle, m.member): m.row_bytes for m in plannable
        }
        mig_bytes = sum(
            T.migration_bytes(n, row_bytes=row_bytes_by_ref[ref])
            for ref, n in moved_map.items()
        )
        gain = T.replan_gain_bytes(loads_current, loads_candidate)
        import math

        self.last_placement = dict(
            model_rep,
            imbalance_current=round(imb_current, 4),
            imbalance_candidate=round(imb_candidate, 4),
            gain_bytes_per_step=round(gain, 1),
            migration_rows=int(sum(moved_map.values())),
            migration_bytes=round(float(mig_bytes), 1),
            horizon_steps=horizon,
            amortize_steps=(
                int(math.ceil(mig_bytes / gain)) if gain > 0 else None
            ),
        )
        self._replan_stats["last_gain_bytes_per_step"] = round(gain, 1)
        from deeprec_tpu.obs import metrics as obs_metrics

        if obs_metrics.metrics_enabled():
            obs_metrics.default_registry().gauge(
                "deeprec_placement_modeled_gain",
                "modeled straggler exchange bytes/step a candidate plan "
                "would save over the active plan",
            ).set(gain)
        imb_ok = imb_candidate * min_gain <= imb_current
        amortized = gain > 0 and gain * float(horizon) >= mig_bytes
        adopt = force or (imb_ok and amortized)
        report = {}
        if not adopt:
            reason = "min_gain" if not imb_ok else "amortization"
            self._replan_stats["deferred"] = (
                int(self._replan_stats.get("deferred", 0)) + 1
            )
            self._replan_stats["last_deferred_reason"] = reason
            return state, {
                bname: {"adopted": False, "deferred": reason,
                        "imbalance_current": imb_current,
                        "imbalance_candidate": imb_candidate,
                        "gain_bytes_per_step": round(gain, 1),
                        "migration_bytes": round(float(mig_bytes), 1)}
                for bname in self.bundles
            }

        tables = dict(state.tables)
        changed_any = False
        moved_rows, moved_bytes = 0, 0.0
        for bname, b in self.bundles.items():
            if bname in pinned:
                report[bname] = {"adopted": False, "skipped": "multi_tier"}
                continue
            mlist = list(range(len(b.features))) if b.stacked else [0]
            bp_new = BundlePlan(tuple(candidate[(bname, m)] for m in mlist))
            bp_old = self._plans.get(bname)
            rep = {"adopted": False, "moved": 0,
                   "offsets": [p.offset for p in bp_new.plans],
                   "hot_keys": sum(len(p.hot_keys) for p in bp_new.plans)}
            if bp_old == bp_new or (bp_old is None and bp_new.is_uniform):
                rep["adopted"] = bp_old is not None or not bp_new.is_uniform
                report[bname] = rep
                continue
            ts = state.tables[bname]
            lead = self._bundle_lead_dims(b)
            idxs = list(np.ndindex(*lead))
            members = [jax.tree.map(lambda a, i=i: a[i], ts) for i in idxs]
            fills = self._slot_fills(b)
            N = self.num_shards
            new_members, moved_total, fail = [], 0, ""
            for m in mlist:
                shard_states = members[m * N:(m + 1) * N]
                res, moved, fail = placement_lib.reshard_members(
                    b.table, shard_states, bp_new.member(m).owner_np,
                    slot_fills=fills,
                )
                if res is None:
                    break
                # Local-dedup telemetry describes the SOURCE side — it is
                # unaffected by where rows live, so the window's counters
                # survive the migration (owner counters stay reset: they
                # were measured under the old plan). insert_fails survives
                # too: maintain()'s growth check reads it AFTER this swap
                # in the same call, and a migration must not eat a pending
                # grow signal.
                res = [
                    r.replace(dedup_unique=o.dedup_unique,
                              dedup_ids=o.dedup_ids,
                              dedup_overflow=o.dedup_overflow,
                              insert_fails=o.insert_fails,
                              a2a_overflow=o.a2a_overflow)
                    for r, o in zip(res, shard_states)
                ]
                new_members.extend(res)
                moved_total += moved
            if len(new_members) != len(members):
                rep["migrate_failed"] = fail or "reshard aborted"
                report[bname] = rep
                continue
            tables[bname] = jax.device_put(
                self._restack(new_members, lead),
                NamedSharding(self.mesh, self._table_spec(bname)),
            )
            self._plans[bname] = bp_new
            # Per-destination a2a budget vector: each destination's
            # bucket pays the hot-key arrivals THIS plan routes to it
            # (elementwise-max across vmapped members — they share the
            # bucket) on top of the tail share, which shrinks by the
            # keys every member routes explicitly
            # (ShardedTable._a2a_budget / ops/traffic.py
            # a2a_dest_budgets; static, baked at the jit rebuild).
            dest_hot = bp_new.dest_hot_counts()
            if dest_hot.any():
                self.sharded[bname].plan_dest_hot = dest_hot
                self.sharded[bname].plan_hot_count = bp_new.hot_count_min()
            else:
                self.sharded[bname].plan_dest_hot = None
                self.sharded[bname].plan_hot_count = 0
            # (bname, 0) is always in the dict: every non-pinned
            # bundle's members are in `plannable`, which populated it.
            moved_bytes += T.migration_bytes(
                moved_total, row_bytes=row_bytes_by_ref[(bname, 0)],
            )
            moved_rows += moved_total
            rep.update(adopted=True, moved=moved_total)
            report[bname] = rep
            changed_any = True
        if changed_any:
            self._make_jits()
            self._replan_stats["replans"] = (
                int(self._replan_stats["replans"]) + 1
            )
            if force:
                self._replan_stats["forced_replans"] = (
                    int(self._replan_stats["forced_replans"]) + 1
                )
            self._replan_stats["migration_rows"] = (
                int(self._replan_stats["migration_rows"]) + moved_rows
            )
            self._replan_stats["migration_bytes"] = round(
                float(self._replan_stats["migration_bytes"]) + moved_bytes, 1
            )
            if obs_metrics.metrics_enabled():
                reg = obs_metrics.default_registry()
                reg.counter(
                    "deeprec_placement_replans",
                    "adopted placement replans",
                    {"trigger": "forced" if force else "auto"},
                ).inc(1)
                reg.counter(
                    "deeprec_placement_migration_bytes",
                    "modeled bytes of rows migrated at plan adoptions",
                ).inc(moved_bytes)
        return (
            TrainState(step=state.step, tables=tables, dense=state.dense,
                       opt_state=state.opt_state),
            report,
        )

    def _measured_member_windows(self, state, window_steps: int):
        """(bundle, member) -> measured per-shard exchange bytes/STEP of
        the current counter window — the learned cost model's training
        targets (same unit as the analytic load model). Members whose
        window saw no arrivals are skipped."""
        import numpy as np

        out = {}
        for bname, b in self.bundles.items():
            ts = state.tables[bname]
            for m in (range(len(b.features)) if b.stacked else [0]):
                member_ts = (
                    jax.tree.map(lambda a, m=m: a[m], ts) if b.stacked
                    else ts
                )
                ps = self._per_shard_stats(b, member_ts)
                if not ps or sum(ps["owner_arrivals"]) == 0:
                    continue
                out[(bname, m)] = (
                    np.asarray(ps["exchange_bytes"], np.float64)
                    / max(1, int(window_steps))
                )
        return out

    def update_budgets(self, state, **kw):
        # The owner-load counters reset here; remember where the window
        # started so the replanner can normalize measured bytes to
        # bytes/step (the cost model's unit).
        state, rep = super().update_budgets(state, **kw)
        self._window_reset_step = int(state.step)
        return state, rep

    def maybe_replan(self, state):
        """The drift-driven replan trigger (maintain() runs this BEFORE
        update_budgets when placement="plan"): publish the window's
        per-shard telemetry into the obs plane, read the windowed
        imbalance level + its ring-buffer slope back
        (obs/metrics.py window queries — the PR 11 consumer contract),
        and run the placer only when the DriftDetector's hysteresis/
        cooldown gate fires. The placer itself then applies the
        min_gain + migration-amortization bars — so the system replans
        exactly when drift is real AND the move pays for itself."""
        if self.placement != "plan":
            return state, {}
        from deeprec_tpu.obs import metrics as obs_metrics

        cfg = self.replan_config
        stats = self.dedup_stats(state)  # device_get + gauge publish
        tables_ps = {
            t: d["per_shard"] for t, d in stats.items()
            if isinstance(d, dict) and d.get("per_shard")
        }
        level = max(
            (ps["imbalance"] for ps in tables_ps.values()), default=1.0
        )
        slope = None
        if obs_metrics.metrics_enabled():
            reg = obs_metrics.default_registry()
            slopes = [
                reg.window(
                    "deeprec_shard_imbalance", {"table": t},
                    cfg.window_secs,
                ).get("slope_per_sec")
                for t in tables_ps
            ]
            slopes = [s for s in slopes if s is not None]
            slope = max(slopes) if slopes else None
        fired = self._drift.observe(level, slope)
        report = {"drift": dict(self._drift.last)}
        if not fired:
            return state, report
        state, placer_rep = self.update_placement(state)
        if any(
            r.get("adopted") for r in placer_rep.values()
            if isinstance(r, dict)
        ):
            self._drift.adopted()
        else:
            self._drift.deferred()
        report.update(placer_rep)
        return state, report

    def placement_stats(self):
        """Replanner telemetry (surfaced as
        dedup_stats()['__placement__'] — dunder key, so a real table
        named 'placement' cannot collide): adoption/migration counters,
        the last drift observation and the learned cost model's
        training state."""
        out = dict(self._replan_stats)
        out["cost_model"] = self.cost_model.info()
        if self._drift.last:
            out["drift"] = dict(self._drift.last)
        return out

    def dedup_stats(self, state):
        out = super().dedup_stats(state)
        if self.placement == "plan":
            # Added AFTER the per-table gauge publication (super() has
            # already run _publish_dedup_obs); per-table consumers use
            # .get("per_shard") and skip this record naturally. Dunder
            # key: a real table named "placement" must not collide.
            out["__placement__"] = self.placement_stats()
        return out

    def restore_owner(self, bname: str, member, keys):
        """Owner shard of `keys` under the ACTIVE plan — the checkpoint
        restore router (training/checkpoint.py) calls this instead of the
        bare hash so a checkpoint saved under plan A restores correctly
        into a trainer running plan B."""
        import numpy as np

        from deeprec_tpu.utils.hashing import hash_shard_np

        bp = self._plans.get(bname)
        if bp is None:
            return hash_shard_np(np.asarray(keys), self.num_shards)
        return bp.member(member).owner_np(keys)

    def routing_fingerprint(self, bname: str) -> str:
        """Stable digest of this bundle's ACTIVE routing. Recorded in the
        checkpoint manifest at save time and compared at restore: a
        shard's saved CBF sketch describes the residents its save-time
        routing put there, so the per-shard exact-sketch reuse is only
        valid when save and restore route identically — rows themselves
        re-route freely (restore_owner), only the sketches fall back to
        the rebuild-from-rows path on a mismatch."""
        bp = self._plans.get(bname)
        if bp is None or bp.is_uniform:
            return "uniform"
        import hashlib

        canon = "|".join(
            f"{p.num_shards}:{p.offset}:"
            f"{','.join(map(str, p.hot_keys))}:"
            f"{','.join(map(str, p.hot_owners))}"
            for p in bp.plans
        )
        return hashlib.sha1(canon.encode()).hexdigest()[:16]

    # --------------------------------------------- capacity management

    def _bundle_lead_dims(self, b):
        # [T?, N, C_local]: members iterate grouped tables × shards.
        T = (len(b.features),) if b.stacked else ()
        return T + (self.num_shards,)

    def _set_bundle_capacity(self, b, new_c):
        super()._set_bundle_capacity(b, new_c)
        # Re-point the collective wrapper at the grown local table. The
        # per-dest a2a budget vector carries over: the adopted plan still
        # concentrates its hot keys regardless of capacity, and dropping
        # it here would re-expose the overflow-degraded hot ids the
        # budget exists to prevent (growth and adoption can land in the
        # SAME maintain call).
        old = self.sharded[b.name]
        self.sharded[b.name] = ShardedTable(
            b.table, old.num_shards, old.axis, comm=old.comm,
            a2a_slack=old.a2a_slack, exchange_chunks=old.exchange_chunks,
            intra=old.intra, inter=old.inter,
            hier_group_factor=old.hier_group_factor,
        )
        self.sharded[b.name].plan_dest_hot = old.plan_dest_hot
        self.sharded[b.name].plan_hot_count = old.plan_hot_count

    def maintain(self, state, **kw):
        # max_capacity is the GLOBAL cap; the base loop compares against
        # per-shard local capacities.
        if kw.get("max_capacity"):
            kw["max_capacity"] = max(1, kw["max_capacity"] // self.num_shards)
        state, report = super().maintain(state, **kw)
        # Growth changed per-shard shapes: restore the mesh sharding the
        # step functions expect (host-side stacking produced unsharded
        # arrays).
        from jax.sharding import NamedSharding

        tables = {}
        for bname, ts in state.tables.items():
            spec = self._table_spec(bname)
            tables[bname] = jax.device_put(
                ts, NamedSharding(self.mesh, spec)
            )
        return (
            TrainState(step=state.step, tables=tables, dense=state.dense,
                       opt_state=state.opt_state),
            report,
        )

    # ------------------------------------------------------------------ steps

    def train_steps(self, state: TrainState, batches, lr=None):
        """K steps per dispatch on the mesh. A list/tuple of batch dicts is
        stacked and placed with the K axis unsharded and the batch axis
        split (P(None, axis)); pass a pre-placed stacked pytree
        (`shard_batch(..., stacked=True)`) to skip the host round-trip."""
        if isinstance(batches, (list, tuple)):
            from deeprec_tpu.parallel.mesh import shard_batch

            batches = shard_batch(
                self.mesh, stack_batches(batches), axis=self.axis,
                stacked=True,
            )
        return super().train_steps(state, batches, lr)
