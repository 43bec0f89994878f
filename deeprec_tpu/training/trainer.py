"""Generic train/eval step over (hash tables + dense params).

The structural translation of DeepRec's session-run training (SURVEY.md §3.1):
one jitted function per step performs — sparse lookups (with insertion,
frequency, admission), the dense forward/backward, the fused sparse applies
and the dense optimizer update. XLA sees the whole step as one program, which
is what replaces DeepRec's executor/cost-model machinery
(docs/docs_en/Executor-Optimization.md) on TPU.

GroupEmbedding is built in: features whose tables share a config and id shape
are automatically *bundled* — their states stack along a leading table axis
and a single vmapped lookup/apply serves all of them, exactly the
N-lookups-in-one-kernel optimization of DeepRec's GroupEmbeddingVarLookup
(core/ops/kv_variable_ops.cc:404; docs/docs_en/Group-Embedding.md), and it
also keeps the compiled program small (one probe loop, not one per feature).

Models are plain objects exposing:
    features: Sequence[SparseFeature | DenseFeature]
    init(key) -> dense params (pytree)
    apply(params, inputs: ModelInputs, train: bool) -> logits [B] or
        {task: logits} for multi-task models (labels then come from
        batch['label_<task>']).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct

from deeprec_tpu import features as fcol
from deeprec_tpu.embedding import combiners
from deeprec_tpu.embedding.table import EmbeddingTable, TableState
from deeprec_tpu.features import SparseFeature
from deeprec_tpu.obs import compile_log
from deeprec_tpu.optim.apply import apply_gradients, ensure_slots
from deeprec_tpu.optim.sparse import SparseOptimizer
from deeprec_tpu.training import metrics as M
from deeprec_tpu.utils import scopes


@struct.dataclass
class TrainState:
    step: jnp.ndarray  # [] int32 global step
    tables: Dict[str, TableState]  # bundle name -> (stacked) table state
    dense: Any
    opt_state: Any


@struct.dataclass
class PipelineCarry:
    """TrainState plus a one-batch lookahead: the batch whose lookup has
    already been issued, its per-feature views and per-bundle lookup
    results. Two users share it:

      * the EXACT pipelined K-step scan (`pipeline_mode != "off"`): the
        carried lookup was finished AFTER the previous step's apply, so
        consuming it is bit-identical to the sequential step;
      * the stale-by-one async stage (parallel/async_stage.py, where it is
        exported as `AsyncState`): the carried lookup was finished BEFORE
        the previous apply — the documented one-step staleness.
    """

    inner: TrainState
    batch: Dict[str, jnp.ndarray]  # the prefetched batch (ids/dense/labels)
    views: Dict[str, Any]  # feature -> (embeddings, inverse, mask)
    bundle_res: Dict[str, Any]  # bundle -> lookup result for the backward
    # Step-sentinel carry ({"ema": f32[]} — guard/sentinel.py) threaded
    # through the pipelined scan exactly like the lookahead. None (an
    # empty pytree node) when the trainer has no sentinel, so existing
    # carriers (parallel/async_stage.py AsyncState) are structurally
    # unchanged.
    guard: Any = None


# `pipeline_mode`: how the K-step device loop schedules the embedding
# exchange relative to dense compute (docs/perf.md round 11).
#   "off"       — strictly sequential scan body (lookup -> dense -> apply).
#   "lookahead" — the scan carries a one-batch lookahead: batch t+1's
#                 routing (id dedup + id exchange) and owner resolve
#                 (probe/insert/meta/init) are issued BEFORE batch t's
#                 dense compute (no data dependency -> XLA's async
#                 collectives hide them behind the matmuls); the value
#                 gather + embedding exchange run after batch t's apply,
#                 which keeps the pipeline exact — bit-identical to "off".
#   "chunked"   — "lookahead" plus the value/grad exchanges split into
#                 `pipeline_chunks` column chunks (ShardedTable
#                 exchange_chunks): several smaller collectives whose wire
#                 time pipelines against the neighbouring gather /
#                 segment-sum compute. Also exact.
#   "nested"    — the 2-D-mesh form of "chunked" (docs/multihost.md):
#                 same rotated scan and chunked exchanges, intended for
#                 comm="hier" where route(t+1) contains BOTH tiers' id
#                 hops — the expensive inter-tier (DCN) exchange of t+1
#                 is issued a full dense fwd/bwd ahead, nesting the DCN
#                 pipeline inside the intra-host one. Same exact-no-
#                 staleness contract (prologue fill, last-iteration
#                 peel): bit-identical to "off".
PIPELINE_MODES = ("off", "lookahead", "chunked", "nested")


def validate_pipeline_mode(mode: str, where: str) -> None:
    if mode not in PIPELINE_MODES:
        raise ValueError(
            f"{where}: pipeline_mode must be one of {PIPELINE_MODES}, "
            f"got {mode!r}"
        )


@dataclasses.dataclass
class Bundle:
    """A set of features served by one (possibly stacked) table state.

    stacked=True: `table` holds the shared per-member config; state arrays
    carry a leading [T] table axis and lookups/applies are vmapped over it.
    stacked=False: a single table, optionally shared by several features
    (shared_embedding semantics) which then look up sequentially.
    """

    name: str
    table: EmbeddingTable
    features: List[SparseFeature]
    stacked: bool

    @property
    def salts(self):
        from deeprec_tpu.utils.hashing import name_salt

        return jnp.asarray([name_salt(f.name) for f in self.features], jnp.uint32)


def build_bundles(specs) -> Dict[str, Bundle]:
    """Group single-use tables by (config-sans-name, id rank/pad); keep
    shared tables as individual bundles."""
    sparse = fcol.sparse_features(specs)
    by_table: Dict[str, List[SparseFeature]] = {}
    for f in sparse:
        by_table.setdefault(fcol.resolve_table_name(f), []).append(f)
    cfgs = fcol.table_configs(specs)

    bundles: Dict[str, Bundle] = {}
    groups: Dict[tuple, List[SparseFeature]] = {}
    for tname, feats in by_table.items():
        cfg = cfgs[tname]
        if len(feats) > 1:
            bundles[tname] = Bundle(tname, EmbeddingTable(cfg), feats, False)
        else:
            f = feats[0]
            # Pooling kind + declared max_len separate sequence features
            # ([B, L] ids) from scalar bags so stacked shapes stay compatible
            # (a runtime shape check in _lookup_all backstops undeclared L).
            key = (dataclasses.replace(cfg, name="_"), f.pad_value, f.pooling,
                   f.max_len)
            groups.setdefault(key, []).append(f)
    for i, (key, feats) in enumerate(sorted(groups.items(), key=lambda kv: kv[1][0].name)):
        if len(feats) == 1:
            f = feats[0]
            tname = fcol.resolve_table_name(f)
            bundles[tname] = Bundle(tname, EmbeddingTable(cfgs[tname]), feats, False)
        else:
            cfg = dataclasses.replace(key[0], name=f"group{i}")
            bundles[cfg.name] = Bundle(cfg.name, EmbeddingTable(cfg), feats, True)
    return bundles


@struct.dataclass
class ModelInputs:
    """What the model's apply() receives each step (a pytree, so it can
    cross transform boundaries like jax.checkpoint)."""

    pooled: Dict[str, jnp.ndarray]  # feature -> [B, D]
    seq: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]]  # feature -> ([B,L,D], [B,L] mask)
    dense: Dict[str, jnp.ndarray]  # feature -> [B, W]


def _prep_ids(ids):
    return ids[:, None] if ids.ndim == 1 else ids


def stack_batches(batches):
    """Stack K same-shape batch dicts into one pytree with a leading
    [K, ...] axis — the input layout of `Trainer.train_steps`. Host-side;
    for ShardedTrainer place the result with `shard_batch(..., stacked=True)`
    so the K axis stays unsharded and the batch axis splits over the mesh."""
    batches = list(batches)
    if len(batches) == 1:
        return jax.tree.map(lambda x: jnp.asarray(x)[None], batches[0])
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


# Module-level so repeated evaluate() calls hit one compile cache.
_jit_auc_update = jax.jit(M.auc_update)


class Trainer:
    @scopes.host_spanned(scopes.TRAINER_BUILD)
    def __init__(
        self,
        model,
        sparse_opt: SparseOptimizer,
        dense_opt: Optional[optax.GradientTransformation] = None,
        grad_averaging: bool = False,
        remat: bool = False,
        stage: str = "auto",
        unique_budget=None,
        pipeline_mode: str = "off",
        pipeline_chunks: int = 4,
        sentinel=None,
    ):
        compile_log.install()  # set-up's recorder: once a process
        self.model = model
        self.sparse_opt = sparse_opt
        self.dense_opt = dense_opt or optax.adam(1e-3)
        self.grad_averaging = grad_averaging
        # Step sentinel (guard/sentinel.py SentinelConfig): per-dispatch
        # model-quality flags fused into the jitted step and the K-step
        # scan body — one int32 scalar out per step, bit-exact no-op on
        # the update math while untripped. Base Trainer only so far:
        # ShardedTrainer runs these same step bodies on its mesh but does
        # not forward the kwarg yet.
        if sentinel is not None:
            from deeprec_tpu.guard.sentinel import SentinelConfig

            if not isinstance(sentinel, SentinelConfig):
                raise TypeError(
                    "sentinel must be a guard.SentinelConfig, got "
                    f"{type(sentinel).__name__}"
                )
        self.sentinel = sentinel
        # In-step pipelining of the K-step device loop (train_steps): see
        # PIPELINE_MODES. Single-device trainers gain the restructured
        # scan (route/resolve hoisted over the dense compute); sharded
        # trainers additionally overlap the collectives it contains.
        validate_pipeline_mode(pipeline_mode, type(self).__name__)
        self.pipeline_mode = pipeline_mode
        self.pipeline_chunks = max(1, int(pipeline_chunks))
        # remat=True recomputes the dense forward in the backward pass
        # (jax.checkpoint): trades MXU FLOPs for HBM — the rematerialisation
        # lever for big towers / long sequences.
        self.remat = remat
        if stage not in ("auto", "off"):
            raise ValueError(f"unknown stage mode {stage!r}")
        self.stage_mode = stage
        # Trainer-wide unique-budget override (None = per-feature/table
        # configs decide): "auto" | "off" | int — see ops/dedup.py and
        # TableConfig.unique_budget. Same grammar check as the configs: an
        # unvalidated typo would fall through _resolve_budget's else-branch
        # and silently mean "auto".
        fcol.validate_unique_budget(unique_budget, "Trainer(unique_budget=)")
        self.unique_budget = unique_budget
        self.sparse_specs = fcol.sparse_features(model.features)
        self.dense_specs = fcol.dense_features(model.features)
        self.bundles = build_bundles(model.features)
        self._budget_modes = {
            bname: self._bundle_budget_mode(b)
            for bname, b in self.bundles.items()
        }
        self._auto_frac: Dict[str, float] = {}  # bundle -> budget fraction
        self._unique_ema: Dict[str, float] = {}  # bundle -> raw EMA
        self._dispatches = 0  # train dispatches so far: the step spans' number
        self._make_jits()

    def _make_jits(self):
        """(Re)wrap the step functions in fresh jit caches. Budget
        resolution happens at TRACE time, so anything that changes a
        resolved budget (update_budgets moving an "auto" bucket) must
        rebuild these — an already-cached executable for the same input
        avals would silently keep its old unique sizes otherwise."""
        on = self._on_mesh
        self._train_step = jax.jit(on(self._step_impl), donate_argnums=0)  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps
        self._train_step_accum = jax.jit(on(self._accum_impl, stacked=True), donate_argnums=0)  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps
        # K-step device loop: jit caches one executable per K (the stacked
        # batch's leading dim is part of the trace signature), so sweeping
        # or changing K recompiles once per value and then amortizes.
        self._train_steps = jax.jit(on(self._steps_impl, stacked=True), donate_argnums=0)  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps
        self._eval_step = jax.jit(on(self._eval_impl, evaluate=True))  # noqa: DRT001 — deliberate rebuild-on-budget/plan-change; one wrapper serves all steps

    # Back-compat/introspection: table object + state accessor per table name.
    @property
    def tables(self) -> Dict[str, EmbeddingTable]:
        out = {}
        for b in self.bundles.values():
            for f in b.features:
                out[fcol.resolve_table_name(f)] = b.table
        return out

    def table_state(self, state: TrainState, table_name: str) -> TableState:
        """Extract the (unstacked) state of one named table."""
        for b in self.bundles.values():
            for k, f in enumerate(b.features):
                if fcol.resolve_table_name(f) == table_name:
                    ts = state.tables[b.name]
                    return jax.tree.map(lambda a: a[k], ts) if b.stacked else ts
        raise KeyError(table_name)

    # ------------------------------------------------------------------ init

    @scopes.host_spanned(scopes.INIT_STATE)
    def init(self, seed: int = 0) -> TrainState:
        """Tables (empty) and dense weights made. A mesh changes
        `_init_state`, not this."""
        return self._init_state(seed)

    def _init_state(self, seed: int) -> TrainState:
        key = jax.random.PRNGKey(seed)
        dense = self.model.init(key)
        tables = {}
        for bname, b in self.bundles.items():
            local = ensure_slots(b.table, b.table.create(), self.sparse_opt)
            if b.stacked:
                T = len(b.features)
                local = jax.tree.map(lambda a: jnp.stack([a] * T), local)
            tables[bname] = local
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            tables=tables,
            dense=dense,
            opt_state=self.dense_opt.init(dense),
        )

    # ------------------------------------------------------------- internals
    #
    # _lookup_one/_apply_one are the per-bundle primitives; ShardedTrainer
    # overrides just these two to swap in the collective path, so the
    # bundling/stacking control flow below exists exactly once.
    #
    # The step bodies (_micro_step ... _eval_impl) exist once too. What a
    # mesh changes of them is these four hooks, each the identity here.

    # The phase scope a step's lookup stands under (utils/scopes.py).
    _lookup_phase = scopes.PHASE_LOOKUP

    def _tables_in(self, tables):
        """The tables as a step body works on them (a mesh: the shard
        axis off). A fresh dict: the bodies assign into it."""
        return dict(tables)

    def _tables_out(self, tables):
        """Inverse of `_tables_in`, for the TrainState a body returns."""
        return tables

    def _replica_mean(self, tree):
        """Mean over the replicas of the dense gradients and the step's
        metrics: one replica here."""
        return tree

    def _on_mesh(self, body, stacked=False, evaluate=False):
        """A step body `(state, batch, lr) -> (state, metrics)`, or the
        eval body `(state, batch) -> (loss, probs)`, as the program a
        dispatch runs. `stacked`: the batch leaves carry a leading [K]
        (scan) or [A] (accumulation) axis before the batch axis. One
        device runs the body as it is."""
        return body

    # ----------------------------------------------------- unique budgets

    def _bundle_budget_mode(self, b: Bundle):
        """Effective budget mode for one bundle: the trainer-wide override
        wins, then feature-level settings (largest int / any "auto"),
        then the table config. Returns None (legacy), "auto", or int."""
        mode = self.unique_budget
        if mode is None:
            feat = [
                f.unique_budget for f in b.features
                if f.unique_budget is not None
            ]
            if feat:
                ints = [m for m in feat if isinstance(m, int)]
                mode = (
                    max(ints) if ints
                    else ("auto" if any(m == "auto" for m in feat) else "off")
                )
            else:
                mode = b.table.cfg.unique_budget
        return mode  # None (legacy, logged) | "off" (legacy, silent) | "auto" | int

    def _resolve_budget(self, b: Bundle, n: int) -> Optional[int]:
        """Static uids-array size for an n-position lookup of bundle `b`,
        or None for the legacy U=N path. "auto" uses the quantized EMA
        fraction once `update_budgets` has measured one (clamped by the
        table capacity — more uniques than slots cannot land anyway);
        before the first measurement it runs at U=N through the hash
        engine so the counters seed the EMA without a sort."""
        from deeprec_tpu.ops import dedup

        mode = self._budget_modes.get(b.name)
        if mode is None or mode == "off":
            if mode is None:  # "off" is a deliberate choice: stay silent
                dedup.log_full_fallback(b.name, n)
            return None
        if isinstance(mode, int):
            return dedup.resolve_size(mode, n)
        frac = self._auto_frac.get(b.name)
        if frac is None:
            budget = n
        else:
            import math

            budget = min(int(math.ceil(frac * n)), self._budget_capacity(b))  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
        return dedup.resolve_size(budget, n)

    def _budget_capacity(self, b: Bundle) -> int:
        """Upper clamp for the auto budget: a batch cannot hold more
        RESIDENT uniques than the table has slots. ShardedTrainer overrides
        with the GLOBAL capacity — its bundle cfg is per-shard, but a local
        batch's ids hash across every shard."""
        return b.table.cfg.capacity

    def _budget_for_lookup(self, b: Bundle, ids, train: bool) -> Optional[int]:
        """Static unique size for one lookup — shared by the local and the
        sharded `_lookup_one`. Budgets apply to TRAIN lookups only: an
        eval/serving batch with more uniques than the (train-skew-derived)
        budget would silently serve defaults for resident keys — and the
        overflow counter only accumulates on train state, so it would be
        invisible. Eval runs exact at U = N."""
        import numpy as np

        if not train:
            return None
        return self._resolve_budget(b, int(np.prod(ids.shape)))  # noqa: DRT002 — np.prod of a static shape tuple, no device value

    def _bundle_plan_leaves(self, b: Bundle):
        """Per-bundle placement-plan device constants threaded through the
        lookup/route vmaps (parallel/placement.py). The base trainer has
        no placement — an empty dict means uniform hash routing and adds
        no vmap leaves; ShardedTrainer overrides with the active plan's
        arrays (leading [T] member axis for stacked bundles)."""
        return {}

    def _lookup_one(self, b: Bundle, state, ids, pad, salt, step, train,
                    plan=None):
        U = self._budget_for_lookup(b, ids, train)
        return b.table._lookup_unique_impl(
            state, ids, step, train, pad, U, salt=salt
        )

    def _bundle_reuse_rows(self, b: Bundle) -> bool:
        """Whether the apply may reuse the forward residual (res.rows)
        instead of re-gathering value rows. Shared-table bundles (several
        features on ONE unstacked table) apply sequentially — feature k's
        residual predates feature k-1's apply and overlapping rows would
        lose updates — so only they re-gather. Stacked (vmapped) members
        and single-feature tables see exactly one apply per step."""
        return b.stacked or len(b.features) == 1

    def _apply_one(self, b: Bundle, state, res, grad, step, lr):
        # Train hot path: opt into the traffic diet — reuse the forward
        # residual where the bundle allows it, and never re-stamp
        # version/dirty (the same-step train lookup's fused metadata
        # scatter already did, for a superset of the applied rows).
        return apply_gradients(
            b.table, state, self.sparse_opt, res, grad, step=step, lr=lr,
            grad_averaging=self.grad_averaging,
            reuse_rows=self._bundle_reuse_rows(b), stamp_meta=False,
        )

    def _stacked_ids(self, b: Bundle, batch) -> jnp.ndarray:
        """[T, B, L] id stack of a grouped bundle (shape-checked)."""
        shapes = {f.name: _prep_ids(batch[f.name]).shape for f in b.features}
        if len(set(shapes.values())) > 1:
            raise ValueError(
                f"grouped features have mismatched id shapes {shapes}; "
                "declare distinct SparseFeature.max_len values to keep "
                "them in separate embedding groups"
            )
        return jnp.stack([_prep_ids(batch[f.name]) for f in b.features])

    def _lookup_all(self, tables, batch, step, train):
        """Run every bundle's lookup. Returns (tables, per-feature views,
        per-bundle stacked results for the backward pass)."""
        views = {}  # feature -> (embeddings [U,D], inverse, mask)
        bundle_res = {}  # bundle -> stacked result
        for bname, b in self.bundles.items():
            plan = self._bundle_plan_leaves(b)
            if b.stacked:
                ids = self._stacked_ids(b, batch)
                pad = b.features[0].pad_value
                masks = ids != jnp.asarray(pad, ids.dtype)

                def one(s, i, sa, pl, b=b, pad=pad):
                    return self._lookup_one(b, s, i, pad, sa, step, train,
                                            plan=pl)

                tables[bname], res = jax.vmap(one)(
                    tables[bname], ids, b.salts, plan
                )
                bundle_res[bname] = res
                for k, f in enumerate(b.features):
                    views[f.name] = (
                        res.embeddings[k],
                        res.inverse[k],
                        masks[k],
                    )
            else:
                for f in b.features:
                    ids = _prep_ids(batch[f.name])
                    mask = ids != jnp.asarray(f.pad_value, ids.dtype)
                    tables[bname], res = self._lookup_one(
                        b, tables[bname], ids, f.pad_value, None, step, train,
                        plan=plan,
                    )
                    bundle_res.setdefault(bname, {})[f.name] = res
                    views[f.name] = (res.embeddings, res.inverse, mask)
        return tables, views, bundle_res

    # ------------------------------------------------- split-phase lookup
    #
    # The three-phase decomposition of _lookup_all the pipelined scan (and
    # the async stale-by-one stage) schedule around the dense compute:
    #   route   — id dedup (+ the id exchange, sharded): ids only, no
    #             table state, hoistable arbitrarily early;
    #   resolve — probe/insert, fused metadata, init scatter, admission:
    #             reads keys/meta, never the value rows an apply writes,
    #             so it commutes bit-exactly with the previous apply;
    #   finish  — value gather (+ the embedding exchange, sharded): reads
    #             the CURRENT values, so running it after the previous
    #             apply keeps the lookahead staleness-free.
    # route → resolve → finish composes to exactly _lookup_all.
    # ShardedTrainer overrides only the three *_one primitives.

    def _route_one(self, b: Bundle, ids, pad, train, plan=None):
        U = self._budget_for_lookup(b, ids, train)
        return b.table._route_ids(ids, pad, U)

    def _resolve_one(self, b: Bundle, state, route, salt, step, train):
        return b.table._resolve_routed(
            state, route, step=step, train=train, salt=salt
        )

    def _finish_one(self, b: Bundle, state, pending, train, keep_rows=True):
        return b.table._finish_resolved(state, pending, keep_rows=keep_rows)

    def _route_all(self, batch, train=True):
        """Phase 1 for every bundle: pure function of the id batch."""
        routes = {}
        for bname, b in self.bundles.items():
            plan = self._bundle_plan_leaves(b)
            if b.stacked:
                ids = self._stacked_ids(b, batch)
                pad = b.features[0].pad_value

                def one(i, pl, b=b, pad=pad):
                    return self._route_one(b, i, pad, train, plan=pl)

                routes[bname] = jax.vmap(one)(ids, plan)
            else:
                routes[bname] = {
                    f.name: self._route_one(
                        b, _prep_ids(batch[f.name]), f.pad_value, train,
                        plan=plan,
                    )
                    for f in b.features
                }
        return routes

    def _resolve_all(self, tables, routes, step, train=True):
        """Phase 2 for every bundle (same bundle/feature order as
        _lookup_all, so shared-table inserts chain identically)."""
        pending = {}
        for bname, b in self.bundles.items():
            if b.stacked:

                def one(s, r, sa, b=b):
                    return self._resolve_one(b, s, r, sa, step, train)

                tables[bname], pend = jax.vmap(one)(
                    tables[bname], routes[bname], b.salts
                )
                pending[bname] = pend
            else:
                for f in b.features:
                    tables[bname], pend = self._resolve_one(
                        b, tables[bname], routes[bname][f.name], None, step,
                        train,
                    )
                    pending.setdefault(bname, {})[f.name] = pend
        return tables, pending

    def _finish_all(self, tables, pending, batch, train=True, keep_rows=True):
        """Phase 3 for every bundle: gather (+ exchange) the value rows
        against the CURRENT tables. Returns (views, bundle_res) shaped
        exactly like _lookup_all's."""
        views = {}
        bundle_res = {}
        for bname, b in self.bundles.items():
            if b.stacked:
                ids = self._stacked_ids(b, batch)
                pad = b.features[0].pad_value
                masks = ids != jnp.asarray(pad, ids.dtype)

                def one(s, p, b=b):
                    return self._finish_one(b, s, p, train, keep_rows)

                res = jax.vmap(one)(tables[bname], pending[bname])
                bundle_res[bname] = res
                for k, f in enumerate(b.features):
                    views[f.name] = (
                        res.embeddings[k],
                        res.inverse[k],
                        masks[k],
                    )
            else:
                for f in b.features:
                    ids = _prep_ids(batch[f.name])
                    mask = ids != jnp.asarray(f.pad_value, ids.dtype)
                    res = self._finish_one(
                        b, tables[bname], pending[bname][f.name], train,
                        keep_rows,
                    )
                    bundle_res.setdefault(bname, {})[f.name] = res
                    views[f.name] = (res.embeddings, res.inverse, mask)
        return views, bundle_res

    def _build_inputs(self, embs, views, batch) -> ModelInputs:
        pooled, seq = {}, {}
        for f in self.sparse_specs:
            _, inverse, mask = views[f.name]
            e_u = embs[f.name]
            if f.pooling == "none":
                e = e_u[inverse]  # [B, L, D]
                seq[f.name] = (jnp.where(mask[..., None], e, 0.0), mask)
            else:
                pooled[f.name] = combiners.combine(e_u, inverse, mask, f.pooling)
        dense = {f.name: batch[f.name] for f in self.dense_specs}
        return ModelInputs(pooled=pooled, seq=seq, dense=dense)

    def _apply_all(self, tables, bundle_res, g_embs, step, lr):
        for bname, b in self.bundles.items():
            if b.stacked:
                res = bundle_res[bname]
                grads = jnp.stack([g_embs[f.name] for f in b.features])

                def one(s, r, g, b=b):
                    return self._apply_one(b, s, r, g, step, lr)

                tables[bname] = jax.vmap(one)(tables[bname], res, grads)
            else:
                for f in b.features:
                    tables[bname] = self._apply_one(
                        b, tables[bname], bundle_res[bname][f.name],
                        g_embs[f.name], step, lr,
                    )
        return tables

    def _loss_from_logits(self, out, batch):
        if isinstance(out, dict):
            losses = {
                task: M.bce_loss(logits, batch[f"label_{task}"])
                for task, logits in out.items()
            }
            return sum(losses.values()), out
        return M.bce_loss(out, batch["label"]), out

    def _forward_loss(self, dense, embs, views, batch):
        """(loss, the step's metrics besides the loss) of one batch. A
        model with a `loss(params, inputs, batch)` of its own owns its loss
        (and its remat: it knows its layers); every other model is scored
        by `_loss_from_logits` on what its `apply` returns."""
        inputs = self._build_inputs(embs, views, batch)
        own = getattr(self.model, "loss", None)
        if own is not None:
            return own(dense, inputs, batch)
        apply = (
            jax.checkpoint(self.model.apply, static_argnums=(2,))
            if self.remat
            else self.model.apply
        )
        loss, out = self._loss_from_logits(apply(dense, inputs, True), batch)
        if isinstance(out, dict):
            return loss, {"accuracy": jnp.zeros(())}
        return loss, {"accuracy": M.accuracy(jax.nn.sigmoid(out),
                                             batch["label"])}

    def _fwd_bwd(self, dense, views, batch):
        """The dense forward and backward over one batch's finished lookup.
        Returns (dense grads, per-feature embedding grads, metrics with the
        loss among them); the dense grads and the metrics are the mean over
        the replicas."""
        with scopes.scope(scopes.PHASE_DENSE_FWD_BWD):
            embs = {n: v[0].astype(jnp.float32) for n, v in views.items()}
            (loss, mets), (g_dense, g_embs) = jax.value_and_grad(
                self._forward_loss, argnums=(0, 1), has_aux=True
            )(dense, embs, views, batch)
            g_dense, mets = self._replica_mean(
                (g_dense, {"loss": loss, **mets})
            )
        return g_dense, g_embs, mets

    def _dense_update(self, state: TrainState, g_dense, tables,
                      mets) -> TrainState:
        """The dense optimizer's update and the step count: the state
        after a step whose sparse side left `tables`.

        A leaf a rule owns: a model may keep in its dense tree leaves that
        no gradient moves (it cuts them off behind `stop_gradient`, so the
        dense optimizer's update of them is exactly 0) and give
        `after_update(dense, metrics) -> dense`, which runs here once a
        step, after the optimizer's update, on the step's metrics `mets`
        (with accumulation the micro-batches' SUM; on a mesh
        the replicas' mean, as every metric is: a rule that reads only the
        sign of a load's distance from the loads' mean, as a router's
        selection bias does, is the same under both). A model without the
        hook lowers to the program it lowered to before."""
        with scopes.scope(scopes.PHASE_DENSE_APPLY):
            updates, opt_state = self.dense_opt.update(
                g_dense, state.opt_state, state.dense
            )
            dense = optax.apply_updates(state.dense, updates)
            rule = getattr(self.model, "after_update", None)
            if rule is not None:
                dense = rule(dense, mets)
            step = state.step + 1
        return TrainState(
            step=step, tables=self._tables_out(tables), dense=dense,
            opt_state=opt_state,
        )

    def _micro_step(self, tables, dense, batch, step, lr):
        """Forward + backward + SPARSE applies for one (micro-)batch; returns
        updated tables, the dense-grad pytree (NOT applied) and metrics.

        Every operation stands under one phase scope (utils/scopes.py),
        so a device trace splits the step by lookup / dense fwd-bwd /
        sparse apply."""
        with scopes.scope(self._lookup_phase):
            tables, views, bundle_res = self._lookup_all(
                tables, batch, step, True
            )
        g_dense, g_embs, mets = self._fwd_bwd(dense, views, batch)
        with scopes.scope(scopes.PHASE_SPARSE_APPLY):
            tables = self._apply_all(tables, bundle_res, g_embs, step, lr)
        if self.sentinel is not None:
            with scopes.scope(scopes.PHASE_SENTINEL):
                tables, mets["_sentinel"] = self._sentinel_observe(
                    tables, bundle_res, mets["loss"], g_dense, g_embs, step
                )
        return tables, g_dense, mets

    # ------------------------------------------------------- step sentinel

    def _sentinel_observe(self, tables, bundle_res, loss, g_dense, g_embs,
                          step):
        """Device half of the step sentinel: fused reductions over the
        step's loss/grads plus a post-apply gather of exactly the rows
        this step updated (guard/rows.py — never a full-table scan).
        Returns (tables, obs dict); tables change only under the
        optional row clamp. Everything is a scalar reduction XLA fuses
        with the step — no host value, no extra dispatch."""
        from deeprec_tpu.guard import rows as guard_rows
        from deeprec_tpu.guard import sentinel as guard_sentinel

        cfg = self.sentinel
        finite, norm_sq = guard_sentinel.grad_observations(g_dense, g_embs)
        obs = {
            "loss": jnp.asarray(loss, jnp.float32),
            "grads_finite": finite,
            "grad_norm_sq": norm_sq,
        }
        want_rows = (
            cfg.row_norm_max is not None or cfg.row_clamp_norm is not None
        ) and not hasattr(self, "num_shards")
        if not want_rows:
            return tables, obs
        clamp = cfg.row_clamp_norm
        row_max = jnp.zeros((), jnp.float32)
        for bname, b in self.bundles.items():
            ts = tables[bname]
            if b.stacked:
                six = bundle_res[bname].slot_ix  # [T, U]

                def one(vals, ix, b=b):
                    n = guard_rows.touched_row_norms(b.table, vals, ix)
                    if clamp is not None:
                        vals = guard_rows.clamp_rows(
                            b.table, vals, ix, n, clamp, step
                        )
                    return vals, jnp.max(n)

                new_vals, maxes = jax.vmap(one)(ts.values, six)
                if clamp is not None:
                    tables[bname] = ts = ts.replace(values=new_vals)
                row_max = jnp.maximum(row_max, jnp.max(maxes))
            else:
                for f in b.features:
                    ts = tables[bname]
                    six = bundle_res[bname][f.name].slot_ix
                    n = guard_rows.touched_row_norms(b.table, ts.values, six)
                    if clamp is not None:
                        tables[bname] = ts.replace(
                            values=guard_rows.clamp_rows(
                                b.table, ts.values, six, n, clamp, step
                            )
                        )
                    row_max = jnp.maximum(row_max, jnp.max(n))
        obs["row_max"] = row_max
        return tables, obs

    def _sentinel_fold(self, mets, guard):
        """Combine a step's sentinel observations (popped from mets)
        with the guard carry into the per-dispatch flags scalar +
        advanced EMA, both riding out through mets."""
        from deeprec_tpu.guard import sentinel as guard_sentinel

        obs = mets.pop("_sentinel")
        if guard is None:
            guard = guard_sentinel.guard_init()
        flags, guard = guard_sentinel.step_flags(
            self.sentinel, obs["loss"], obs["grads_finite"],
            obs["grad_norm_sq"], obs.get("row_max"), guard,
        )
        mets["guard_flags"] = flags
        mets["guard_ema"] = guard["ema"]
        return mets, guard

    def _step_impl(self, state: TrainState, batch, lr, guard=None):
        tables, g_dense, mets = self._micro_step(
            self._tables_in(state.tables), state.dense, batch, state.step, lr
        )
        if self.sentinel is not None:
            with scopes.scope(scopes.PHASE_SENTINEL):
                mets, guard = self._sentinel_fold(mets, guard)
        return self._dense_update(state, g_dense, tables, mets), mets

    def _accum_impl(self, state: TrainState, batch, lr, guard=None):
        """Gradient micro-batching — the Auto-Micro-Batch analog
        (reference graph_execution_state.cc:635 PipelineGraph duplicates the
        compute graph N×; here it's a lax.scan over micro-batches): sparse
        tables apply per micro-batch (the reference's semantics), dense grads
        accumulate and apply once."""
        step = state.step
        A = next(iter(batch.values())).shape[0]

        def micro(carry, mb):
            tables, g_acc = carry
            tables, g_dense, mets = self._micro_step(
                tables, state.dense, mb, step, lr
            )
            g_acc = jax.tree.map(jnp.add, g_acc, g_dense)
            return (tables, g_acc), mets

        g0 = jax.tree.map(jnp.zeros_like, state.dense)
        (tables, g_acc), mets = jax.lax.scan(
            micro, (self._tables_in(state.tables), g0), batch
        )
        with scopes.scope(scopes.PHASE_DENSE_APPLY):
            g_mean = jax.tree.map(lambda g: g / jnp.float32(A), g_acc)
        sen = mets.pop("_sentinel", None)  # [A]-stacked micro observations
        new_state = self._dense_update(
            state, g_mean, tables,
            jax.tree.map(lambda m: jnp.sum(m, axis=0), mets))
        mets = jax.tree.map(jnp.mean, mets)
        if self.sentinel is not None and sen is not None:
            # The dispatch is the sentinel unit: micro-batch observations
            # reduce to one step-level record (ANY bad micro grad poisons
            # the step; norms take the worst micro-batch).
            mets["_sentinel"] = {
                "loss": jnp.mean(sen["loss"]),
                "grads_finite": jnp.all(sen["grads_finite"]),
                "grad_norm_sq": jnp.max(sen["grad_norm_sq"]),
            }
            if "row_max" in sen:
                mets["_sentinel"]["row_max"] = jnp.max(sen["row_max"])
            mets, guard = self._sentinel_fold(mets, guard)
        return new_state, mets

    def _steps_impl(self, state: TrainState, batches, lr, guard=None):
        """Multi-step device loop — K full train steps per dispatch.

        DeepRec amortizes per-step host overhead with graph-level pipeline
        stages (Stage/SmartStage); in the functional world the same cure is
        a `lax.scan` over K steps inside ONE compiled program: the host
        dispatches once per K steps instead of once per step, which is the
        lever when the step is dispatch-overhead-bound (docs/perf.md). The
        scan threads the FULL TrainState — dense params, optimizer state
        and every hash-table TableState — so insertion, eviction counters,
        frequency/admission and version stamping behave exactly as K
        sequential `train_step` calls (tests/test_train_steps.py pins the
        equivalence, exact on table ints). With a sentinel configured the
        guard carry (loss EMA) rides the scan carry and the per-step
        flags stack [K] in the metrics — the host still reads ONE array
        per dispatch."""
        if self.pipeline_mode != "off":
            return self._steps_pipelined(state, batches, lr, guard)
        if self.sentinel is None:

            def body(state, batch):
                return self._step_impl(state, batch, lr)

            return jax.lax.scan(body, state, batches)
        from deeprec_tpu.guard.sentinel import guard_init

        def body(carry, batch):
            st, g = carry
            st, mets = self._step_impl(st, batch, lr, g)
            return (st, {"ema": mets["guard_ema"]}), mets

        (state, _), mets = jax.lax.scan(
            body, (state, guard if guard is not None else guard_init()),
            batches,
        )
        return state, mets

    # ------------------------------------------------- pipelined K-step scan

    def _pipe_prologue(self, state: TrainState, batch0, guard=None,
                       keep_rows=True) -> PipelineCarry:
        """Fill the pipeline: full split-phase lookup of the window's
        first batch (identical program to the sequential lookup)."""
        tables = self._tables_in(state.tables)
        with scopes.scope(self._lookup_phase):
            routes = self._route_all(batch0, True)
            tables, pending = self._resolve_all(
                tables, routes, state.step, True
            )
            views, res = self._finish_all(
                tables, pending, batch0, True, keep_rows=keep_rows
            )
        return PipelineCarry(
            inner=state.replace(tables=self._tables_out(tables)),
            batch=batch0, views=views, bundle_res=res, guard=guard,
        )

    def _pipe_step(self, carry: PipelineCarry, batch_next, lr):
        """One pipelined train step: dense fwd/bwd + sparse apply + dense
        update for the CARRIED batch t, interleaved with the lookahead for
        batch t+1 —

          1. route+resolve(t+1) issued BEFORE the dense compute (no data
             dependency on it: route reads only ids, resolve reads
             keys/meta which the diet apply never writes) so XLA's async
             collectives hide the id exchange + probe behind the matmuls;
          2. dense fwd/bwd on the carried (finished) lookup of batch t;
          3. sparse apply of batch t;
          4. finish(t+1) — value gather + embedding exchange — AFTER the
             apply, so batch t+1 sees post-apply tables: exact, no
             staleness.

        `batch_next=None` is the window epilogue (nothing to prefetch);
        the returned carry's lookahead fields are then stale garbage and
        only `.inner` is meaningful."""
        state = carry.inner
        step = state.step
        tables = self._tables_in(state.tables)
        if batch_next is not None:
            with scopes.scope(scopes.PHASE_ROUTE_NEXT):
                routes = self._route_all(batch_next, True)
                tables, pending = self._resolve_all(
                    tables, routes, step + 1, True
                )
        views = carry.views
        prev_batch = carry.batch
        g_dense, g_embs, mets = self._fwd_bwd(state.dense, views, prev_batch)
        with scopes.scope(scopes.PHASE_SPARSE_APPLY):
            tables = self._apply_all(tables, carry.bundle_res, g_embs, step, lr)
        guard = carry.guard
        if self.sentinel is not None:
            # Sentinel over batch t: the apply above wrote batch t's rows,
            # so the row pass reads them BEFORE finish(t+1)'s gather.
            with scopes.scope(scopes.PHASE_SENTINEL):
                tables, mets["_sentinel"] = self._sentinel_observe(
                    tables, carry.bundle_res, mets["loss"], g_dense, g_embs,
                    step,
                )
                mets, guard = self._sentinel_fold(mets, guard)
        if batch_next is not None:
            with scopes.scope(scopes.PHASE_FINISH_EXCHANGE):
                views_n, res_n = self._finish_all(
                    tables, pending, batch_next, True
                )
        else:
            batch_next, views_n, res_n = prev_batch, views, carry.bundle_res
        return PipelineCarry(
            inner=self._dense_update(state, g_dense, tables, mets),
            batch=batch_next, views=views_n, bundle_res=res_n, guard=guard,
        ), mets

    def _steps_pipelined(self, state: TrainState, batches, lr, guard=None):
        """K-step device loop with the one-batch lookahead rotated through
        the scan carry (pipeline_mode != "off"): prologue looks up batch
        0, each scan iteration consumes the carried lookup and prefetches
        the next batch's, the peeled epilogue consumes the last. Bit-
        identical to the sequential scan — tests/test_pipeline_overlap.py
        pins exactness on table ints, values and losses."""
        if self.sentinel is not None and guard is None:
            from deeprec_tpu.guard.sentinel import guard_init

            guard = guard_init()
        batch0 = jax.tree.map(lambda x: x[0], batches)
        rest = jax.tree.map(lambda x: x[1:], batches)
        carry = self._pipe_prologue(state, batch0, guard)

        def body(carry, batch_next):
            return self._pipe_step(carry, batch_next, lr)

        carry, mets = jax.lax.scan(body, carry, rest)
        carry, tail = self._pipe_step(carry, None, lr)
        mets = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b[None]]), mets, tail
        )
        return carry.inner, mets

    def forward_views(self, state: TrainState, batch):
        """Readonly lookup pass (no inserts/counters): per-feature views
        plus per-bundle results. Shared by eval and the serving predictor."""
        _, views, bundle_res = self._lookup_all(
            self._tables_in(state.tables), batch, state.step, False
        )
        return views, bundle_res

    def probs_from_views(self, state: TrainState, views, batch):
        """Label-free forward: views -> sigmoid probabilities (dict per
        task for multi-task models). Returns (logits, probs)."""
        embs = {n: v[0].astype(jnp.float32) for n, v in views.items()}
        inputs = self._build_inputs(embs, views, batch)
        out = self.model.apply(state.dense, inputs, train=False)
        if isinstance(out, dict):
            probs = {k: jax.nn.sigmoid(v) for k, v in out.items()}
        else:
            probs = jax.nn.sigmoid(out)
        return out, probs

    def _eval_impl(self, state: TrainState, batch):
        views, _ = self.forward_views(state, batch)
        out, probs = self.probs_from_views(state, views, batch)
        loss, _ = self._loss_from_logits(out, batch)
        return self._replica_mean(loss), probs

    # ----------------------------------------------------------- auto-stage

    def input_keys(self) -> frozenset:
        """Batch keys the jitted step consumes — the model's input
        signature (sparse + dense feature names; labels ride by the
        'label*' convention, see _loss_from_logits). This is the
        SmartStage boundary derivation
        (/root/reference/tensorflow/core/graph/smart_stage_pass.cc:30)
        reduced to its JAX form: the reference walks the graph to find
        the IO-side cut; here the cut IS the batch dict, so the analysis
        collapses to 'which keys does the step read'."""
        return frozenset(f.name for f in self.sparse_specs) | frozenset(
            f.name for f in self.dense_specs
        )

    def stage_batch(self, batch):
        """Trim a host batch to the input signature and start its async
        device transfer (device_put returns immediately). Idempotent —
        re-staging a staged batch is a cheap no-op."""
        keep = self.input_keys()
        with scopes.host_span(scopes.STAGE_BATCH):
            return self._stage_put({
                k: v for k, v in batch.items()
                if k in keep or k.startswith("label")
            })

    def _stage_put(self, batch):
        # ShardedTrainer overrides with mesh placement.
        return jax.device_put(batch)

    def stage(self, source, depth: int = 2, on_consume=None):
        """Auto-staged input pipeline: wrap any host batch iterator so IO,
        the host->device transfer, and the train step overlap — zero
        manual `staged()` calls, boundary derived from the model (the
        SmartStage user contract). Returns `source` unchanged when the
        trainer was built with stage="off".

        `on_consume`: called once per batch DELIVERED to the train loop
        (not per batch produced) — stream-position carriers
        (CriteoStats.mark_consumed) checkpoint the consumed index through
        this so a restore never skips the ring's in-flight batches.
        When omitted and `source` itself carries the contract
        (mark_consumed/attach_consumer — CriteoStats, the
        ParallelInputPipeline), it is wired automatically: forgetting the
        hookup silently broke exactly-once resume, the worst kind of
        correct-looking bug."""
        if self.stage_mode != "auto":
            return source
        from deeprec_tpu.data.prefetch import Prefetcher

        if on_consume is None:
            mark = getattr(source, "mark_consumed", None)
            if callable(mark):
                attach = getattr(source, "attach_consumer", None)
                if callable(attach):
                    attach()
                on_consume = mark
        pager = getattr(self, "_tier_pager", None)
        return Prefetcher(iter(source), depth=depth,
                          transform=self.stage_batch,
                          on_consume=on_consume,
                          peek=pager.observe if pager is not None else None)

    # --------------------------------------------------------------- public

    def _guard_or_init(self, guard):
        from deeprec_tpu.guard.sentinel import guard_init

        return guard if guard is not None else guard_init()

    def _step_span(self):
        """The host span of one train dispatch (`deeprec.train_step`),
        numbered by a host count of dispatches."""
        n = self._dispatches
        self._dispatches = n + 1
        return scopes.step_span(n)

    def train_step(self, state: TrainState, batch, lr: Optional[float] = None,
                   guard=None):
        # lr always rides as a traced scalar so schedules never recompile.
        # `guard` is the sentinel carry from the PREVIOUS dispatch's mets
        # (guard/sentinel.guard_carry) — a device reference, never read
        # host-side here; omitted entirely when no sentinel is configured
        # so sentinel-less trainers trace the exact legacy signature.
        with self._step_span():
            lr = jnp.asarray(
                self.sparse_opt.lr if lr is None else lr, jnp.float32
            )
            if self.sentinel is None:
                return self._train_step(state, batch, lr)
            return self._train_step(
                state, batch, lr, self._guard_or_init(guard)
            )

    def train_steps(self, state: TrainState, batches,
                    lr: Optional[float] = None, guard=None):
        """Run K train steps in ONE device dispatch (`lax.scan`).

        `batches` is either a list/tuple of K same-shape batch dicts
        (stacked on the spot via `stack_batches`) or an already-stacked
        pytree with a leading [K, ...] axis — pre-stack and `device_put`
        it when the transfer should overlap compute. Returns
        (final_state, metrics) with metric leaves stacked [K] (per-step
        loss/accuracy, so streamed metric accumulation sees every step,
        same as K `train_step` calls). The input state is donated.

        Semantics are exactly K sequential `train_step` calls — table
        insertion/admission/eviction counters and the global step advance
        per inner step. Run checkpoint/eval/maintain() at K-step
        boundaries (they are host-side and see only the returned state).
        Compiles once per K; see docs/perf.md for the K-curve."""
        with self._step_span():
            if isinstance(batches, (list, tuple)):
                batches = stack_batches(batches)
            lr = jnp.asarray(
                self.sparse_opt.lr if lr is None else lr, jnp.float32
            )
            if self.sentinel is None:
                return self._train_steps(state, batches, lr)
            return self._train_steps(state, batches, lr,
                                     self._guard_or_init(guard))

    def train_step_accum(self, state: TrainState, batch, accum_steps: int,
                         lr: Optional[float] = None, guard=None):
        """Micro-batched step: batch leaves [A*B, ...] are split into A
        micro-batches; sparse tables update per micro-batch, dense params
        once — DeepRec's micro_batch_num semantics with scan instead of graph
        duplication. Cuts activation memory A× for large effective batches."""
        def split(x):
            return x.reshape(accum_steps, x.shape[0] // accum_steps,
                             *x.shape[1:])

        with self._step_span():
            lr = jnp.asarray(
                self.sparse_opt.lr if lr is None else lr, jnp.float32
            )
            if self.sentinel is None:
                return self._train_step_accum(
                    state, jax.tree.map(split, batch), lr
                )
            return self._train_step_accum(
                state, jax.tree.map(split, batch), lr,
                self._guard_or_init(guard),
            )

    def eval_step(self, state: TrainState, batch):
        return self._eval_step(state, batch)

    @scopes.host_spanned(scopes.EVICT_TABLES)
    def evict_tables(self, state: TrainState, step=None) -> TrainState:
        """Apply each table's eviction policies (TTL / L2) and rebuild —
        run at checkpoint cadence like the reference
        (docs/docs_en/Feature-Eviction.md). No-op for tables without
        eviction options."""
        step = jnp.asarray(int(state.step) if step is None else step, jnp.int32)
        tables = dict(state.tables)
        for bname, b in self.bundles.items():
            ev = b.table.cfg.ev
            if ev.global_step_evict is None and ev.l2_weight_evict is None:
                continue
            tables[bname] = self._evict_bundle(b, tables[bname], step)
        return TrainState(step=state.step, tables=tables, dense=state.dense,
                          opt_state=state.opt_state)

    def _slot_fills(self, b: Bundle):
        """Optimizer slot init values, so evicted rows are reborn correctly."""
        return tuple(
            (name, init)
            for name, (_, init) in self.sparse_opt.slot_specs(b.table.cfg.dim).items()
        )

    def _evict_bundle(self, b: Bundle, ts, step):
        fills = self._slot_fills(b)
        fn = lambda s: b.table.evict(s, step, slot_fills=fills)
        if b.stacked:
            return jax.vmap(fn)(ts)
        return fn(ts)

    # --------------------------------------------- capacity management

    def _bundle_lead_dims(self, b: Bundle) -> Tuple[int, ...]:
        """Leading axes of this bundle's state arrays before [C, ...]:
        (T,) for stacked groups, () for single tables. ShardedTrainer adds
        the shard axis."""
        return (len(b.features),) if b.stacked else ()

    def _multi_tier_for(self, b: Bundle, idx: Tuple[int, ...]):
        """Lazily build one MultiTierTable per (bundle, member/shard) —
        each holds its own host KV store."""
        from deeprec_tpu.embedding.multi_tier import MultiTierTable

        if not hasattr(self, "_tiers"):
            self._tiers = {}
        key = (b.name, idx)
        if key not in self._tiers:
            # Per-member store paths: every grouped table / shard owns its
            # own disk log — a shared path would interleave members' rows
            # in one log and let each member's index save clobber the rest.
            base = b.table.cfg.ev.storage.storage_path
            member_path = (
                base + "_m" + "_".join(map(str, idx)) if base and idx
                else base
            )
            self._tiers[key] = MultiTierTable(
                b.table, slot_fills=self._slot_fills(b),
                storage_path=member_path,
            )
        return self._tiers[key]

    @staticmethod
    def _state_bytes(ts) -> int:
        return sum(
            a.size * a.dtype.itemsize for a in jax.tree.leaves(ts)
        )

    # ------------------------------------------- unique-budget telemetry

    def _bundle_dedup_counters(self, ts):
        """Host-read (unique, ids, overflow) totals of one bundle's state,
        summed over every leading axis (grouped tables × shards)."""
        import numpy as np

        return (
            int(np.sum(np.asarray(jax.device_get(ts.dedup_unique)))),
            int(np.sum(np.asarray(jax.device_get(ts.dedup_ids)))),
            int(np.sum(np.asarray(jax.device_get(ts.dedup_overflow)))),
        )

    def _per_shard_stats(self, b: Bundle, member_ts):
        """Per-mesh-position owner-load breakdown of one member table, or
        None when there is no shard axis (the base trainer). ShardedTrainer
        overrides — the counters themselves accumulate in
        ShardedTable.resolve."""
        return None

    def dedup_stats(self, state: TrainState) -> Dict[str, Dict[str, float]]:
        """Per-TABLE dedup telemetry since the last counter reset:
        `unique_fraction` (budgeted uniques + overflow over id positions —
        the quantity the auto budget tracks) and `dedup_overflow`. Stacked
        bundles report each member table under its own feature name.

        Sharded trainers additionally report `per_shard` per table — the
        owner-unique/arrival counts and modeled exchange bytes of every
        mesh position plus their max/mean imbalance (ops/traffic.py) — so
        exchange skew is observable from a live TrainState without
        running a bench."""
        import numpy as np

        out: Dict[str, Dict[str, float]] = {}
        for bname, b in self.bundles.items():
            ts = state.tables[bname]
            for k, f in enumerate(b.features):
                member = (
                    jax.tree.map(lambda a: a[k], ts) if b.stacked else ts
                )
                uniq, ids, ovf = self._bundle_dedup_counters(member)
                out[fcol.resolve_table_name(f)] = {
                    "unique_fraction": (
                        round((uniq + ovf) / ids, 4) if ids else None
                    ),
                    "dedup_overflow": ovf,
                }
                per_shard = self._per_shard_stats(b, member)
                if per_shard is not None:
                    out[fcol.resolve_table_name(f)]["per_shard"] = per_shard
                if not b.stacked:
                    break  # shared-table bundles hold one merged counter
        self._publish_dedup_obs(out)
        return out

    @staticmethod
    def _publish_dedup_obs(stats: Dict[str, Dict]) -> None:
        """Mirror the dedup/per-shard telemetry into the obs plane:
        per-table unique-fraction + overflow gauges, and — for sharded
        trainers — the per-shard exchange-bytes series plus the max/mean
        imbalance gauge whose windowed SLOPE is the drift signal
        Placement v2's replan cadence keys off. Values are the host ints
        this method already paid the device_get for; labels (table name,
        shard index) are bounded sets."""
        from deeprec_tpu.obs import metrics as obs_metrics

        if not obs_metrics.metrics_enabled():
            return
        reg = obs_metrics.default_registry()
        for tname, rec in stats.items():
            lab = {"table": tname}
            if rec.get("unique_fraction") is not None:
                reg.gauge("deeprec_dedup_unique_fraction",
                          "budgeted uniques + overflow over id positions",
                          lab).set(rec["unique_fraction"])
            reg.gauge("deeprec_dedup_overflow",
                      "ids past the unique budget since last reset",
                      lab).set(rec.get("dedup_overflow") or 0)
            ps = rec.get("per_shard")
            if not ps:
                continue
            reg.gauge("deeprec_shard_imbalance",
                      "max/mean per-shard exchange-bytes imbalance",
                      lab).set(ps["imbalance"])
            for i, xb in enumerate(ps.get("exchange_bytes", ())):
                reg.gauge("deeprec_shard_exchange_bytes",
                          "modeled exchange bytes per mesh position",
                          {"table": tname, "shard": str(i)}).set(xb)

    @scopes.host_spanned(scopes.UPDATE_BUDGETS)
    def update_budgets(
        self, state: TrainState, *, slack: float = 1.5, ema: float = 0.5
    ) -> Tuple[TrainState, Dict[str, Dict[str, float]]]:
        """Fold the per-table dedup counters into the auto-budget EMA,
        derive each "auto" bundle's budget fraction (slack x EMA, rounded
        UP onto a 1/16 grid so drift inside a bucket never recompiles),
        and reset the counters. Host-side, call at maintain/log cadence —
        a changed bucket rebuilds the jitted steps (budgets resolve at
        trace time; a cached executable would silently keep its old unique
        sizes) so the next dispatch recompiles once. Returns (new_state,
        report) with per-bundle unique_fraction / dedup_overflow /
        unique_budget_fraction."""
        from deeprec_tpu.ops import dedup

        tables = dict(state.tables)
        report: Dict[str, Dict[str, float]] = {}
        changed = False
        for bname, b in self.bundles.items():
            ts = tables[bname]
            uniq, ids, ovf = self._bundle_dedup_counters(ts)
            rep: Dict[str, float] = {"dedup_overflow": ovf}
            if ids > 0:
                # Overflowed ids are uniques the budget refused — count
                # them so a too-tight budget widens instead of latching.
                frac = min(1.0, (uniq + ovf) / ids)
                rep["unique_fraction"] = round(frac, 4)
                old = self._unique_ema.get(bname)
                self._unique_ema[bname] = (
                    frac if old is None else (1.0 - ema) * old + ema * frac
                )
                if self._budget_modes.get(bname) == "auto":
                    new_frac = dedup.auto_budget_fraction(
                        self._unique_ema[bname], slack=slack
                    )
                    changed |= self._auto_frac.get(bname) != new_frac
                    self._auto_frac[bname] = new_frac
            if bname in self._auto_frac:
                rep["unique_budget_fraction"] = self._auto_frac[bname]
            # Reset via *0 so sharded leaves keep their placement. The
            # owner-load telemetry shares the window semantics: stats read
            # since-last-reset, bench windows bracket with update_budgets.
            tables[bname] = ts.replace(
                dedup_unique=ts.dedup_unique * 0,
                dedup_ids=ts.dedup_ids * 0,
                dedup_overflow=ts.dedup_overflow * 0,
                owner_arrivals=ts.owner_arrivals * 0,
                owner_unique=ts.owner_unique * 0,
            )
            report[bname] = rep
        if changed:
            self._make_jits()
        return (
            TrainState(step=state.step, tables=tables, dense=state.dense,
                       opt_state=state.opt_state),
            report,
        )

    def update_placement(
        self, state: TrainState, **kw
    ) -> Tuple[TrainState, Dict[str, Dict[str, float]]]:
        """Recompute the skew-aware shard placement from live counters and
        re-shard tables whose plan changed (parallel/placement.py). The
        base trainer has no shard axis — placement is meaningless, so this
        is a no-op; ShardedTrainer implements it and maintain() runs it
        (through the maybe_replan drift gate) next to update_budgets when
        the trainer was built with placement="plan"."""
        return state, {}

    def maybe_replan(
        self, state: TrainState
    ) -> Tuple[TrainState, Dict[str, Dict[str, float]]]:
        """Drift-driven replan gate: run the placer only when the live
        per-shard imbalance telemetry says the key distribution moved AND
        the modeled gain amortizes the migration. No shard axis on the
        base trainer — no-op; ShardedTrainer implements."""
        return state, {}

    @scopes.host_spanned(scopes.MAINTAIN)
    def maintain(
        self,
        state: TrainState,
        *,
        grow_threshold: float = 0.85,
        max_capacity: Optional[int] = None,
        hbm_budget_bytes: Optional[int] = None,
        step: Optional[int] = None,
        tier_async: bool = False,
    ) -> Tuple[TrainState, Dict[str, Dict[str, float]]]:
        """Close the capacity loop DeepRec's tables close implicitly
        (embedding_var.h:142 LookupOrCreateKey never refuses a key): consume
        each table's insert_fails / occupancy signals and act — demote cold
        rows to the host tier (storage_type=HBM_DRAM), else grow the table.
        Host-side; call at log/checkpoint cadence, NOT per step. Growth
        recompiles downstream jits once per new capacity.

        Returns (new_state, report) where report[bundle] carries occupancy,
        insert_fails, and what action was taken. max_capacity is the cap PER
        TABLE as this trainer shards it (for ShardedTrainer: the global cap;
        it is divided by the shard count internally); non-power-of-two caps
        round down.

        hbm_budget_bytes bounds the TOTAL device bytes of all table state:
        when a needed growth would exceed it, the bundle is auto-tiered —
        cold rows demote to the host store instead of the table growing.
        This is the automated device-placement decision (the reference
        places oversized EVs on CPU by hand; DeepRec multi_tier_storage.h).

        tier_async=True overlaps each member tier's HostKV/DiskKV IO with
        the next dispatches (MultiTierTable.sync_async): maintain() pays
        only the device-side extraction, promotions found in the
        background land at the NEXT maintain() boundary. Capacity-
        pressure syncs (hbm_budget_bytes force path) stay synchronous.
        """
        import numpy as np

        step = int(state.step) if step is None else int(step)
        # Placement BEFORE update_budgets: the replanner wants the
        # window's owner-load counters, which update_budgets resets.
        # maybe_replan is the drift gate — the placer itself runs only
        # when the windowed imbalance telemetry breaches the ReplanConfig
        # trigger and the modeled gain amortizes the migration.
        placement_report = {}
        if getattr(self, "placement", "uniform") == "plan":
            state, placement_report = self.maybe_replan(state)
        # Dedup telemetry: fold counters into the auto-budget EMA,
        # reset them, and carry the per-bundle stats into the report.
        state, dedup_report = self.update_budgets(state)
        total_bytes = (
            sum(self._state_bytes(ts) for ts in state.tables.values())
            if hbm_budget_bytes
            else 0
        )
        if max_capacity:
            # largest power of two <= cap (capacities must be powers of two)
            max_capacity = 1 << (int(max_capacity).bit_length() - 1)
        tables = dict(state.tables)
        report: Dict[str, Dict[str, float]] = {}
        for bname, b in self.bundles.items():
            ts = tables[bname]
            lead = self._bundle_lead_dims(b)
            C = b.table.cfg.capacity
            # Member states: iterate every leading index (tables × shards).
            idxs = list(np.ndindex(*lead)) if lead else [()]
            members = [
                jax.tree.map(lambda a, i=i: a[i] if i else a, ts)
                for i in idxs
            ]
            # Row hygiene (guard/rows.py): rows whose norm exploded past
            # the quantile bound re-initialize HERE, before occupancy /
            # growth read the state — a hot poisoned id must not
            # contaminate the table between checkpoints, and must never
            # trigger a growth it doesn't deserve.
            rows_reinit = 0
            sen = getattr(self, "sentinel", None)
            if sen is not None and sen.row_evict_quantile is not None:
                from deeprec_tpu.guard import rows as guard_rows

                fills = self._slot_fills(b)
                for mi, m in enumerate(members):
                    members[mi], n_bad = guard_rows.anomaly_evict(
                        b.table, m, sen.row_evict_quantile,
                        sen.row_evict_factor, fills,
                    )
                    rows_reinit += n_bad
                if rows_reinit:
                    ts = self._restack(members, lead)
                    from deeprec_tpu.obs import metrics as _obs_metrics

                    if _obs_metrics.metrics_enabled():
                        _obs_metrics.default_registry().counter(
                            "deeprec_guard_rows_reinit",
                            "anomalous table rows re-initialized by "
                            "maintain() row hygiene",
                            {"table": bname},
                        ).inc(rows_reinit)
            occ = max(int(b.table.size(m)) for m in members) / C
            fails_each = [int(m.insert_fails) for m in members]
            fails = sum(fails_each)
            rep = {"occupancy": occ, "insert_fails": fails, "capacity": C}
            if rows_reinit:
                rep["rows_reinit"] = rows_reinit
            rep.update(dedup_report.get(bname, {}))
            if bname in placement_report:
                rep["placement"] = placement_report[bname]
            multi_tier = b.table.cfg.ev.storage.storage_type.value in (
                "hbm_dram", "hbm_dram_ssd"
            )
            if multi_tier:
                members, demoted, promoted = self._tier_sync(
                    b, idxs, members, step, tier_async=tier_async
                )
                rep.update(demoted=demoted, promoted=promoted)
                ts = self._restack(members, lead)
            elif fails > 0 or occ > grow_threshold:
                # Size by the WORST member (each member has its own slots);
                # summing across shards would overprovision every shard.
                worst = max(fails_each)
                new_c = C * 2
                while worst > 0 and new_c < (worst + occ * C) * 2:
                    new_c *= 2
                if max_capacity:
                    new_c = min(new_c, max_capacity)
                bundle_bytes = self._state_bytes(ts)
                growth_bytes = bundle_bytes * (new_c // C - 1)
                if (
                    hbm_budget_bytes
                    and total_bytes + growth_bytes > hbm_budget_bytes
                ):
                    # Budget exceeded: auto-place on the host tier instead
                    # of growing — demote cold rows, keep capacity fixed.
                    # force=True: pressure may come from probe clustering
                    # below the high watermark; the tier must still act
                    # (demote to the low mark, or at least rebuild to heal
                    # chains and reset insert_fails).
                    members, demoted, promoted = self._tier_sync(
                        b, idxs, members, step, force=True
                    )
                    rep.update(auto_tiered=True, demoted=demoted,
                               promoted=promoted)
                    ts = self._restack(members, lead)
                elif new_c > C:
                    fills = self._slot_fills(b)
                    members = [
                        b.table.grow(m, new_c, slot_fills=fills)
                        for m in members
                    ]
                    self._set_bundle_capacity(b, new_c)
                    rep["grew_to"] = new_c
                    total_bytes += growth_bytes
                    ts = self._restack(members, lead)
            tables[bname] = ts
            report[bname] = rep
        pager = getattr(self, "_tier_pager", None)
        if pager is not None:
            # The demotes above retired the pump's in-flight gathers and
            # may have demoted rows the staged batches are about to look
            # up — re-probe the pipeline window so the next folds still
            # land before those lookups.
            pager.requeue_recent()
        return (
            TrainState(step=state.step, tables=tables, dense=state.dense,
                       opt_state=state.opt_state),
            report,
        )

    def _tier_sync(self, b: Bundle, idxs, members, step: int,
                   force: bool = False, tier_async: bool = False):
        """Run the host-tier sync over every member state; returns
        (members, total_demoted, total_promoted). tier_async=True routes
        through MultiTierTable.sync_async — the HostKV/DiskKV IO of every
        member overlaps the next dispatches, promotions land at the next
        maintain() boundary. Capacity-pressure syncs (force=True) stay
        synchronous: the caller needs the healed table NOW."""
        demoted = promoted = 0
        members = list(members)
        for k, (i, m) in enumerate(zip(idxs, members)):
            mt = self._multi_tier_for(b, i)
            if tier_async and not force:
                m, stats = mt.sync_async(m, step)
            else:
                m, stats = mt.sync(m, step, force=force)
            members[k] = m
            demoted += stats.demoted
            promoted += stats.promoted
        return members, demoted, promoted

    def tier_stall_ms(self) -> float:
        """Accumulated caller-side multi-tier sync stall across every
        member tier (bench.py `sync_stall_ms` accounting)."""
        return sum(
            mt.sync_stall_ms for mt in getattr(self, "_tiers", {}).values()
        )

    # ------------------------------------------------ overlapped tier paging

    def enable_tier_paging(self, *, depth: int = 4, chunk: int = 256,
                           max_pending: int = 8192):
        """Turn on demand-driven tier paging: a background `TierPrefetcher`
        probes each staged batch's ids (Prefetcher `peek`, before
        `device_put`) against every multi-tier member's host/disk key
        indexes and gathers resident packed rows off the training thread;
        `fold_tier_prefetch(state)` folds them back into the device tables
        at dispatch boundaries through one fixed-chunk compiled promote
        program. Call BEFORE `stage()` — the pager taps the pipeline there.
        Returns the pager (close() it when the run ends; the thread is a
        daemon either way). docs/multi-tier-storage.md#overlapped-tier-paging.

        chunk: fold chunk size — rounded up to a power of two by
        `fold_candidates`, one compile per (table, chunk) then 0
        steady-state compiles."""
        if hasattr(self, "num_shards"):
            # Sharded multi-tier is pinned to uniform routing
            # (docs/placement.md); paging the per-shard members from the
            # base pump needs shard-aware id routing — not wired yet.
            raise NotImplementedError(
                "tier paging is wired for the base Trainer; sharded "
                "multi-tier runs keep maintain(tier_async=True)"
            )
        from deeprec_tpu.embedding.tier_prefetch import TierPrefetcher

        specs = []
        for bname, b in self.bundles.items():
            if b.table.cfg.ev.storage.storage_type.value not in (
                "hbm_dram", "hbm_dram_ssd"
            ):
                continue
            if b.stacked:
                specs.extend(
                    ((bname, (k,)), (f.name,))
                    for k, f in enumerate(b.features)
                )
            else:
                specs.append(
                    ((bname, ()), tuple(f.name for f in b.features))
                )
        if not specs:
            raise ValueError(
                "no multi-tier bundle (storage_type hbm_dram / "
                "hbm_dram_ssd) — nothing to page"
            )

        def extract(batch, specs=tuple(specs)):
            import numpy as np

            out = {}
            for key, names in specs:
                arrs = [
                    np.asarray(batch[n]).reshape(-1)
                    for n in names if n in batch
                ]
                if arrs:
                    out[key] = (
                        np.concatenate(arrs) if len(arrs) > 1 else arrs[0]
                    )
            return out

        self._tier_chunk = int(chunk)
        # resolve via _tiers.get, never _multi_tier_for: the pump must not
        # CREATE tiers (a member that never demoted has nothing resident).
        self._tier_pager = TierPrefetcher(
            resolve=lambda key: getattr(self, "_tiers", {}).get(key),
            extract=extract, depth=depth, max_pending=max_pending,
        )
        return self._tier_pager

    def warm_tier_folds(self, state: TrainState) -> None:
        """Pre-compile every multi-tier member's fixed-chunk fold program
        (an all-sentinel no-op fold per member). Call at the end of a
        warmup phase: the steady-state window then pays zero fold
        compiles even when the first demote lands inside it."""
        import numpy as np

        chunk = getattr(self, "_tier_chunk", 256)
        for bname, b in self.bundles.items():
            if b.table.cfg.ev.storage.storage_type.value not in (
                "hbm_dram", "hbm_dram_ssd"
            ):
                continue
            ts = state.tables[bname]
            lead = self._bundle_lead_dims(b)
            idxs = list(np.ndindex(*lead)) if lead else [()]
            for i in idxs:
                member = jax.tree.map(lambda a, i=i: a[i] if i else a, ts)
                self._multi_tier_for(b, i).warm_fold(member, chunk=chunk)

    def fold_tier_prefetch(self, state: TrainState):
        """Dispatch-boundary half of tier paging: fold every buffered
        candidate package into its member table (revalidated against
        current device freq — a row that trained past its tier copy is
        dropped to the retry set, never clobbered). Host-side, call where
        you'd call maintain() but at a finer cadence (every K-step
        dispatch is fine: with nothing buffered it is two dict reads).
        Returns (new_state, report) with per-bundle folded/dropped counts;
        `state` comes back unchanged when nothing folds."""
        import numpy as np

        pager = getattr(self, "_tier_pager", None)
        if pager is None:
            return state, {}
        keys = pager.pending_keys()
        if not keys:
            return state, {}
        by_bundle: Dict[str, list] = {}
        for key in keys:
            by_bundle.setdefault(key[0], []).append(key)
        tables = dict(state.tables)
        report: Dict[str, Dict[str, int]] = {}
        changed = False
        for bname, bkeys in by_bundle.items():
            b = self.bundles.get(bname)
            if b is None:
                continue
            ts = tables[bname]
            lead = self._bundle_lead_dims(b)
            idxs = list(np.ndindex(*lead)) if lead else [()]
            members = [
                jax.tree.map(lambda a, i=i: a[i] if i else a, ts)
                for i in idxs
            ]
            folded = dropped = 0
            touched = False
            for key in bkeys:
                idx = key[1]
                if idx not in idxs:
                    continue
                cand = pager.take(key)
                if cand is None:
                    continue
                mt = self._multi_tier_for(b, idx)
                k = idxs.index(idx)
                members[k], f, d = mt.fold_candidates(
                    members[k], cand,
                    chunk=getattr(self, "_tier_chunk", 256),
                )
                folded += f
                dropped += d
                touched = touched or bool(f)
            if touched:
                tables[bname] = self._restack(members, lead)
                changed = True
            if folded or dropped:
                report[bname] = {"folded": folded, "dropped": dropped}
        if not changed:
            return state, report
        return (
            TrainState(step=state.step, tables=tables, dense=state.dense,
                       opt_state=state.opt_state),
            report,
        )

    def tier_paging_stats(self) -> Dict[str, float]:
        """Pager + fold accounting for bench/eval reports: pump drop/error
        counters plus the per-tier fold totals (rows, bytes, training-
        thread stall ms — `fold_stall_ms` is the paging analog of the
        `sync_stall_ms` that `tier_stall_ms()` sums)."""
        pager = getattr(self, "_tier_pager", None)
        out: Dict[str, float] = dict(pager.stats()) if pager else {}
        tiers = getattr(self, "_tiers", {}).values()
        out["folded_rows"] = sum(mt.folded_rows for mt in tiers)
        out["fold_bytes"] = sum(mt.fold_bytes for mt in tiers)
        out["fold_stall_ms"] = sum(mt.fold_stall_ms for mt in tiers)
        return out

    def close_tier_paging(self) -> None:
        """Stop the pager pump (safe mid-gather — probes are read-only)."""
        pager = getattr(self, "_tier_pager", None)
        if pager is not None:
            pager.close()
            self._tier_pager = None

    def _restack(self, members, lead):
        """Reassemble member states into the bundle's stacked layout."""
        if not lead:
            return members[0]
        flat = [jax.tree.flatten(m)[0] for m in members]
        treedef = jax.tree.structure(members[0])
        stacked = []
        for leaf_i in range(len(flat[0])):
            arrs = jnp.stack([f[leaf_i] for f in flat])
            stacked.append(arrs.reshape(lead + arrs.shape[1:]))
        return jax.tree.unflatten(treedef, stacked)

    def _set_bundle_capacity(self, b: Bundle, new_c: int) -> None:
        """Point the bundle at the grown capacity (invalidates jit caches
        keyed on the old config — one recompile per growth event)."""
        b.table = EmbeddingTable(
            dataclasses.replace(b.table.cfg, capacity=new_c)
        )

    def evaluate(self, state: TrainState, batches) -> Dict[str, float]:
        """Streamed AUC/loss over an iterable of batches. Multi-task models
        report one AUC per task (labels under 'label_<task>')."""
        aucs: Dict[str, M.AucState] = {}
        total, n = 0.0, 0
        upd = _jit_auc_update
        for batch in batches:
            loss, probs = self.eval_step(state, batch)
            task_probs = probs if isinstance(probs, dict) else {"": probs}
            for task, p in task_probs.items():
                label = batch[f"label_{task}"] if task else batch["label"]
                aucs.setdefault(task, M.AucState.create())
                aucs[task] = upd(aucs[task], p, label)
            total += float(loss)
            n += 1
        out = {"loss": total / max(n, 1)}
        for task, st in aucs.items():
            out[f"auc_{task}" if task else "auc"] = float(M.auc_compute(st))
        return out
