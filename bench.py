"""Benchmark harness — prints ONE JSON line.

Metric: DLRM synthetic-Criteo training throughput (examples/sec) on the
available device, batch 2048, reference protocol mean(steps/sec) × batch
(modelzoo/benchmark/*/README.md). vs_baseline compares against the
reference's best published DLRM number: 188.11 global steps/sec × bs 2048 =
385,249 examples/sec on 1×A100-80G + 64-core Xeon
(docs/docs_en/Smart-Stage.md:182-190, see BASELINE.md).

Multi-step device loop: `--steps-per-dispatch K` (default 16) measures the
`Trainer.train_steps` path — K training steps per host dispatch via
`lax.scan` — and sweeps the K-curve over {1, 4, 16} ∩ [1, K] so the
dispatch-overhead amortization lands in the JSON (`k_curve`, with >= 3
timed repetitions and mean/min/max per K so single-core noise is
distinguishable from regression; see docs/perf.md). The headline `value`
is the requested K's best repetition; `steps_per_dispatch` records it.
`--smoke` (or BENCH_SMOKE=1, used by cibuild) shrinks the sweep and the
timed windows so CI completes quickly.

Unique budgets: `--unique-budget auto` (default) engages the budgeted
dedup (ops/dedup.py) — each table's unique fraction is measured during
pre-fill, folded into an EMA budget, and every downstream op of the lookup/
apply hot path is sized at the budget instead of the full flattened batch;
the JSON records the per-table `unique_fraction`/`dedup_overflow` under
"dedup" plus the run's "unique_budget" mode. `--unique-budget off` keeps the
legacy full-batch sort-unique for A/B comparison.

Runs on whatever platform jax resolves and says which in the JSON
(`device`, `device_kind`, `device_count`); any failure is a non-zero exit.
"""
import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_EXAMPLES_PER_SEC = 188.11 * 2048  # DLRM GPU SmartStage, BASELINE.md

def _error_line(text: str) -> str:
    """The most informative line of a crashed subprocess's output: prefer
    the exception line over jax's traceback-filtering boilerplate."""
    lines = [l.strip() for l in text.strip().splitlines() if l.strip()]
    for l in reversed(lines):
        if "Error" in l or "Exception" in l or "FAILED" in l:
            return l[-200:]
    return lines[-1][-200:] if lines else ""


def _measure_k(trainer, batches, B, k, timed_steps, reps):
    """Throughput at k steps/dispatch: identical pre-fill + warmup schedule
    for every k (same batch sequence), then `reps` timed windows. Returns
    (per-k stats, per-table dedup stats); "examples_per_sec" is the best
    repetition (identical programs show run-to-run noise — the fastest
    window is the least-noisy estimate),
    mean/min/max expose the spread."""
    import jax

    from deeprec_tpu.training import stack_batches

    n = len(batches)
    # Identical budget state for every k: the trainer's EMA persists across
    # the K sweep, so without a reset later ks would pre-fill under the
    # previous k's engaged budget and could land in a different budget
    # bucket — conflating dispatch amortization with budget differences.
    trainer._unique_ema.clear()
    trainer._auto_frac.clear()
    trainer._make_jits()
    state = trainer.init(0)
    # Pre-fill: populate the table through the single-step path so every k
    # starts timing from the same table occupancy.
    for i in range(16):
        state, mets = trainer.train_step(state, batches[i % n])
    jax.block_until_ready(mets["loss"])
    if trainer.unique_budget is not None:
        # Fold the pre-fill's measured unique fractions into the budgets so
        # the warmed/timed windows run the dedup at-budget
        # (docs/perf.md); the one recompile lands in the warmup window.
        state, _ = trainer.update_budgets(state)

    steps_k = max(k, timed_steps - timed_steps % k)
    ndisp = steps_k // k
    if k == 1:
        def window(state):
            for i in range(steps_k):
                state, mets = trainer.train_step(state, batches[i % n])
            return state, mets
    else:
        stacked = [
            stack_batches([batches[(d * k + i) % n] for i in range(k)])
            for d in range(ndisp)
        ]

        def window(state):
            for d in range(ndisp):
                state, mets = trainer.train_steps(state, stacked[d])
            return state, mets

    # Warmup window: compiles the k-path, advances the same steps_k steps.
    state, mets = window(state)
    jax.block_until_ready(mets["loss"])

    # Steady-state compile budget: after the warmup window every timed rep
    # must be pure cache-hit dispatch — an XLA compile inside the timed
    # loop means something retraces per step (the DRT001 class) and the
    # throughput number is garbage. Smoke runs HARD-FAIL on it
    # (trace_guard raises); full runs record the count into the JSON,
    # where tools/roofline.py --assert-compiles gates it.
    from deeprec_tpu.analysis import trace_guard

    budget = 0 if os.environ.get("BENCH_SMOKE") == "1" else None
    times = []
    with trace_guard(max_compiles=budget, note=f"K={k} steady state") as g:
        for _ in range(reps):
            t0 = time.perf_counter()
            state, mets = window(state)
            jax.block_until_ready(mets["loss"])
            times.append(time.perf_counter() - t0)
    ex = [steps_k * B / t for t in times]
    return {
        "examples_per_sec": round(max(ex), 1),
        "mean": round(sum(ex) / len(ex), 1),
        "min": round(min(ex), 1),
        "max": round(max(ex), 1),
        "ms_per_step": round(min(times) / steps_k * 1e3, 3),
        "timed_steps": steps_k,
        "reps": reps,
        "steady_compiles": g.compiles,
    }, trainer.dedup_stats(state)


def _traffic_report(trainer, budget_mode, dedup_stats):
    """The traffic-diet artifact: modeled per-step embedding-engine bytes
    (before vs after the diet, at the measured single-device shape AND the
    reference sharded DLRM shape) plus MEASURED stablehlo gather/scatter
    counts of the single-table lookup+apply program, next to the model's
    expected counts. `tools/roofline.py --assert-traffic <json>` fails when
    model and measurement drift."""
    import jax
    import jax.numpy as jnp

    from deeprec_tpu.ops import dedup
    from deeprec_tpu.ops import traffic as T
    from deeprec_tpu.optim.apply import apply_gradients, ensure_slots

    # Measured unique fraction (auto budgets) scales the touched rows.
    fracs = [
        s["unique_fraction"] for s in dedup_stats.values()
        if s.get("unique_fraction")
    ]
    uf = round(sum(fracs) / len(fracs), 4) if fracs else 1.0

    slot_widths = tuple(
        w for (shape, _) in trainer.sparse_opt.slot_specs(16).values()
        for w in shape
    ) or (0,)
    shapes = {
        "measured_1dev": dict(num_shards=1, comm=None),
        "reference_8dev_allgather": dict(num_shards=8, comm="allgather"),
    }
    modeled = {}
    for name, kw in shapes.items():
        before = T.dlrm_reference_traffic(
            diet=False, exchange_dtype="float32", unique_fraction=uf,
            slot_widths=slot_widths, **kw,
        )
        after = T.dlrm_reference_traffic(
            diet=True, exchange_dtype="bfloat16", unique_fraction=uf,
            slot_widths=slot_widths, **kw,
        )
        modeled[name] = {
            "before_bytes": round(before["total_bytes"]),
            "after_bytes": round(after["total_bytes"]),
            "wire_after_bytes": round(after["wire_bytes"]),
            "reduction": round(
                1.0 - after["total_bytes"] / before["total_bytes"], 4
            ),
        }

    # Measured op counts: lower the single-table train lookup+apply at a
    # small static shape (op COUNTS are shape-independent) on both the
    # diet and the legacy-apply arm.
    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.embedding.table import EmbeddingTable

    t = EmbeddingTable(TableConfig(name="_traffic_probe", dim=16,
                                   capacity=1 << 12))
    s = ensure_slots(t, t.create(), trainer.sparse_opt)
    ids = jnp.arange(256, dtype=jnp.int32)
    budgeted = budget_mode != "off"
    U = dedup.resolve_size(128, 256) if budgeted else None

    def prog(s, ids, diet):
        s, res = t._lookup_unique_impl(s, ids, jnp.int32(0), True, -1, U)
        g = jnp.ones_like(res.embeddings, jnp.float32)
        return apply_gradients(t, s, trainer.sparse_opt, res, g, step=0,
                               reuse_rows=diet, stamp_meta=not diet)

    n_slots = sum(1 for n in s.slots if not n.startswith("scalar/"))
    ops = {}
    for arm, diet in (("diet", True), ("legacy_apply", False)):
        txt = jax.jit(  # noqa: DRT001 — built once per bench invocation, reused across the timed loop
            lambda s, ids, d=diet: prog(s, ids, d)
        ).lower(s, ids).as_text()
        ops[arm] = T.count_stablehlo_ops(txt)
    return {
        "unique_fraction": uf,
        "engine_bytes_per_step": modeled["measured_1dev"]["after_bytes"],
        "modeled": modeled,
        "ops_measured": ops,
        "ops_model": {
            "diet": T.expected_lookup_apply_ops(
                diet=True, budgeted=budgeted, n_row_slots=n_slots),
            "legacy_apply": T.expected_lookup_apply_ops(
                diet=False, budgeted=budgeted, n_row_slots=n_slots),
        },
        "budgeted": budgeted,
    }


def _skew_bench_model(dims):
    """Linear model over T skewed single-hot tables + 2 dense features —
    shared by the placement grid arm and the drift arm (same structure,
    different dims/zipf constants)."""
    import jax
    import jax.numpy as jnp

    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.features import DenseFeature, SparseFeature

    t_tables = len(dims)

    class SkewModel:
        features = [
            SparseFeature(
                f"C{i+1}",
                table=TableConfig(
                    name=f"C{i+1}", dim=dims[i], capacity=1 << 13
                ),
            )
            for i in range(t_tables)
        ] + [DenseFeature("I1", 1), DenseFeature("I2", 1)]

        def init(self, key):
            return {
                "w": jax.random.normal(key, (sum(dims) + 2,)) * 0.05
            }

        def apply(self, dense, inputs, train):
            x = jnp.concatenate(
                [inputs.pooled[f"C{i+1}"] for i in range(t_tables)]
                + [inputs.dense["I1"], inputs.dense["I2"]],
                -1,
            )
            return x @ dense["w"]

    return SkewModel()


def _placement_workload():
    """Skew-aware placement bench (round 12): measured per-shard
    exchange-bytes imbalance, uniform hash vs the adopted ShardPlan, on a
    skewed multi-table 8-shard workload.

    Runs in its OWN subprocess (stdout = one JSON line) because it needs
    the virtual 8-device CPU mesh — forcing 8 host devices in the main
    bench process would change the headline single-device measurement.

    Workload: 4 single-hot tables with heterogeneous dims (64/48/16/8 —
    per-table row bytes are a placer input, ops/traffic.py
    exchange_row_bytes) drawing per-table bounded-zipf ids from ONE shared
    raw id space (`SyntheticCriteo(offset_ids=False)`): every table's head
    is the same raw ids, so under `hash_shard` they hammer the same owner
    shards — the correlated-head case the plan's owner-offset rotation +
    hot-key re-routing flattens. Protocol: prefill window under uniform
    routing (fills the freq/owner counters), measure imbalance_before +
    uniform step time; `update_placement` adopts the plan (mode="uniform"
    skips adoption — the comparison arm); measure imbalance_after + plan
    step time on the SAME batch sequence. `tools/roofline.py
    --assert-imbalance` gates the ratio and the step-time bound in CI."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.data import SyntheticCriteo
    from deeprec_tpu.features import DenseFeature, SparseFeature
    from deeprec_tpu.optim import Adagrad
    from deeprec_tpu.parallel import ShardedTrainer, make_mesh, shard_batch

    mode = os.environ.get("BENCH_PLACEMENT", "grid")
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    N = 8
    ZIPF = [2.6, 2.4, 2.2, 2.0]
    DIMS = [64, 48, 16, 8]
    T_TABLES = len(ZIPF)
    B = 128
    n_batches = 8 if smoke else 12
    reps = 2 if smoke else 3

    mesh = make_mesh(N)
    gen = SyntheticCriteo(
        batch_size=B, num_cat=T_TABLES, num_dense=2, vocab=200_000,
        seed=7, zipf_a=ZIPF, offset_ids=False,
    )
    sb = [
        shard_batch(mesh, {k: jnp.asarray(v) for k, v in gen.batch().items()})
        for _ in range(n_batches)
    ]
    tr = ShardedTrainer(
        _skew_bench_model(DIMS), Adagrad(lr=0.1), mesh=mesh,
        placement="plan",
    )
    st = tr.init(0)

    def per_shard_bytes(state):
        per = np.zeros(N)
        for _, d in tr.dedup_stats(state).items():
            ps = d.get("per_shard")
            if ps:
                per += np.asarray(ps["exchange_bytes"])
        return per

    def window(state):
        """One timed pass over the batch sequence (counters accumulate)."""
        t0 = time.perf_counter()
        for i in range(n_batches):
            state, mets = tr.train_step(state, sb[i])
        jax.block_until_ready(mets["loss"])
        return state, (time.perf_counter() - t0) / n_batches * 1e3

    def measure(state):
        """Reset the owner counters, run `reps` timed windows; imbalance
        comes off the counters the windows accumulated."""
        state, _ = tr.update_budgets(state)
        times = []
        for _ in range(reps):
            state, ms = window(state)
            times.append(ms)
        per = per_shard_bytes(state)
        from deeprec_tpu.ops import traffic as T

        return state, T.shard_imbalance(per), per, round(min(times), 3)

    # Prefill: populate tables + freq counters (and compile) under the
    # uniform default plan, then measure the uniform arm.
    st, _ = window(st)
    st, imb_before, per_before, ms_uniform = measure(st)

    report = {
        "mode": mode,
        "device": jax.devices()[0].platform,
        "num_shards": N,
        "num_tables": T_TABLES,
        "zipf": ZIPF,
        "dims": DIMS,
        "batch": B,
        "imbalance_before": round(imb_before, 4),
        "step_ms": {"uniform": ms_uniform},
        "per_shard_exchange_bytes": {
            "uniform": [round(float(x)) for x in per_before]
        },
    }
    if mode != "uniform":
        st, plan_rep = tr.update_placement(st)
        adopted = [b for b, r in plan_rep.items() if r.get("adopted")]
        st, imb_after, per_after, ms_plan = measure(st)
        report.update({
            "imbalance_after": round(imb_after, 4),
            "imbalance_ratio": round(imb_before / max(imb_after, 1e-9), 3),
            "adopted_bundles": adopted,
            "moved_rows": sum(
                r.get("moved", 0) for r in plan_rep.values()
            ),
            "hot_keys": (tr.last_placement or {}).get("hot_keys"),
            "modeled": {
                "imbalance_before":
                    (tr.last_placement or {}).get("imbalance_current"),
                "imbalance_after":
                    (tr.last_placement or {}).get("imbalance_candidate"),
            },
        })
        report["step_ms"]["plan"] = ms_plan
        report["per_shard_exchange_bytes"]["plan"] = [
            round(float(x)) for x in per_after
        ]
    if mode in ("grid", "drift"):
        report["drift"] = _placement_drift_arm(smoke)
    print(json.dumps(report))


def _placement_drift_arm(smoke):
    """Drifting-skew placement arm (round 19): the hot-key set rotates
    mid-stream (`SyntheticCriteo(zipf_rotate_every=)`) under a live
    `placement="plan"` trainer on the budgeted a2a exchange — the
    workload the drift-driven replanner exists for.

    Protocol: one dominant-dim zipf-head table + three light tables in a
    shared raw id space; windows of train steps with `maintain()` after
    each (the maybe_replan drift gate runs exactly as production would).
    The trainer first adopts a plan off the early windows; at the
    midpoint the generator rotates the hot set, the adopted plan goes
    stale, the measured imbalance spikes, and the replanner must catch
    it AUTOMATICALLY — hysteresis-triggered, amortization-approved,
    never forced. Records the per-window imbalance trajectory, the
    replan/migration accounting, the a2a overflow counters (must be 0:
    the drift-safety margin of the per-dest budget covers the stale
    window), and the per-dest-budget wire diet next to the v1
    global-headroom model (measured bucket == modeled vector max).
    `tools/roofline.py --assert-imbalance` gates all of it in CI."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.data import SyntheticCriteo
    from deeprec_tpu.features import DenseFeature, SparseFeature
    from deeprec_tpu.ops import traffic as T
    from deeprec_tpu.optim import Adagrad
    from deeprec_tpu.parallel import ShardedTrainer, make_mesh, shard_batch
    from deeprec_tpu.parallel.placement import ReplanConfig

    N = 8
    ZIPF = [3.0, 1.6, 1.4, 1.2]
    DIMS = [128, 8, 8, 8]
    T_TABLES = len(ZIPF)
    B = 512
    spw = 2 if smoke else 3  # steps per maintain window
    # ONE rotation at the midpoint: pre == post keeps exactly one
    # rotate_every boundary inside the run (the generator rotates at
    # every multiple).
    pre = 4 if smoke else 5  # windows before the hot set rotates
    post = 4 if smoke else 5  # windows after

    mesh = make_mesh(N)
    gen = SyntheticCriteo(
        batch_size=B, num_cat=T_TABLES, num_dense=2, vocab=200_000,
        seed=11, zipf_a=ZIPF, offset_ids=False,
        zipf_rotate_every=pre * spw,
    )
    tr = ShardedTrainer(
        _skew_bench_model(DIMS), Adagrad(lr=0.1), mesh=mesh, comm="a2a",
        placement="plan", placement_hot_budget=64,
        replan=ReplanConfig(threshold=1.4, sustain=1, cooldown=1,
                            horizon_steps=20_000),
    )
    st = tr.init(0)

    def window_imbalance(state):
        per = np.zeros(N)
        for _, d in tr.dedup_stats(state).items():
            ps = d.get("per_shard") if isinstance(d, dict) else None
            if ps:
                per += np.asarray(ps["exchange_bytes"])
        return T.shard_imbalance(per)

    trajectory = []
    post_drift_auto = 0
    last_sb = None
    for w in range(pre + post):
        for _ in range(spw):
            last_sb = shard_batch(
                mesh, {k: jnp.asarray(v) for k, v in gen.batch().items()}
            )
            st, mets = tr.train_step(st, last_sb)
        jax.block_until_ready(mets["loss"])
        imb = window_imbalance(st)
        before = int(tr._replan_stats["replans"])
        st, _ = tr.maintain(st)
        replanned = int(tr._replan_stats["replans"]) > before
        if replanned and w >= pre:
            post_drift_auto += 1
        trajectory.append({
            "window": w, "imbalance": round(imb, 4),
            "post_drift": w >= pre, "replanned": replanned,
        })

    # One settling step AFTER the last maintain(): an adoption on the
    # final window updates plan_dest_hot/plan_hot_count and rebuilds the
    # jits, but last_a2a_budgets/bucket/unique only refresh at the next
    # TRACE — without this step the measured==modeled budget assert
    # below would compare the NEW plan's model against the OLD plan's
    # compiled bucket and fail spuriously. Re-runs the LAST drawn batch
    # (never a fresh draw — the next index would cross a SECOND
    # rotate_every boundary and train one step on a third hot set the
    # protocol never replans).
    st, mets = tr.train_step(st, last_sb)
    jax.block_until_ready(mets["loss"])

    # Post-drift peak = worst window up to and including the first
    # post-drift replan; recovery = the final window (plan re-settled).
    post_w = [t for t in trajectory if t["post_drift"]]
    peak = 0.0
    for t in post_w:
        peak = max(peak, t["imbalance"])
        if t["replanned"]:
            break
    recovered = post_w[-1]["imbalance"] if post_w else None

    overflow = sum(
        int(np.sum(np.asarray(jax.device_get(ts.a2a_overflow))))
        for ts in st.tables.values()
    )
    # Per-dest budget diet: the bucket each bundle's trace compiled
    # (measured) vs the model's vector max (must agree exactly) vs the
    # v1 global-headroom bucket, in wire bytes (id/count + both payload
    # directions, ops/traffic.py a2a_exchange_wire_bytes).
    budgets = {}
    wire_plan = wire_global = 0.0
    budgets_match = True
    for bname, b in tr.bundles.items():
        sh = tr.sharded[bname]
        bp = tr._plans.get(bname)
        U = sh.last_a2a_unique
        dest_hot = sh.plan_dest_hot
        hot_max = int(bp.dest_hot_counts().max()) if bp else 0
        modeled = T.a2a_dest_budgets(
            unique=U, num_shards=N, slack=sh.a2a_slack,
            dest_hot=dest_hot, hot_count=sh.plan_hot_count,
        )
        match = (
            int(modeled.max()) == sh.last_a2a_bucket
            and np.array_equal(modeled, np.asarray(sh.last_a2a_budgets))
        )
        budgets_match &= match
        g_bucket = T.a2a_bucket_rows_global(
            unique=U, num_shards=N, slack=sh.a2a_slack, hot_max=hot_max,
        )
        n_members = len(b.features) if b.stacked else 1
        cfg = b.table.cfg
        wire_b = 2 if cfg.exchange_dtype == "bfloat16" else 4
        wire_plan += n_members * T.a2a_exchange_wire_bytes(
            bucket_rows=sh.last_a2a_bucket, num_shards=N, dim=cfg.dim,
            wire_bytes=wire_b,
        )
        wire_global += n_members * T.a2a_exchange_wire_bytes(
            bucket_rows=g_bucket, num_shards=N, dim=cfg.dim,
            wire_bytes=wire_b,
        )
        budgets[bname] = {
            "unique": U,
            "bucket_rows": sh.last_a2a_bucket,
            "modeled_bucket_rows": int(modeled.max()),
            "dest_budgets": [int(x) for x in modeled],
            "global_headroom_rows": g_bucket,
            "hot_max": hot_max,
            "measured_eq_modeled": match,
        }
    return {
        "batch": B, "num_shards": N, "zipf": ZIPF, "dims": DIMS,
        "steps_per_window": spw, "windows_pre": pre, "windows_post": post,
        "rotate_at_step": pre * spw,
        "trajectory": trajectory,
        "peak_post_drift": round(peak, 4),
        "recovered_imbalance": (
            round(recovered, 4) if recovered is not None else None
        ),
        "replans": {
            "total": int(tr._replan_stats["replans"]),
            "forced": int(tr._replan_stats["forced_replans"]),
            "post_drift_auto": post_drift_auto,
        },
        "migration_rows": int(tr._replan_stats["migration_rows"]),
        "migration_bytes": float(tr._replan_stats["migration_bytes"]),
        "a2a_overflow": overflow,
        "budgets": budgets,
        "budgets_measured_eq_modeled": bool(budgets_match),
        "wire_bytes_per_dest_model": round(wire_plan, 1),
        "wire_bytes_global_headroom_model": round(wire_global, 1),
        "cost_model": tr.cost_model.info(),
    }


def _mesh_workload():
    """Pod-scale 2-D mesh bench (round 19): flat 1-D exchange vs the
    hierarchical two-tier exchange on the same high-overlap stream.

    Runs in its OWN subprocess (stdout = one JSON line) on the forced
    virtual 8-device CPU mesh, like the placement arm. Workload: the
    skew-bench model drawing per-table zipf ids from one SMALL shared id
    space (`vocab=1500, offset_ids=False`) so devices inside a host group
    see heavily overlapping id sets — the regime the intra-tier
    aggregation exists for (a disjoint stream would make U_g = intra·U
    and the hierarchy pointless).

    Arms (mode "grid" runs all; "1d"/"2d" subsets):
      1d_a2a      make_mesh(8),        comm="a2a"     — the flat baseline
      2d_hier     make_mesh_2d(4, 2),  comm="hier"    — two-tier exchange
      2d_nested   same mesh/comm, pipeline_mode="nested" K-scan — the
                  inter-tier id exchange of batch t+1 hoisted behind
                  dense(t) across BOTH tiers
    Every arm records its first-step loss from a fresh init (the forward
    is exact under the hierarchy — one contributor per psum_scatter
    position — so all arms must agree BITWISE), single-step and K-scan
    ms/step under trace_guard (steady compiles: contract 0), and the a2a
    overflow counters (contract 0).

    The hier arm also records the per-tier wire model at the measured
    unique budget — `ops/traffic.py hier_exchange_bytes` next to
    `flat_exchange_tier_bytes` (the flat a2a mapped onto the same 2×4
    topology) — plus the compiled inter bucket vs the model's vector max
    (must agree exactly, same discipline as the drift arm's budgets).
    `tools/roofline.py --assert-hierarchy` gates: inter-tier modeled
    bytes ≤ total_flat/intra AND ≤ 0.5× flat inter-host bytes, 0
    overflow, 0 steady compiles, bitwise loss parity, nested K-scan
    within tolerance of the unpipelined hier K-scan."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeprec_tpu.analysis import trace_guard
    from deeprec_tpu.data import SyntheticCriteo
    from deeprec_tpu.ops import traffic as T
    from deeprec_tpu.optim import Adagrad
    from deeprec_tpu.parallel import (
        ShardedTrainer, make_mesh, make_mesh_2d, shard_batch,
    )
    from deeprec_tpu.training import stack_batches

    mode = os.environ.get("BENCH_MESH", "grid")
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    N, INTRA, INTER = 8, 4, 2
    GROUP_FACTOR = 1.5
    SLACK = 2.0
    ZIPF = [2.2, 2.0, 1.8, 1.6]
    DIMS = [32, 16, 16, 8]
    # Batch large enough that the per-device unique budget clears the
    # multiple-of-8 bucket rounding by a wide margin — at tiny U the
    # rounding, not the hierarchy, sets the inter bucket and the modeled
    # ratios are granularity noise.
    B = 1024
    K = 4
    n_batches = 8
    prefill = 4 if smoke else 8
    reps = 2 if smoke else 3
    timed_steps = 4 if smoke else 8

    gen = SyntheticCriteo(
        batch_size=B, num_cat=len(DIMS), num_dense=2, vocab=1500,
        seed=5, zipf_a=ZIPF, offset_ids=False,
    )
    host_batches = [
        {k: jnp.asarray(v) for k, v in gen.batch().items()}
        for _ in range(n_batches)
    ]

    def run_arm(mesh, comm, pipeline_mode="off", group_factor=None):
        tr = ShardedTrainer(
            _skew_bench_model(DIMS), Adagrad(lr=0.1), mesh=mesh, comm=comm,
            a2a_slack=SLACK, pipeline_mode=pipeline_mode, pipeline_chunks=2,
            hier_group_factor=group_factor,
        )
        sb = [shard_batch(mesh, b) for b in host_batches]
        st = tr.init(0)
        # First step from a FRESH init on the shared batch: the parity
        # anchor (forward is exact, so every arm must agree bitwise).
        st, mets = tr.train_step(st, sb[0])
        first_loss = float(mets["loss"])
        for i in range(1, prefill):
            st, mets = tr.train_step(st, sb[i % n_batches])
        jax.block_until_ready(mets["loss"])

        # Timed single-step windows. Record-only guard (the gate reads
        # the count): the arm is measured, not hard-failed mid-bench.
        times = []
        with trace_guard(max_compiles=None, note=f"mesh {comm} step") as g1:
            for _ in range(reps):
                t0 = time.perf_counter()
                for i in range(timed_steps):
                    st, mets = tr.train_step(st, sb[i % n_batches])
                jax.block_until_ready(mets["loss"])
                times.append((time.perf_counter() - t0) / timed_steps * 1e3)
        # Snapshot NOW: .compiles reads the process-wide counter live, so
        # a late read would absorb the scan warmup's legitimate compiles.
        step_compiles = g1.compiles
        # K-step scan arm (where pipeline_mode engages).
        sh = NamedSharding(mesh, P(None, tr.axis))
        stacked = [
            jax.device_put(
                stack_batches(
                    [host_batches[(d * K + i) % n_batches] for i in range(K)]
                ),
                sh,
            )
            for d in range(2)
        ]
        st, mets = tr.train_steps(st, stacked[0])  # warm: compile K-path
        jax.block_until_ready(mets["loss"])
        scan_times = []
        with trace_guard(max_compiles=None, note=f"mesh {comm} scan") as g2:
            for _ in range(reps):
                t0 = time.perf_counter()
                for d in range(len(stacked)):
                    st, mets = tr.train_steps(st, stacked[d])
                jax.block_until_ready(mets["loss"])
                scan_times.append(
                    (time.perf_counter() - t0) / (len(stacked) * K) * 1e3
                )
        scan_compiles = g2.compiles
        overflow = sum(
            int(np.sum(np.asarray(jax.device_get(ts.a2a_overflow))))
            for ts in st.tables.values()
        )
        return {
            "first_loss": first_loss,
            "step_ms": round(min(times), 3),
            "scan_ms_per_step": round(min(scan_times), 3),
            "steady_compiles": step_compiles + scan_compiles,
            "overflow": overflow,
        }, tr

    arms = {}
    hier_tr = None
    if mode in ("1d", "grid"):
        arms["1d_a2a"], _ = run_arm(make_mesh(N), "a2a")
    if mode in ("2d", "grid"):
        mesh2 = make_mesh_2d(INTRA, INTER)
        arms["2d_hier"], hier_tr = run_arm(
            mesh2, "hier", group_factor=GROUP_FACTOR
        )
        arms["2d_nested"], _ = run_arm(
            make_mesh_2d(INTRA, INTER), "hier", pipeline_mode="nested",
            group_factor=GROUP_FACTOR,
        )

    report = {
        "mode": mode,
        "device": jax.devices()[0].platform,
        "num_devices": N,
        "shape_2d": {"intra": INTRA, "inter": INTER},
        "group_factor": GROUP_FACTOR,
        "slack": SLACK,
        "zipf": ZIPF, "dims": DIMS, "batch": B,
        "steps_per_dispatch": K,
        "arms": arms,
        "first_loss_equal": len({a["first_loss"] for a in arms.values()}) <= 1,
        "overflow": sum(a["overflow"] for a in arms.values()),
        "trace_guard": {
            "budget": 0,
            "steady_state_compiles": sum(
                a["steady_compiles"] for a in arms.values()
            ),
        },
    }
    if hier_tr is not None:
        # Per-tier wire model at each bundle's MEASURED unique budget,
        # next to the flat a2a mapped onto the same topology; the
        # compiled inter bucket must equal the model's vector max.
        tiers = {}
        hier_intra = hier_inter = 0.0
        flat_intra = flat_inter = flat_total = 0.0
        buckets_match = True
        for bname, b in hier_tr.bundles.items():
            sh_t = hier_tr.sharded[bname]
            U = sh_t.last_a2a_unique
            cfg = b.table.cfg
            wire_b = 2 if cfg.exchange_dtype == "bfloat16" else 4
            n_members = len(b.features) if b.stacked else 1
            hb = T.hier_exchange_bytes(
                unique=U, intra=INTRA, inter=INTER, dim=cfg.dim,
                wire_bytes=wire_b, slack=sh_t.a2a_slack,
                group_factor=sh_t.hier_group_factor,
                dest_hot=sh_t.plan_dest_hot, hot_count=sh_t.plan_hot_count,
            )
            fb = T.flat_exchange_tier_bytes(
                unique=U, num_shards=N, intra=INTRA, comm="a2a",
                dim=cfg.dim, wire_bytes=wire_b, slack=sh_t.a2a_slack,
            )
            match = int(hb["bucket_rows"]) == sh_t.last_a2a_bucket
            buckets_match &= match
            hier_intra += n_members * hb["intra_bytes"]
            hier_inter += n_members * hb["inter_bytes"]
            flat_intra += n_members * fb["intra_bytes"]
            flat_inter += n_members * fb["inter_bytes"]
            flat_total += n_members * fb["total_bytes"]
            tiers[bname] = {
                "unique": U,
                "group_unique_budget": int(hb["group_unique_budget"]),
                "bucket_rows": sh_t.last_a2a_bucket,
                "modeled_bucket_rows": int(hb["bucket_rows"]),
                "measured_eq_modeled": match,
            }
        report["hier"] = {
            "per_bundle": tiers,
            "modeled_bytes": {
                "hier_intra": round(hier_intra),
                "hier_inter": round(hier_inter),
                "flat_a2a_intra": round(flat_intra),
                "flat_a2a_inter": round(flat_inter),
                "flat_a2a_total": round(flat_total),
            },
            "inter_ratio_vs_flat_inter": round(
                hier_inter / max(flat_inter, 1e-9), 4
            ),
            "inter_ratio_vs_flat_total_over_intra": round(
                hier_inter / max(flat_total / INTRA, 1e-9), 4
            ),
            "buckets_measured_eq_modeled": bool(buckets_match),
        }
    print(json.dumps(report))


def _run_cpu_mesh_worker(flag: str, what: str) -> dict:
    """Run this file's `flag` arm (placement / mesh) in a child on an
    8-device virtual CPU mesh and return its JSON section. CPU-only so
    far: the child inherits the platform, and a parent on an accelerator
    holds it, so the arm refuses there rather than merge CPU timings into
    a record whose `device` names the accelerator."""
    import jax

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"bench.py {what} arm runs on a virtual CPU mesh; this process "
            f"is on {jax.default_backend()!r} — run it with JAX_PLATFORMS=cpu"
        )
    env = dict(os.environ)
    env[flag] = "1"
    # Force EXACTLY 8 virtual devices: an inherited count (a 1- or
    # 4-device flag from some other arm's environment) would fail
    # make_mesh(8) in the worker, so any existing token is replaced, not
    # respected.
    flags = [
        t for t in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in t
    ]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"]
    )
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, timeout=1200, capture_output=True, text=True,
    )
    if r.returncode != 0:
        raise RuntimeError("%s workload rc=%d: %s" % (
            what, r.returncode, _error_line(r.stderr or "")))
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    raise RuntimeError(f"{what} workload produced no JSON")


def _ckpt_report():
    """Host-choreography stall accounting (round 9): what a checkpoint /
    multi-tier sync costs the TRAINING THREAD, sync vs async, plus the
    incremental-save transfer diet (device-compacted dirty rows vs the
    legacy full-table device->host pull). Small dedicated model — the
    numbers are stall ratios, not throughput, so smoke-scale is fine."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from deeprec_tpu.data import SyntheticCriteo
    from deeprec_tpu.models import WDL
    from deeprec_tpu.optim import Adagrad
    from deeprec_tpu.training import Trainer
    from deeprec_tpu.training.checkpoint import CheckpointManager

    model = WDL(emb_dim=16, capacity=1 << 14, hidden=(32,), num_cat=4,
                num_dense=2)
    tr = Trainer(model, Adagrad(lr=0.1))
    gen = SyntheticCriteo(batch_size=512, num_cat=4, num_dense=2,
                          vocab=6000, seed=0)
    batches = [
        {k: jnp.asarray(v) for k, v in gen.batch().items()} for _ in range(4)
    ]
    st = tr.init(0)
    for b in batches:
        st, mets = tr.train_step(st, b)
    jax.block_until_ready(mets["loss"])

    tmp = tempfile.mkdtemp(prefix="deeprec_bench_ckpt_")
    try:
        ck = CheckpointManager(os.path.join(tmp, "sync"), tr)
        cka = CheckpointManager(os.path.join(tmp, "async"), tr)
        report = {"ckpt_stall_ms": {}, "incr_transfer_bytes": {}}

        st, _ = ck.save(st)
        report["ckpt_stall_ms"]["sync_full"] = ck.last_save["stall_ms"]
        full_bytes = ck.last_save["transfer_bytes"]
        _, _ = cka.save_async(st)
        report["ckpt_stall_ms"]["async_full"] = cka.last_save["stall_ms"]
        cka.wait()

        # dirty a fraction of the table, then delta-save both ways
        st, mets = tr.train_step(st, batches[0])
        jax.block_until_ready(mets["loss"])
        st2, _ = ck.save_incremental(st)
        report["ckpt_stall_ms"]["sync_incr"] = ck.last_save["stall_ms"]
        incr_bytes = ck.last_save["transfer_bytes"]
        _, _ = cka.save_incremental_async(st)
        report["ckpt_stall_ms"]["async_incr"] = cka.last_save["stall_ms"]
        cka.wait()
        report["incr_transfer_bytes"] = {
            "full_tables": int(full_bytes),
            "dirty_compacted": int(incr_bytes),
            "reduction": round(1.0 - incr_bytes / max(full_bytes, 1), 4),
        }

        # multi-tier migration: sync stall vs overlapped extraction
        from deeprec_tpu.config import (
            EmbeddingVariableOption, StorageOption, TableConfig,
        )
        from deeprec_tpu.embedding.multi_tier import MultiTierTable
        from deeprec_tpu.embedding.table import EmbeddingTable

        def tier_run(use_async):
            cfg = TableConfig(
                name="bench_tier", dim=16, capacity=1 << 12,
                ev=EmbeddingVariableOption(storage=StorageOption(
                    storage_type="hbm_dram")),
            )
            t = EmbeddingTable(cfg)
            mt = MultiTierTable(t, high_watermark=0.7, low_watermark=0.5)
            s = t.create()
            s, res = t.lookup_unique(
                s, jnp.arange(3500, dtype=jnp.int32), step=0
            )
            jax.block_until_ready(res.embeddings)
            t0 = time.perf_counter()
            if use_async:
                s, _ = mt.sync_async(s, step=1)
            else:
                s, _ = mt.sync(s, step=1)
            stall = (time.perf_counter() - t0) * 1e3
            if use_async:
                mt.drain(s)
            return round(stall, 3)

        report["sync_stall_ms"] = {
            "sync": tier_run(False), "async": tier_run(True),
        }
        return report
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _tier_paging_report():
    """Overlapped tier paging arm (round 20): rotated-zipf stream sized to
    force demotion, hot set rotated each maintain window so demoted keys
    reappear MID-window. Two arms on the identical stream — paging off
    (promotes only at maintain cadence) vs paging on (TierPrefetcher
    gathers + dispatch-boundary folds) — recording the fresh-init
    (optimizer-state-loss) rate, fold bytes, training-thread stall, step
    time, and steady-state fold compiles. Gated by tools/roofline.py
    --assert-tier: loss rate >=10x lower, 0 steady compiles, fold stall
    <= the async-round stall, step time parity."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeprec_tpu.analysis import trace_guard
    from deeprec_tpu.config import EmbeddingVariableOption, StorageOption
    from deeprec_tpu.models import WDL
    from deeprec_tpu.optim import Adagrad
    from deeprec_tpu.training import Trainer

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    B = 256 if smoke else 512
    warm_steps = 10
    timed_steps = 24 if smoke else 48
    maintain_every = 8
    capacity = 1 << 11
    vocab = 8000
    window = 3000  # uniques live in a rotating zipf window of this width
    rotate = 500   # window shift per maintain window

    steps = warm_steps + timed_steps
    rng = np.random.default_rng(7)

    def zipf_ids(n):
        # a=1.1: flat-tailed — each window's distinct set overruns the
        # demote watermark, and the cold tail keeps re-appearing so the
        # off arm pays fresh re-inits mid-window
        z = rng.zipf(1.1, size=n)
        return (z - 1) % window

    batches = []
    for t in range(steps):
        base = (t // maintain_every) * rotate
        cats = [
            ((zipf_ids(B) + base) % vocab).astype(np.int32)
            for _ in range(2)
        ]
        batches.append({
            "label": rng.integers(0, 2, B).astype(np.float32),
            "I1": rng.normal(size=(B, 1)).astype(np.float32),
            "I2": rng.normal(size=(B, 1)).astype(np.float32),
            "C1": cats[0], "C2": cats[1],
        })

    def build():
        ev = EmbeddingVariableOption(
            storage=StorageOption(storage_type="hbm_dram")
        )
        model = WDL(emb_dim=16, capacity=capacity, hidden=(32,),
                    num_cat=2, num_dense=2, ev=ev)
        tr = Trainer(model, Adagrad(lr=0.1))
        return tr, tr.init(0)

    def resident_keys(tr, cache):
        """Tier-resident key set, recomputed only when a boundary/fold
        changed the stores (revision-keyed — the same discipline the row
        cache uses)."""
        rev = sum(mt._tier_rev for mt in getattr(tr, "_tiers", {}).values())
        if cache.get("rev") != rev:
            keys = set()
            for mt in getattr(tr, "_tiers", {}).values():
                if mt.host is not None:
                    keys.update(int(k) for k in mt.host.export()[0])
                if mt.disk is not None:
                    keys.update(int(k) for k in mt.disk.index)
            cache["rev"], cache["keys"] = rev, keys
        return cache["keys"]

    def run_arm(paging):
        tr, st = build()
        pager = tr.enable_tier_paging(depth=16, chunk=256) if paging else None
        res_cache = {}
        loss_touches = positions = 0
        step_ms = []
        steady_compiles = 0
        warmed = False
        try:
            src = tr.stage(iter(batches), depth=2)
            for i, b in enumerate(src):
                timed = i >= warm_steps
                if paging and timed and not warmed:
                    # pre-compile the fold programs: the first demote (and
                    # so the first real fold) may land inside the timed
                    # window, and a cold compile is not a steady-state one
                    tr.warm_tier_folds(st)
                    warmed = True
                if pager is not None:
                    pager.drain(10.0)
                if paging:
                    # guard ONLY the fold path: the fixed-chunk compile
                    # contract is the fold program's, not maintain's
                    # (demote shapes recompile at their own cadence)
                    if timed:
                        with trace_guard(
                            max_compiles=None, note="tier paging fold"
                        ) as g:
                            st, _ = tr.fold_tier_prefetch(st)
                        steady_compiles += g.compiles
                    else:
                        st, _ = tr.fold_tier_prefetch(st)
                # step timing excludes the fold — fold cost is reported
                # separately as fold_stall_ms
                t0 = time.perf_counter()
                st, mets = tr.train_step(st, b)
                jax.block_until_ready(mets["loss"])
                if timed:
                    step_ms.append((time.perf_counter() - t0) * 1e3)
                # fresh-init accounting AFTER the folds this step saw:
                # a batch position hitting a tier-resident key trains from
                # a re-initialized row — lost optimizer state
                if timed:
                    ids = np.concatenate([
                        np.asarray(jax.device_get(b["C1"])),
                        np.asarray(jax.device_get(b["C2"])),
                    ]).astype(np.int64)
                    res = resident_keys(tr, res_cache)
                    if res:
                        loss_touches += int(np.isin(
                            ids, np.fromiter(res, np.int64, len(res))
                        ).sum())
                    positions += ids.size
                if (i + 1) % maintain_every == 0:
                    st, _ = tr.maintain(st, tier_async=True)
            for mt in getattr(tr, "_tiers", {}).values():
                mt._settle()  # join any in-flight round before reading stalls
            rec = {
                "fresh_init_rate": round(loss_touches / max(positions, 1), 6),
                "loss_touches": loss_touches,
                "positions": positions,
                "step_ms": round(float(np.mean(step_ms)), 3),
                "sync_stall_ms": round(tr.tier_stall_ms(), 3),
            }
            if paging:
                stats = tr.tier_paging_stats()
                rec.update(
                    fold_stall_ms=round(stats["fold_stall_ms"], 3),
                    folded_rows=int(stats["folded_rows"]),
                    fold_bytes=int(stats["fold_bytes"]),
                    gather_errors=int(stats["gather_errors"]),
                    dropped_batches=int(stats["dropped_batches"]),
                    steady_compiles=steady_compiles,
                )
            return rec
        finally:
            if pager is not None:
                tr.close_tier_paging()

    off = run_arm(paging=False)
    on = run_arm(paging=True)
    r0, r1 = off["fresh_init_rate"], on["fresh_init_rate"]
    return {
        "stream": {
            "batch": B, "timed_steps": timed_steps, "vocab": vocab,
            "zipf_window": window, "rotate_per_window": rotate,
            "maintain_every": maintain_every, "capacity": capacity,
        },
        "off": off,
        "on": on,
        # the headline: optimizer-state-loss suppression from paging
        "loss_factor": round(r0 / r1, 2) if r1 > 0 else None,
        "step_time_ratio": round(on["step_ms"] / max(off["step_ms"], 1e-9), 4),
    }


def _overlap_model_inputs(trainer, batches):
    """Host-clock times (best of 8, ms) of the three blocking programs the
    overlap model of `_pipeline_report` is fed with: the whole step, the
    hoistable routing phase alone, and lookup + sparse apply alone (the
    dense side is the difference). A model's inputs on whatever platform
    this runs on, not a phase breakdown: the device time of each phase
    comes from a trace, by the scopes of utils/scopes.py."""
    import jax
    import jax.numpy as jnp

    state = trainer.init(0)
    for i in range(4):
        state, mets = trainer.train_step(state, batches[i % len(batches)])
    jax.block_until_ready(mets["loss"])

    # The hoistable routing phase (id dedup + id exchange; ids only, no
    # table state) — what pipeline_mode="lookahead" overlaps with the
    # dense compute.
    route_jit = jax.jit(lambda b: trainer._route_all(b, True))  # noqa: DRT001 — built once per bench invocation, reused across the timed loop

    def sparse(tables, b, step):
        tables, views, bundle_res = trainer._lookup_all(
            tables, b, step, True
        )
        g = {n: jnp.ones_like(v[0], jnp.float32) for n, v in views.items()}
        return trainer._apply_all(tables, bundle_res, g, step,
                                  jnp.float32(trainer.sparse_opt.lr))

    # DONATES the table pytree (like the step path does) — without
    # donation the output materializes a full copy of every table per call
    # and the copy, not the phase, dominates.
    sparse_jit = jax.jit(sparse, donate_argnums=0)  # noqa: DRT001 — built once per bench invocation, reused across the timed loop
    best = {}

    def clock(name, t0, out):
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) * 1e3
        best[name] = min(best.get(name, ms), ms)

    # Full step FIRST: the sub-program below then takes over (and donates)
    # the final state's table buffers.
    for i in range(8):
        t0 = time.perf_counter()
        state, mets = trainer.train_step(state, batches[i % len(batches)])
        clock("step", t0, mets["loss"])
    # Fresh host-round-tripped scalar: train_step donated the state (and
    # its step buffer) every iteration above.
    step0 = jnp.asarray(int(state.step), jnp.int32)
    # compile outside the timed loop; thread the donated tables through
    tables = sparse_jit(dict(state.tables), batches[0], step0)
    jax.block_until_ready((tables, route_jit(batches[0])))
    for i in range(8):
        b = batches[i % len(batches)]
        t0 = time.perf_counter()
        clock("route", t0, route_jit(b))
        t0 = time.perf_counter()
        tables = sparse_jit(tables, b, step0)
        clock("lookup_plus_apply", t0, tables)
    return {k: round(v, 3) for k, v in best.items()}


def _pipeline_report(trainer, batches, B, k_curve, K, pipeline_arg, smoke):
    """In-step pipelining artifact (round 11): measure the K-step scan
    under each `pipeline_mode` on the identical protocol (`_measure_k` per
    arm; the "off" arm is the already-measured k_curve entry), time the
    hoistable routing phase standalone, and put the measured pipelined
    step next to the overlap model (`ops/traffic.py
    modeled_overlap_step`: exchange time max'd with — not added to —
    dense time).  `tools/roofline.py --assert-overlap <json>` gates CI on
    this section: the pipelined arms must not regress past tolerance and
    the overlap efficiency (modeled / measured) must be recorded."""
    from deeprec_tpu.ops import traffic as T
    from deeprec_tpu.training import Trainer

    chunks = 4
    reps = 2 if smoke else 3
    timed_steps = 8 if smoke else int(os.environ.get("BENCH_TIMED_STEPS", "32"))
    # "grid" = off + lookahead. The chunked arm only differs on SHARDED
    # exchanges (ShardedTable.exchange_chunks); on this single-device
    # protocol it compiles the identical program, so the grid skips it —
    # tools/bench_async.py --pipeline-mode chunked is the mesh measurement.
    # An explicit --pipeline-mode chunked still measures it here on request.
    modes = ["off", "lookahead"]
    if pipeline_arg in ("lookahead", "chunked"):
        modes = ["off", pipeline_arg]

    # Pipelining only engages on the K-step scan; measure every arm at the
    # same K >= 2 (the already-measured k_curve entry serves the "off" arm
    # when it matches).
    K_pipe = max(K, 2)
    grid = {}
    for mode in modes:
        if mode == "off" and str(K_pipe) in k_curve:
            head = k_curve[str(K_pipe)]
            # NB: no steady_compiles here — this arm REUSES the k_curve
            # measurement, whose compile count is already reported under
            # its k arm; copying it would double-count in _guard_record.
            grid[mode] = {
                "ms_per_step": head["ms_per_step"],
                "examples_per_sec": head["examples_per_sec"],
            }
            continue
        # Same model object + optimizers as the headline trainer (bundles
        # are rebuilt per trainer, so sharing the stateless model is safe)
        # — the arms can never drift from the measured protocol.
        tr = Trainer(
            trainer.model, trainer.sparse_opt, trainer.dense_opt,
            grad_averaging=trainer.grad_averaging,
            unique_budget=trainer.unique_budget, pipeline_mode=mode,
            pipeline_chunks=chunks,
        )
        stats, _ = _measure_k(tr, batches, B, K_pipe, timed_steps, reps)
        grid[mode] = {
            "ms_per_step": stats["ms_per_step"],
            "examples_per_sec": stats["examples_per_sec"],
            "steady_compiles": stats["steady_compiles"],
        }

    # Phase decomposition for the model: route (hoistable), dense
    # (overlap target), other (stays serial: value gather + embedding
    # exchange + apply + dense update). Sub-program timings come off the
    # single-step path; the off-arm K-scan step anchors the total.
    times = _overlap_model_inputs(trainer, batches)
    route_ms = times["route"]
    dense_ms = max(0.0, times["step"] - times["lookup_plus_apply"])
    step_off_ms = grid["off"]["ms_per_step"]
    other_ms = max(0.0, step_off_ms - dense_ms - route_ms)
    modeled = {
        mode: round(T.modeled_overlap_step(
            dense_ms=dense_ms, route_ms=route_ms, other_ms=other_ms,
            mode=mode, chunks=chunks,
        ), 3)
        for mode in grid
    }
    pipe_modes = [m for m in grid if m != "off"]
    eff = {
        m: round(modeled[m] / grid[m]["ms_per_step"], 4)
        for m in pipe_modes
        if grid[m]["ms_per_step"] > 0
    }
    report = {
        "modes": grid,
        "chunks": chunks,
        "steps_per_dispatch": K_pipe,
        "phase_ms": {
            "route": route_ms,
            "dense": round(dense_ms, 3),
            "other": round(other_ms, 3),
        },
        "modeled_ms": modeled,
        # modeled max(exchange, dense) step vs the measured pipelined step:
        # 1.0 = the overlap the model promises fully materialized; CPU runs
        # (no async collectives) sit below it by construction.
        "overlap_efficiency": eff,
        "modeled_buffer_bytes": round(T.dlrm_reference_traffic(
            pipeline_mode="lookahead",
        )["pipeline_buffer_bytes"]),
    }
    return report


def _obs_overhead_report(trainer, batches, B, smoke):
    """The telemetry-plane cost artifact (JSON 'obs_overhead', gated by
    tools/roofline.py --assert-obs): two measured single-step arms — the
    TrainLoop per-step instrumentation (one counter inc + gauge set)
    with the obs plane ON vs DEEPREC_OBS=off (no-op singletons) — plus a
    deterministic per-record microbench. `overhead_pct` (the gated
    number) is MODELED from the per-record cost × ops/step over the
    measured step time: two same-program wall-clock arms differ by
    scheduler noise that can exceed any honest overhead bound on a
    shared CI box, while the per-op cost is stable to measure; the raw
    arm timings are recorded alongside for eyeballs. A parse check of
    the live registry's Prometheus rendering rides along."""
    import time as _time

    import jax

    from deeprec_tpu.obs import metrics as om

    n = len(batches)
    steps = 6 if smoke else 16
    reps = 3

    def arm(enabled):
        om.set_metrics_enabled(enabled)
        try:
            reg = om.MetricsRegistry()
            ctr = reg.counter("bench_obs_steps", "bench arm counter")
            gau = reg.gauge("bench_obs_step", "bench arm gauge")
            state = trainer.init(0)
            for i in range(4):  # warm (programs already compiled)
                state, mets = trainer.train_step(state, batches[i % n])
            jax.block_until_ready(mets["loss"])
            times = []
            for _ in range(reps):
                t0 = _time.perf_counter()
                for i in range(steps):
                    state, mets = trainer.train_step(state, batches[i % n])
                    ctr.inc()
                    gau.set(i)
                jax.block_until_ready(mets["loss"])
                times.append(_time.perf_counter() - t0)
            return round(min(times) / steps * 1e3, 4), reg
        finally:
            om.set_metrics_enabled(None)

    on_ms, live_reg = arm(True)
    off_ms, _ = arm(False)

    # Deterministic per-record cost: counter+gauge+histogram round-robin.
    reg = om.MetricsRegistry()
    c = reg.counter("bench_obs_c", "")
    g = reg.gauge("bench_obs_g", "")
    h = reg.histogram("bench_obs_h", "")
    N = 2000 if smoke else 20000
    t0 = _time.perf_counter()
    for i in range(N):
        c.inc()
        g.set(float(i))
        h.record(1e-3)
    per_record_ns = (_time.perf_counter() - t0) / (3 * N) * 1e9
    ops_per_step = 2.0  # TrainLoop: 1 counter inc/step + save-cadence gauges
    modeled_pct = 100.0 * ops_per_step * per_record_ns / (on_ms * 1e6)

    text = live_reg.render_prometheus()
    try:
        series = len(om.parse_prometheus(text))
        parsed = True
    except ValueError:
        series, parsed = 0, False
    return {
        "arms": {"on": {"ms_per_step": on_ms},
                 "off": {"ms_per_step": off_ms}},
        "measured_overhead_pct": round(max(0.0, on_ms / off_ms - 1) * 100, 3),
        "per_record_ns": round(per_record_ns, 1),
        "ops_per_step": ops_per_step,
        "overhead_pct": round(modeled_pct, 5),
        "metrics_parse": {"parsed": parsed, "series": series},
    }


def workload():
    """The measured DLRM loop. Runs on whatever platform jax resolves."""
    import jax
    import jax.numpy as jnp

    from deeprec_tpu.data import SyntheticCriteo
    from deeprec_tpu.models import DLRM
    from deeprec_tpu.optim import Adagrad
    from deeprec_tpu.training import Trainer

    K = max(1, int(os.environ.get("BENCH_K", "16")))
    reps = max(3, int(os.environ.get("BENCH_REPS", "3")))
    timed_steps = int(os.environ.get("BENCH_TIMED_STEPS", "32"))
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    ks = [k for k in (1, 4, 16) if k <= K]
    if K not in ks:
        ks.append(K)
    if smoke:
        timed_steps = min(timed_steps, 8)
        ks = sorted({ks[0], ks[-1]})  # endpoints only: fast CI green

    B = 2048
    # Hash dedup engine (ops/dedup.py): "auto" (default) measures each
    # table's unique fraction during pre-fill and sizes every downstream op
    # at the derived budget; an int fixes the budget; "off" keeps the
    # legacy full-batch sort-unique.
    budget_mode = os.environ.get("BENCH_UNIQUE_BUDGET", "auto")
    unique_budget = (
        None if budget_mode == "off"
        else ("auto" if budget_mode == "auto" else int(budget_mode))
    )
    model = DLRM(emb_dim=16, capacity=1 << 20)
    trainer = Trainer(model, Adagrad(lr=0.05), unique_budget=unique_budget)

    gen = SyntheticCriteo(batch_size=B, vocab=1_000_000, seed=0)
    # Pre-generate host batches so input generation isn't measured.
    batches = [
        {k: jnp.asarray(v) for k, v in gen.batch().items()} for _ in range(8)
    ]

    k_curve = {}
    dedup_stats = {}
    for k in ks:
        k_curve[str(k)], dedup_stats = _measure_k(
            trainer, batches, B, k, timed_steps, reps
        )

    head = k_curve[str(K)]
    ex_per_sec = head["examples_per_sec"]

    # Steady-state compile accounting (analysis/trace_guard.py): every
    # timed arm records how many XLA compiles landed inside its timed
    # windows — the contract is ZERO after warmup. Gated in CI by
    # tools/roofline.py --assert-compiles (and hard-enforced in smoke by
    # the guard itself).
    def _guard_record(arms: dict) -> dict:
        per_arm = {
            name: stats["steady_compiles"]
            for name, stats in arms.items()
            if isinstance(stats, dict) and "steady_compiles" in stats
        }
        return {
            "budget": 0,
            "steady_state_compiles": sum(per_arm.values()),
            "per_arm": per_arm,
        }

    traffic = _traffic_report(trainer, budget_mode, dedup_stats)
    obs_overhead = _obs_overhead_report(trainer, batches, B, smoke)
    ckpt = _ckpt_report()
    # Overlapped tier paging arm (round 20): rotated-zipf demotion stream,
    # paging off vs on — fresh-init (state-loss) rate, fold bytes, stalls,
    # step parity. Gated in CI by tools/roofline.py --assert-tier.
    tier_paging = (
        _tier_paging_report()
        if os.environ.get("BENCH_TIER", "off") != "off"
        else None
    )
    # In-step pipelining grid: measured off/lookahead(/chunked) arms +
    # the overlap model + overlap efficiency (round 11). "off" skips it.
    pipeline_arg = os.environ.get("BENCH_PIPELINE", "grid")
    pipeline = (
        _pipeline_report(trainer, batches, B, k_curve, K, pipeline_arg, smoke)
        if pipeline_arg != "off"
        else None
    )
    # Skew-aware placement arm (round 12): measured per-shard exchange
    # imbalance uniform-hash vs ShardPlan on the 8-shard skewed multi-table
    # workload (own subprocess — needs the virtual mesh). Gated in CI by
    # tools/roofline.py --assert-imbalance.
    placement = (
        _run_cpu_mesh_worker("BENCH_PLACEMENT_WORKER", "placement")
        if os.environ.get("BENCH_PLACEMENT", "off") != "off"
        else None
    )
    # Pod-scale 2-D mesh arm (round 19): flat 1-D a2a vs the two-tier
    # hierarchical exchange (+ nested lookahead) with the per-tier wire
    # model (own subprocess — needs the virtual mesh). Gated in CI by
    # tools/roofline.py --assert-hierarchy.
    mesh_rec = (
        _run_cpu_mesh_worker("BENCH_MESH_WORKER", "mesh")
        if os.environ.get("BENCH_MESH", "off") != "off"
        else None
    )

    # Record the program actually measured — backend, storage layout, and
    # kernel-trust flags — so round-over-round numbers are comparable (the
    # r03->r04 regression was an unrecorded layout change). The layout is
    # read off the measured model's own table configs, not a hardcoded
    # probe shape.
    from deeprec_tpu.embedding.table import EmbeddingTable
    from deeprec_tpu.features import table_configs
    from deeprec_tpu.ops import fused_lookup as _fl

    packs = {
        EmbeddingTable(c).pack()
        for c in table_configs(model.features).values()
    }
    pack = max(packs) if packs else 1
    print(
        json.dumps(
            {
                "metric": "dlrm_criteo_examples_per_sec",
                "value": round(ex_per_sec, 1),
                "unit": "examples/sec",
                "vs_baseline": round(ex_per_sec / BASELINE_EXAMPLES_PER_SEC, 4),
                "steps_per_dispatch": K,
                "repetitions": {
                    "mean": head["mean"], "min": head["min"],
                    "max": head["max"], "n": head["reps"],
                },
                "k_curve": k_curve,
                "device": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "device_count": len(jax.devices()),
                "backend": jax.default_backend(),
                "layout": "packed_x%d" % pack if pack > 1 else "unpacked",
                # Dedup engine telemetry: per-table measured unique fraction
                # + budget-overflowed ids from the timed windows, and the
                # budget mode the run used (comparability across rounds).
                "unique_budget": budget_mode,
                "dedup": dedup_stats,
                # Steady-state retrace gate: compiles observed inside the
                # timed windows of every arm (contract: 0 after warmup) —
                # checked by tools/roofline.py --assert-compiles.
                "trace_guard": _guard_record({
                    **{f"k{kk}": st for kk, st in k_curve.items()},
                    **({f"pipeline_{m}": st
                        for m, st in pipeline["modes"].items()}
                       if pipeline else {}),
                }),
                # Traffic-diet artifact: modeled engine bytes/step (before
                # vs after, measured + reference sharded shapes) and the
                # MEASURED gather/scatter op counts of the hot path, which
                # tools/roofline.py --assert-traffic checks against the
                # model (ops/traffic.py).
                "traffic": traffic,
                # Telemetry-plane cost (round 13): instrumented vs
                # DEEPREC_OBS=off step arms + deterministic per-record
                # cost; tools/roofline.py --assert-obs gates the modeled
                # overhead ≤2% and the /metrics parse check.
                "obs_overhead": obs_overhead,
                # Host-choreography stall accounting (round 9): training-
                # thread ms per checkpoint / tier sync (sync vs async) and
                # the incremental-save transfer diet (dirty-compacted vs
                # full-table device->host bytes).
                "ckpt": ckpt,
                # Overlapped tier paging (round 20): rotated-zipf paging
                # off/on arms — fresh-init (optimizer-state-loss) rate,
                # fold bytes/stall, step parity, steady fold compiles —
                # gated by tools/roofline.py --assert-tier in CI smoke.
                **({"tier_paging": tier_paging} if tier_paging else {}),
                # In-step pipelining (round 11): per-mode K-scan step time,
                # phase decomposition (route / dense / other), the overlap
                # model and its efficiency vs measurement — gated by
                # tools/roofline.py --assert-overlap in CI smoke.
                **({"pipeline": pipeline} if pipeline else {}),
                # Skew-aware placement (round 12): measured per-shard
                # exchange-bytes imbalance before (uniform hash) and after
                # (adopted ShardPlan) + step time per arm — gated by
                # tools/roofline.py --assert-imbalance in CI smoke.
                **({"placement": placement} if placement else {}),
                # Pod-scale 2-D mesh (round 19): per-tier modeled wire
                # bytes of the hierarchical exchange vs flat a2a on the
                # same topology, bitwise loss parity across arms, 0
                # overflow / steady compiles, nested K-scan — gated by
                # tools/roofline.py --assert-hierarchy in CI smoke.
                **({"mesh": mesh_rec} if mesh_rec else {}),
                "flags": {
                    "f32_row": _fl.AUTO_TRUSTS_F32_ROW,
                    "bf16_pair": _fl.AUTO_TRUSTS_BF16_PAIR,
                },
            }
        )
    )


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--steps-per-dispatch", type=int,
                   default=int(os.environ.get("BENCH_K", "16")),
                   help="K training steps per device dispatch (lax.scan); "
                        "the K-curve over {1,4,16} up to K is also measured")
    p.add_argument("--reps", type=int,
                   default=int(os.environ.get("BENCH_REPS", "3")),
                   help="timed repetitions per K (min 3; JSON records "
                        "mean/min/max so noise is visible)")
    p.add_argument("--timed-steps", type=int,
                   default=int(os.environ.get("BENCH_TIMED_STEPS", "32")),
                   help="training steps per timed repetition")
    p.add_argument("--smoke", action="store_true",
                   help="fast CI path: endpoints-only K sweep, short windows")
    p.add_argument("--unique-budget",
                   default=os.environ.get("BENCH_UNIQUE_BUDGET", "auto"),
                   help="dedup unique budget: 'auto' (measured EMA, "
                        "default), an int (fixed ids per lookup), or 'off' "
                        "(legacy full-batch sort-unique)")
    p.add_argument("--pipeline-mode",
                   default=os.environ.get("BENCH_PIPELINE", "grid"),
                   choices=["off", "lookahead", "chunked", "grid"],
                   help="in-step pipelining arms to measure on the K-step "
                        "scan: 'grid' (default) records off + lookahead "
                        "with the overlap model under JSON 'pipeline' "
                        "(chunked only differs on sharded exchanges — see "
                        "tools/bench_async.py); a single mode measures "
                        "just off + that arm; 'off' skips the section")
    p.add_argument("--placement", nargs="?", const="grid",
                   default=os.environ.get("BENCH_PLACEMENT", "off"),
                   choices=["off", "uniform", "plan", "grid"],
                   help="skew-aware placement arm on the 8-shard skewed "
                        "multi-table workload (own subprocess): 'grid' "
                        "(bare --placement) measures uniform-hash AND the "
                        "adopted ShardPlan (imbalance before/after + step "
                        "time, JSON 'placement'); 'uniform' measures only "
                        "the hash baseline; 'plan' is an alias of grid "
                        "(the plan arm needs the uniform window first); "
                        "'off' (default) skips the section")
    p.add_argument("--mesh", nargs="?", const="grid",
                   default=os.environ.get("BENCH_MESH", "off"),
                   choices=["off", "1d", "2d", "grid"],
                   help="pod-scale 2-D mesh arm on the virtual 8-device "
                        "mesh (own subprocess): 'grid' (bare --mesh) "
                        "measures flat 1-D a2a AND the 2x4 hierarchical "
                        "two-tier exchange (+ nested lookahead K-scan) "
                        "with the per-tier wire model (JSON 'mesh'); "
                        "'1d'/'2d' run one side; 'off' (default) skips")
    p.add_argument("--tier-paging", action="store_true",
                   default=os.environ.get("BENCH_TIER", "off") != "off",
                   help="add the overlapped tier paging arm: rotated-zipf "
                        "stream forcing demotion mid-window, paging off vs "
                        "on — fresh-init (state-loss) rate, fold bytes, "
                        "training-thread stall and step-time parity (JSON "
                        "'tier_paging'); gated by roofline --assert-tier")
    args = p.parse_args()
    if args.steps_per_dispatch < 1:
        p.error("--steps-per-dispatch must be >= 1")
    if args.unique_budget not in ("auto", "off"):
        try:
            if int(args.unique_budget) <= 0:
                raise ValueError
        except ValueError:
            p.error("--unique-budget must be 'auto', 'off' or a positive int")
    # workload() and its sub-reports read their parameters from the env.
    os.environ["BENCH_K"] = str(args.steps_per_dispatch)
    os.environ["BENCH_REPS"] = str(args.reps)
    os.environ["BENCH_TIMED_STEPS"] = str(args.timed_steps)
    os.environ["BENCH_UNIQUE_BUDGET"] = str(args.unique_budget)
    os.environ["BENCH_PIPELINE"] = str(args.pipeline_mode)
    os.environ["BENCH_PLACEMENT"] = str(args.placement)
    os.environ["BENCH_MESH"] = str(args.mesh)
    os.environ["BENCH_TIER"] = "on" if args.tier_paging else "off"
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    from deeprec_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    workload()


if __name__ == "__main__":
    if os.environ.get("BENCH_PLACEMENT_WORKER") == "1":
        _placement_workload()
    elif os.environ.get("BENCH_MESH_WORKER") == "1":
        _mesh_workload()
    else:
        main()
