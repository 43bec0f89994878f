"""Budgeted dedup (ops/dedup.py) + unique budgets through the hot path.

Three layers, matching the test_train_steps standard (exact on table ints):

  * engine vs `jnp.unique`: same unique set / counts / inverse semantics
    (hash order instead of sorted order), pad-sentinel collapse, defined
    overflow saturation past the budget, and all of it under `vmap` (the
    stacked-bundle layout); and, array for array, against the same
    contract written in NumPy over a grid of shapes, alone, under `vmap`
    and inside a `lax.scan`.
  * budgeted `lookup_unique` vs the legacy path: identical per-key table
    content when the budget covers the batch; default-serving + no-update
    semantics for overflowed ids when it does not.
  * budgeted trainers: `train_steps` scan == sequential steps exactly on
    table ints for Trainer and ShardedTrainer (allgather and a2a), plus
    the auto-budget measurement loop (update_budgets EMA engage).
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeprec_tpu.config import TableConfig
from deeprec_tpu.data import SyntheticCriteo
from deeprec_tpu.embedding.table import EmbeddingTable, empty_key
from deeprec_tpu.models import WDL
from deeprec_tpu.optim import Adagrad
from deeprec_tpu.ops import dedup
from deeprec_tpu.training import Trainer, stack_batches

SENT = int(np.iinfo(np.int32).min)


def _collapse(ids, pad=-1):
    return np.where(ids == pad, SENT, ids).astype(np.int32)


# ------------------------------------------------------------ engine level


def test_dedup_at_budget_matches_jnp_unique_semantics():
    rng = np.random.default_rng(0)
    for trial in range(4):
        N = int(rng.integers(64, 2000))
        ids = rng.integers(0, int(rng.integers(8, N)), size=N).astype(np.int32)
        ids[rng.random(N) < 0.25] = -1  # padding
        flat = _collapse(ids)
        size = dedup.resolve_size(N, N)  # no-overflow budget
        u, inv, c, ovf = map(
            np.asarray, dedup.dedup_at_budget(jnp.asarray(flat), size, sentinel=SENT)
        )
        ref = np.unique(flat[flat != SENT])
        # same unique set (hash order, not sorted), zero overflow
        assert np.array_equal(np.sort(u[u != SENT]), ref)
        assert ovf == 0
        # sentinel bucket reserved at index 0 with no counts
        assert u[0] == SENT and c[0] == 0
        # inverse reconstructs every real position; pads point at bucket 0
        real = flat != SENT
        assert np.array_equal(u[inv[real]], flat[real])
        assert (inv[~real] == 0).all()
        # counts == occurrences, exactly
        for uu in ref:
            assert c[u == uu][0] == (flat == uu).sum()
        # count mass equals real positions (pads contribute nothing)
        assert c.sum() == real.sum()


def test_dedup_at_budget_overflow_saturation():
    """More distinct ids than budget: exactly budget-many survive, the rest
    are counted in overflow and their positions collapse onto the sentinel
    bucket (inverse 0) — never onto another id's row."""
    N = 512
    flat = np.arange(N, dtype=np.int32)  # all distinct
    size = dedup.resolve_size(100, N)
    u, inv, c, ovf = map(
        np.asarray, dedup.dedup_at_budget(jnp.asarray(flat), size, sentinel=SENT)
    )
    kept = u[u != SENT]
    assert len(kept) == size - 1
    assert ovf == N - len(kept)
    surv = inv > 0
    assert np.array_equal(u[inv[surv]], flat[surv])
    assert (inv[~surv] == 0).all()
    assert c.sum() == surv.sum()


def test_dedup_at_budget_under_vmap():
    rng = np.random.default_rng(3)
    T, N = 5, 384
    ids = rng.integers(0, 60, size=(T, N)).astype(np.int32)
    ids[rng.random((T, N)) < 0.2] = -1
    flat = _collapse(ids)
    size = dedup.resolve_size(N, N)
    vu, vi, vc, vo = jax.vmap(
        lambda f: dedup.dedup_at_budget(f, size, sentinel=SENT)
    )(jnp.asarray(flat))
    for t in range(T):
        u, inv, c, o = (np.asarray(x[t]) for x in (vu, vi, vc, vo))
        su, si, sc, so = map(
            np.asarray,
            dedup.dedup_at_budget(jnp.asarray(flat[t]), size, sentinel=SENT),
        )
        np.testing.assert_array_equal(u, su)
        np.testing.assert_array_equal(inv, si)
        np.testing.assert_array_equal(c, sc)
        assert o == so == 0


def test_dedup_at_budget_weighted_counts():
    """Owner-side dedup segment-sums exchanged counts via `weights`."""
    flat = np.array([7, 7, 9, SENT, 9, 7], np.int32)
    w = np.array([2, 3, 5, 100, 1, 4], np.int32)
    size = dedup.resolve_size(6, 6)
    u, inv, c, _ = map(
        np.asarray,
        dedup.dedup_at_budget(
            jnp.asarray(flat), size, sentinel=SENT, weights=jnp.asarray(w)
        ),
    )
    assert c[u == 7][0] == 2 + 3 + 4
    assert c[u == 9][0] == 5 + 1
    assert c[0] == 0  # sentinel weight never lands


def _np_dedup(flat, size, sent, weights=None):
    """The contract of `dedup_at_budget` written out: the distinct real ids
    in the order of (mixed hash clamped under the sentinel's key, id), the
    first `size - 1` of them at `uids[1:]`, everything else at bucket 0."""
    from deeprec_tpu.utils import hashing

    real = flat != sent
    ids = np.unique(flat[real])
    key = np.minimum(hashing.mix32_np(hashing.fold64_np(ids)),
                     np.uint32(0xFFFFFFFE))
    ids = ids[np.lexsort((ids, key))]
    kept = ids[: size - 1]
    uids = np.full((size,), sent, flat.dtype)
    uids[1: 1 + len(kept)] = kept
    slot = {int(u): j + 1 for j, u in enumerate(kept)}
    inverse = np.array([slot.get(int(x), 0) if ok else 0
                        for x, ok in zip(flat, real)], np.int32)
    w = np.ones(len(flat), np.int64) if weights is None else weights
    counts = np.zeros((size,), np.int64)
    np.add.at(counts, inverse, np.where(inverse > 0, w, 0))
    return (uids, inverse, counts.astype(np.int32),
            np.int32(max(len(ids) - (size - 1), 0)))


def _case(name):
    """`(flat, size, weights, dtype)` of one named case, ids from a seed."""
    rng = np.random.default_rng(sum(map(ord, name)))
    dtype, weights = np.int32, None

    def draw(n, high, pad, low=0):
        ids = rng.integers(low, high, size=n)
        ids[rng.random(n) < pad] = -1
        return ids

    if name == "zipf_like_quarter_padded":
        ids, budget = draw(1500, 90, 0.25), 1500
    elif name == "all_distinct_no_padding":
        ids, budget = rng.permutation(700), 700
    elif name == "size_over_n":           # resolve_size: _mult8(N + 1) = N + 7
        ids, budget = draw(65, 40, 0.1), 65
    elif name == "size_over_n_all_distinct":
        ids, budget = rng.permutation(33) + 5, 33
    elif name == "all_sentinel":
        ids, budget = np.full(96, -1), 16
    elif name == "one_id_n_times":
        ids, budget = np.full(128, 77), 128
    elif name == "n_equals_budget_exactly":    # 23 distinct, size 24
        ids, budget = np.concatenate([np.arange(23), draw(177, 23, 0.2)]), 23
    elif name == "one_over_budget":            # 24 distinct, size 24
        ids, budget = np.concatenate([np.arange(24), draw(176, 24, 0.2)]), 23
    elif name == "far_over_budget":
        ids, budget = rng.permutation(512), 100
    elif name == "weighted_under_overflow":
        ids, budget = draw(400, 120, 0.15), 50
        weights = rng.integers(0, 9, size=400)
    elif name == "weighted_covering_budget":
        ids, budget = draw(300, 50, 0.3), 300
        weights = rng.integers(1, 1000, size=300)
    elif name == "negative_and_large_ids":
        ids, budget = draw(600, 2**31 - 1, 0.1, low=-(2**31) + 1), 256
        ids[::7] = ids[0]
    elif name == "two_ids_share_the_key_under_the_sentinels":
        # mix32 sends these to 0xFFFFFFFF and 0xFFFFFFFE: clamped, one key
        pair = np.array([857579651, -606117695])
        ids = np.concatenate([pair[rng.integers(0, 2, 40)], draw(60, 30, .3)])
        ids, budget = rng.permutation(ids), 100
    elif name == "int64_ids":                  # distinct ids, equal low words
        ids = draw(500, 60, 0.2).astype(np.int64)
        ids = np.where(ids >= 0, ids + (ids % 3 << 40), ids)
        budget, dtype = 500, np.int64
    elif name == "int64_ids_over_budget_weighted":
        ids = (draw(300, 2**62, 0.1, low=2**33)).astype(np.int64)
        ids[::5] = ids[1]
        budget, dtype = 40, np.int64
        weights = rng.integers(0, 5, size=300)
    else:
        raise KeyError(name)
    sent = int(np.iinfo(dtype).min)
    flat = np.where(ids == -1, sent, ids).astype(dtype)
    return flat, dedup.resolve_size(budget, len(flat)), weights, dtype


CASES = [
    "zipf_like_quarter_padded", "all_distinct_no_padding", "size_over_n",
    "size_over_n_all_distinct", "all_sentinel", "one_id_n_times",
    "n_equals_budget_exactly", "one_over_budget", "far_over_budget",
    "weighted_under_overflow", "weighted_covering_budget",
    "negative_and_large_ids", "two_ids_share_the_key_under_the_sentinels",
    "int64_ids", "int64_ids_over_budget_weighted",
]


@pytest.mark.parametrize("name", CASES)
def test_dedup_at_budget_is_its_contract_alone_under_vmap_and_in_a_scan(name):
    """Array for array the NumPy contract; then the same batch as one of 5
    tables under `vmap` and as one of 3 steps of a `lax.scan`, exact."""
    flat, size, weights, dtype = _case(name)
    sent = int(np.iinfo(dtype).min)
    want = _np_dedup(flat, size, sent, weights)
    if name == "size_over_n":
        assert size > len(flat)
    if name == "n_equals_budget_exactly":
        assert (want[0] != sent).sum() == size - 1 and want[3] == 0
    if name == "one_over_budget":
        assert want[3] == 1
    if name == "weighted_under_overflow":
        assert want[3] > 0
    if name == "two_ids_share_the_key_under_the_sentinels":
        from deeprec_tpu.utils import hashing
        pair = np.array([857579651, -606117695]).astype(np.uint32)
        assert list(hashing.mix32_np(pair)) == [0xFFFFFFFF, 0xFFFFFFFE]
        assert set(pair.astype(np.int32)) <= set(flat)

    with jax.enable_x64(dtype == np.int64):
        w = None if weights is None else jnp.asarray(weights, jnp.int32)

        def one(f, w):
            return dedup.dedup_at_budget(f, size, sentinel=sent, weights=w)

        got = jax.jit(one)(jnp.asarray(flat), w)
        assert [g.dtype for g in got] == [dtype, np.int32, np.int32, np.int32]
        for g, e in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), e)

        # 5 tables / 3 steps: this batch and rotations of it with other ids
        # padded, so the lanes differ and every lane has its own answer
        rows = [flat] + [
            np.where(np.arange(len(flat)) % (k + 2) == 0, sent,
                     np.roll(flat, k)) for k in range(1, 5)]
        stack = jnp.asarray(np.stack(rows))
        ws = None if w is None else jnp.stack([w] * 5)
        batched = jax.jit(jax.vmap(one, in_axes=(0, None if w is None else 0))
                          )(stack, ws)
        _, scanned = jax.lax.scan(
            lambda c, x: (c, one(x, w)), 0, stack[:3])
        for t, row in enumerate(rows):
            e = _np_dedup(row, size, sent, weights)
            for k in range(4):
                np.testing.assert_array_equal(np.asarray(batched[k][t]), e[k])
                if t < 3:
                    np.testing.assert_array_equal(
                        np.asarray(scanned[k][t]), e[k])


# ------------------------------------------------------------ table level


def _table(**kw):
    return EmbeddingTable(TableConfig(name="t", dim=4, capacity=1 << 10, **kw))


def test_lookup_unique_budget_matches_legacy_per_key():
    """With a covering budget, the budgeted lookup builds the same table as
    the legacy sort-unique path: same key set, per-key freq/version/values,
    and per-position embeddings."""
    t = _table()
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, 50, size=(16, 4)).astype(np.int32))
    s0, r0 = t.lookup_unique(t.create(), ids, step=1)
    size = dedup.resolve_size(64, 64)
    s1, r1 = t.lookup_unique(t.create(), ids, step=1, unique_size=size)
    k0, k1 = np.asarray(s0.keys), np.asarray(s1.keys)
    occ0, occ1 = k0 != SENT, k1 != SENT
    assert set(k0[occ0].tolist()) == set(k1[occ1].tolist())
    f0 = dict(zip(k0.tolist(), np.asarray(s0.freq).tolist()))
    f1 = dict(zip(k1.tolist(), np.asarray(s1.freq).tolist()))
    for k in k0[occ0].tolist():
        assert f0[k] == f1[k]
    # per-position embeddings identical across dedup orders
    e0 = np.asarray(r0.embeddings)[np.asarray(r0.inverse)]
    e1 = np.asarray(r1.embeddings)[np.asarray(r1.inverse)]
    np.testing.assert_allclose(e0, e1, atol=0)
    # telemetry counters recorded on both paths
    assert int(s1.dedup_unique) == int(s0.dedup_unique) == occ0.sum()
    assert int(s1.dedup_ids) == ids.size


def test_lookup_unique_budget_overflow_serves_default():
    """Ids past the budget: counted in dedup_overflow, not inserted, and
    their positions serve the blocked default (0.0) for the step."""
    cfg = TableConfig(name="t", dim=4, capacity=1 << 10)
    t = EmbeddingTable(cfg)
    ids = jnp.arange(100, dtype=jnp.int32)
    size = dedup.resolve_size(10, 100)
    s, r = t.lookup_unique(t.create(), ids, step=0, unique_size=size)
    kept = size - 1
    assert int(s.dedup_overflow) == 100 - kept
    assert int(t.size(s)) == kept
    inv = np.asarray(r.inverse)
    emb = np.asarray(r.embeddings)[inv]
    dropped = inv == 0
    assert dropped.sum() == 100 - kept
    np.testing.assert_array_equal(emb[dropped], 0.0)
    # non-dropped ids get real (initializer) embeddings
    assert np.abs(emb[~dropped]).sum() > 0


def test_table_budget_never_applies_to_eval_lookups():
    """An int cfg.unique_budget budgets TRAIN lookups only: eval/serving
    must read resident keys exactly (and overflow on read-only state would
    be invisible to the counters)."""
    t = _table(unique_budget=8)
    ids = jnp.arange(20, dtype=jnp.int32)
    s, _ = t.lookup_unique(t.create(), ids, step=0)  # train: budget applies
    assert int(s.dedup_overflow) > 0
    _, r = t.lookup_unique(s, ids, train=False)  # eval: exact U=N
    assert len(np.unique(np.asarray(r.inverse))) == 20


def test_trainer_budget_typo_rejected():
    """The trainer-wide override shares the config grammar check — an
    unvalidated typo would silently mean "auto"."""
    with pytest.raises(ValueError, match="unique_budget"):
        Trainer(_model(), Adagrad(lr=0.1), unique_budget="Off")


def test_default_unique_size_resolution():
    """cfg.unique_budget routes the no-argument lookup: int engages the
    budgeted dedup at that size, None/"auto"/"off" keep legacy U=N."""
    assert _table().default_unique_size(128) is None
    assert _table(unique_budget="auto").default_unique_size(128) is None
    assert _table(unique_budget="off").default_unique_size(128) is None
    sz = _table(unique_budget=32).default_unique_size(128)
    assert sz == dedup.resolve_size(32, 128)
    # resolve_size caps at the no-overflow size and reserves the sentinel
    assert dedup.resolve_size(10_000, 64) == dedup.resolve_size(64, 64)


# ---------------------------------------------------------- trainer level


def _model():
    return WDL(emb_dim=8, capacity=1 << 12, hidden=(16,), num_cat=4,
               num_dense=2)


def _batches(K=4, batch_size=64, seed=7):
    gen = SyntheticCriteo(batch_size=batch_size, num_cat=4, num_dense=2,
                          vocab=500, seed=seed)
    batches = [{k: jnp.asarray(v) for k, v in gen.batch().items()}
               for _ in range(K)]
    for t in range(1, K):
        batches[t]["C1"] = batches[t]["C1"] + jnp.int32(10_000 * t)
    return batches


def _assert_tables_exact(s_a, s_b):
    for bname in s_a.tables:
        a, b = s_a.tables[bname], s_b.tables[bname]
        np.testing.assert_array_equal(np.asarray(a.keys), np.asarray(b.keys))
        np.testing.assert_array_equal(np.asarray(a.freq), np.asarray(b.freq))
        np.testing.assert_array_equal(
            np.asarray(a.version), np.asarray(b.version)
        )
        np.testing.assert_allclose(
            np.asarray(a.values), np.asarray(b.values), atol=1e-5
        )


def test_budgeted_train_matches_legacy_per_key():
    """Fixed covering budget vs legacy: same loss stream and same per-key
    table content after training (layouts differ — hash vs sorted order)."""
    batches = _batches()
    tr0 = Trainer(_model(), Adagrad(lr=0.1), optax.adam(2e-3))
    tr1 = Trainer(_model(), Adagrad(lr=0.1), optax.adam(2e-3),
                  unique_budget=64)
    s0, s1 = tr0.init(0), tr1.init(0)
    for b in batches:
        s0, m0 = tr0.train_step(s0, b)
        s1, m1 = tr1.train_step(s1, b)
        np.testing.assert_allclose(
            float(m0["loss"]), float(m1["loss"]), atol=1e-6
        )
    for bname in s0.tables:
        a, b = s0.tables[bname], s1.tables[bname]
        ka, kb = np.asarray(a.keys), np.asarray(b.keys)
        for t in range(ka.shape[0] if ka.ndim > 1 else 1):
            k0 = ka[t] if ka.ndim > 1 else ka
            k1 = kb[t] if kb.ndim > 1 else kb
            assert set(k0[k0 != SENT].tolist()) == set(k1[k1 != SENT].tolist())


def test_train_steps_scan_parity_with_budget():
    """K-step scan == K sequential steps, exact on table ints, with the
    budgeted dedup engaged (fixed budget)."""
    K = 4
    batches = _batches(K)
    tr = Trainer(_model(), Adagrad(lr=0.1), optax.adam(2e-3),
                 unique_budget=64)
    s_seq = tr.init(0)
    seq_losses = []
    for b in batches:
        s_seq, m = tr.train_step(s_seq, b)
        seq_losses.append(float(m["loss"]))
    s_scan, mets = tr.train_steps(tr.init(0), stack_batches(batches))
    assert mets["loss"].shape == (K,)
    np.testing.assert_allclose(np.asarray(mets["loss"]), seq_losses,
                               atol=1e-5)
    _assert_tables_exact(s_scan, s_seq)
    # dedup telemetry accumulates identically through the scan carry
    for bname in s_scan.tables:
        np.testing.assert_array_equal(
            np.asarray(s_scan.tables[bname].dedup_unique),
            np.asarray(s_seq.tables[bname].dedup_unique),
        )


def test_auto_budget_measure_then_engage():
    """"auto": the first window runs at U=N seeding the counters; after
    update_budgets the quantized EMA budget engages, training continues,
    and stats report per-table fractions."""
    batches = _batches()
    tr = Trainer(_model(), Adagrad(lr=0.1), unique_budget="auto")
    s = tr.init(0)
    for b in batches:
        s, _ = tr.train_step(s, b)
    assert not tr._auto_frac  # not engaged yet
    stats = tr.dedup_stats(s)
    assert all(0 < v["unique_fraction"] <= 1 for v in stats.values())
    s, report = tr.update_budgets(s)
    assert tr._auto_frac  # engaged
    for rep in report.values():
        assert 0 < rep["unique_budget_fraction"] <= 1
    # counters were reset
    for ts in s.tables.values():
        assert int(np.sum(np.asarray(ts.dedup_ids))) == 0
    before = {k: v for k, v in tr._auto_frac.items()}
    for b in batches:
        s, m = tr.train_step(s, b)
    assert np.isfinite(float(m["loss"]))
    # overflow stays 0: the budget's slack covers the measured fraction
    assert all(
        v["dedup_overflow"] == 0 for v in tr.dedup_stats(s).values()
    )
    assert tr._auto_frac == before  # no drift without update_budgets


def test_auto_budget_engages_compiled_step_and_eval_stays_exact():
    """update_budgets must reach ALREADY-COMPILED executables: train on
    low-unique batches (tight budget), then feed a high-unique batch of
    the same shape — the budgeted trace must overflow, proving the jit
    caches were rebuilt (a stale executable would still run at U=N).
    Eval lookups on the same trainer stay exact at U=N."""
    gen = SyntheticCriteo(batch_size=64, num_cat=4, num_dense=2, vocab=500,
                          seed=1)
    low = {k: jnp.asarray(v) for k, v in gen.batch().items()}
    high = {k: jnp.asarray(v) for k, v in gen.batch().items()}
    for c in range(1, 5):
        low[f"C{c}"] = jnp.asarray(np.arange(64) % 4 + 1000 * c, jnp.int32)
        high[f"C{c}"] = jnp.asarray(np.arange(64) + 1000 * c, jnp.int32)
    tr = Trainer(_model(), Adagrad(lr=0.1), unique_budget="auto")
    s = tr.init(0)
    s, _ = tr.train_step(s, low)  # compiles the step at U=N
    s, rep = tr.update_budgets(s)  # ~0.06 fraction -> tight budget bucket
    assert all(r["unique_budget_fraction"] < 0.5 for r in rep.values())
    s, _ = tr.train_step(s, high)  # same avals as the pre-budget trace
    ovf = sum(v["dedup_overflow"] for v in tr.dedup_stats(s).values())
    assert ovf > 0  # the budgeted executable really ran
    # Eval/serving is never budgeted: a high-unique eval batch resolves
    # more uniques than the train budget allows.
    views, _ = tr.forward_views(s, high)
    inv = np.asarray(views["C1"][1])
    assert len(np.unique(inv)) == 64


def test_update_budgets_rebuild_recompiles_then_runs_steady():
    """The PR 2 stale-executable contract, pinned as a compile budget
    (analysis/trace_guard.py): steady-state training after warmup
    compiles NOTHING; update_budgets engaging a new budget bucket
    REBUILDS the jitted step (the next dispatch really compiles — a
    stale executable would be a silent cache hit at the old U); and the
    rebuilt step is itself steady afterwards."""
    from deeprec_tpu.analysis import trace_guard

    batches = _batches()
    tr = Trainer(_model(), Adagrad(lr=0.1), unique_budget="auto")
    s = tr.init(0)
    s, m = tr.train_step(s, batches[0])  # warmup: compiles the U=N step
    jax.block_until_ready(m["loss"])
    with trace_guard(max_compiles=0, note="pre-budget steady state"):
        for b in batches:
            s, m = tr.train_step(s, b)
        jax.block_until_ready(m["loss"])
    s, _ = tr.update_budgets(s)  # budget bucket engages -> jits rebuilt
    with trace_guard(max_compiles=None) as g:
        s, m = tr.train_step(s, batches[0])
        jax.block_until_ready(m["loss"])
    assert g.compiles > 0, (
        "update_budgets engaged a budget but the next dispatch compiled "
        "nothing — the stale pre-budget executable is still serving"
    )
    with trace_guard(max_compiles=0, note="post-budget steady state"):
        for b in batches:
            s, m = tr.train_step(s, b)
        jax.block_until_ready(m["loss"])


def test_maintain_reports_dedup_and_resets():
    batches = _batches()
    tr = Trainer(_model(), Adagrad(lr=0.1), unique_budget="auto")
    s = tr.init(0)
    for b in batches:
        s, _ = tr.train_step(s, b)
    s, report = tr.maintain(s)
    assert all("unique_fraction" in r for r in report.values())
    for ts in s.tables.values():
        assert int(np.sum(np.asarray(ts.dedup_ids))) == 0


# ---------------------------------------------------------- sharded level


@pytest.fixture(scope="module")
def mesh():
    from deeprec_tpu.parallel import make_mesh

    return make_mesh(8)


@pytest.mark.parametrize("comm", ["allgather", "a2a"])
def test_sharded_budget_scan_parity(mesh, comm):
    """Budgeted dedup BEFORE the exchange: train_steps scan == sequential,
    exact table ints, on both exchange strategies."""
    from deeprec_tpu.parallel import ShardedTrainer, shard_batch

    tr = ShardedTrainer(_model(), Adagrad(lr=0.1), optax.adam(2e-3),
                        mesh=mesh, comm=comm, unique_budget=64)
    batches = [shard_batch(mesh, b) for b in _batches(3, seed=2)]
    s_seq = tr.init(0)
    seq_losses = []
    for b in batches:
        s_seq, m = tr.train_step(s_seq, b)
        seq_losses.append(float(m["loss"]))
    s_scan, mets = tr.train_steps(tr.init(0), batches)
    np.testing.assert_allclose(np.asarray(mets["loss"]), seq_losses,
                               atol=1e-5)
    _assert_tables_exact(s_scan, s_seq)


def test_sharded_auto_budget_clamps_at_global_capacity(mesh):
    """The auto-budget capacity clamp must use the GLOBAL table capacity:
    the sharded bundle cfg is per-shard (C/N), but a local batch's unique
    ids hash across every shard — a per-shard clamp would latch the budget
    N× too tight and permanently overflow resident keys."""
    from deeprec_tpu.parallel import ShardedTrainer

    tr = ShardedTrainer(_model(), Adagrad(lr=0.1), mesh=mesh,
                        unique_budget="auto")
    b = next(iter(tr.bundles.values()))
    tr._auto_frac[b.name] = 1.0
    C_local = b.table.cfg.capacity
    n = tr.num_shards * C_local  # far beyond the per-shard capacity
    size = tr._resolve_budget(b, n)
    assert size > dedup.resolve_size(C_local, n)  # not per-shard-clamped
    assert size == dedup.resolve_size(C_local * tr.num_shards, n)


def test_sharded_budget_matches_legacy_keys(mesh):
    """Budgeted vs legacy sharded training agree on losses and on the
    global key set per table (the a2a payload shrank, semantics did not)."""
    from deeprec_tpu.parallel import ShardedTrainer, shard_batch

    batches_raw = _batches(3, seed=5)
    out = {}
    for budget in (None, 64):
        tr = ShardedTrainer(_model(), Adagrad(lr=0.1), mesh=mesh,
                            unique_budget=budget)
        batches = [shard_batch(mesh, b) for b in batches_raw]
        s = tr.init(0)
        losses = []
        for b in batches:
            s, m = tr.train_step(s, b)
            losses.append(float(m["loss"]))
        keys = {
            bname: set(np.asarray(ts.keys).ravel().tolist()) - {SENT}
            for bname, ts in s.tables.items()
        }
        out[budget] = (losses, keys)
    np.testing.assert_allclose(out[None][0], out[64][0], atol=1e-6)
    assert out[None][1] == out[64][1]
