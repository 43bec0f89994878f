"""Traffic diet (forward-residual reuse + fused metadata + bf16 exchanges).

Parity contract: the diet deletes REDUNDANT work — the apply-side value
re-gather (the forward already gathered those rows) and the apply-side
version/dirty re-stamps (the same-step train lookup already stamped them) —
so the diet path must be indistinguishable from the legacy apply
(`apply_gradients(reuse_rows=False, stamp_meta=True)`): bit-identical
keys/freq/version/dirty and identical loss trajectories, single-device and
sharded under both comm modes.  The bf16 wire format is the one deliberate
numeric change and gets its own convergence bound; eval exchanges must
ignore it entirely.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeprec_tpu.config import TableConfig
from deeprec_tpu.data import SyntheticCriteo
from deeprec_tpu.features import DenseFeature, SparseFeature
from deeprec_tpu.models import WDL
from deeprec_tpu.optim import Adagrad
from deeprec_tpu.optim.apply import apply_gradients, ensure_slots
from deeprec_tpu.parallel import ShardedTrainer, make_mesh, shard_batch
from deeprec_tpu.training import Trainer


def J(b):
    return {k: jnp.asarray(v) for k, v in b.items()}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def small():
    return WDL(emb_dim=8, capacity=1 << 12, hidden=(16,), num_cat=4,
               num_dense=2)


def retable(model, **cfg):
    model.features = [
        dataclasses.replace(f, table=dataclasses.replace(f.table, **cfg))
        if isinstance(f, SparseFeature) and f.table is not None
        else f
        for f in model.features
    ]
    return model


class LegacyApplyTrainer(Trainer):
    """The pre-diet apply: re-gather value rows, re-stamp version/dirty."""

    def _apply_one(self, b, state, res, grad, step, lr):
        return apply_gradients(
            b.table, state, self.sparse_opt, res, grad, step=step, lr=lr,
            grad_averaging=self.grad_averaging,
            reuse_rows=False, stamp_meta=True,
        )


class LegacyApplySharded(ShardedTrainer):
    def _apply_one(self, b, state, res, grad, step, lr):
        return self.sharded[b.name].apply_gradients(
            state, self.sparse_opt, res, grad, step=step, lr=lr,
            grad_averaging=self.grad_averaging,
            reuse_rows=False, stamp_meta=True,
        )


def batches_with_inserts(K=4, batch_size=64, seed=7):
    gen = SyntheticCriteo(batch_size=batch_size, num_cat=4, num_dense=2,
                          vocab=400, seed=seed)
    batches = [J(gen.batch()) for _ in range(K)]
    for t in range(1, K):
        batches[t]["C1"] = batches[t]["C1"] + jnp.int32(10_000 * t)
    return batches


def assert_tables_bitwise(s_a, s_b, values_exact=True):
    for bname in s_a.tables:
        a, b = s_a.tables[bname], s_b.tables[bname]
        np.testing.assert_array_equal(np.asarray(a.keys), np.asarray(b.keys))
        np.testing.assert_array_equal(np.asarray(a.freq), np.asarray(b.freq))
        np.testing.assert_array_equal(
            np.asarray(a.version), np.asarray(b.version)
        )
        np.testing.assert_array_equal(
            np.asarray(a.dirty), np.asarray(b.dirty)
        )
        if values_exact:
            np.testing.assert_array_equal(
                np.asarray(a.values), np.asarray(b.values)
            )
        else:
            np.testing.assert_allclose(
                np.asarray(a.values), np.asarray(b.values), atol=1e-6
            )


# ----------------------------------------------------------- exact parity


def test_diet_matches_legacy_apply_single_device():
    batches = batches_with_inserts(4)
    t_diet = Trainer(small(), Adagrad(lr=0.1), optax.adam(2e-3))
    t_leg = LegacyApplyTrainer(small(), Adagrad(lr=0.1), optax.adam(2e-3))
    s_d, s_l = t_diet.init(0), t_leg.init(0)
    for b in batches:
        s_d, m_d = t_diet.train_step(s_d, b)
        s_l, m_l = t_leg.train_step(s_l, b)
        np.testing.assert_allclose(
            float(m_d["loss"]), float(m_l["loss"]), rtol=0, atol=0
        )
    assert_tables_bitwise(s_d, s_l)


@pytest.mark.parametrize("comm", ["allgather", "a2a"])
def test_diet_matches_legacy_apply_sharded(mesh, comm):
    batches = [
        shard_batch(mesh, b) for b in batches_with_inserts(3, seed=5)
    ]
    t_diet = ShardedTrainer(small(), Adagrad(lr=0.1), optax.adam(2e-3),
                            mesh=mesh, comm=comm)
    t_leg = LegacyApplySharded(small(), Adagrad(lr=0.1), optax.adam(2e-3),
                               mesh=mesh, comm=comm)
    s_d, s_l = t_diet.init(0), t_leg.init(0)
    for b in batches:
        s_d, m_d = t_diet.train_step(s_d, b)
        s_l, m_l = t_leg.train_step(s_l, b)
        np.testing.assert_allclose(
            float(m_d["loss"]), float(m_l["loss"]), rtol=0, atol=0
        )
    assert_tables_bitwise(s_d, s_l)


def test_diet_matches_legacy_apply_async(mesh):
    """The async stage re-gathers by design (its carried residual is a step
    stale); its trajectory must equal the pre-diet async path exactly —
    which it is, since stamp_meta=True restores the apply-side stamps."""
    from deeprec_tpu.parallel import AsyncShardedTrainer

    class LegacyAsync(AsyncShardedTrainer):
        def _apply_one(self, b, state, res, grad, step, lr):
            return self.sharded[b.name].apply_gradients(
                state, self.sparse_opt, res, grad, step=step, lr=lr,
                grad_averaging=self.grad_averaging,
                reuse_rows=False, stamp_meta=True,
            )

    batches = [
        shard_batch(mesh, b) for b in batches_with_inserts(4, seed=11)
    ]
    t_a = AsyncShardedTrainer(small(), Adagrad(lr=0.1), optax.adam(2e-3),
                              mesh=mesh)
    t_b = LegacyAsync(small(), Adagrad(lr=0.1), optax.adam(2e-3), mesh=mesh)
    a = t_a.bootstrap(t_a.init(0), batches[0])
    b_ = t_b.bootstrap(t_b.init(0), batches[0])
    for x in batches[1:]:
        a, m_a = t_a.train_step_async(a, x)
        b_, m_b = t_b.train_step_async(b_, x)
        np.testing.assert_allclose(
            float(m_a["loss"]), float(m_b["loss"]), rtol=0, atol=0
        )
    assert_tables_bitwise(a.inner, b_.inner)


def test_diet_matches_legacy_through_train_steps_scan(mesh):
    """K-step scan path: the residual rides the scan body unchanged."""
    batches = batches_with_inserts(4, seed=3)
    t_diet = Trainer(small(), Adagrad(lr=0.1))
    t_leg = LegacyApplyTrainer(small(), Adagrad(lr=0.1))
    s_d, m_d = t_diet.train_steps(t_diet.init(0), batches)
    s_l, m_l = t_leg.train_steps(t_leg.init(0), batches)
    np.testing.assert_array_equal(
        np.asarray(m_d["loss"]), np.asarray(m_l["loss"])
    )
    assert_tables_bitwise(s_d, s_l)


# ------------------------------------------------ residual contract & hazard


def test_unique_lookup_rows_residual_contract():
    """UniqueLookup.rows == the raw post-insert value rows at safe_ix;
    embeddings is its admission-masked view."""
    cfg = TableConfig(name="t", dim=8, capacity=1 << 10)
    from deeprec_tpu.embedding.table import EmbeddingTable

    t = EmbeddingTable(cfg)
    s = t.create()
    s, res = t.lookup_unique(s, jnp.array([5, 5, 9, -1, 3], jnp.int32),
                             step=2)
    safe = jnp.where(res.slot_ix >= 0, res.slot_ix, 0)
    raw = np.asarray(t._gather(s.values, safe, s.capacity))
    np.testing.assert_array_equal(np.asarray(res.rows), raw)
    want = np.where(np.asarray(res.admitted)[:, None], raw, 0.0)
    np.testing.assert_array_equal(np.asarray(res.embeddings), want)


def _shared_model():
    tab = TableConfig(name="item", dim=8, capacity=1 << 10)

    class TinyShared:
        features = [
            SparseFeature("item", table=tab),
            SparseFeature("item2", shared_table="item"),
            DenseFeature("d", 1),
        ]

        def init(self, key):
            return {"w": jax.random.normal(key, (16,)) * 0.1}

        def apply(self, dense, inputs, train):
            x = jnp.concatenate(
                [inputs.pooled["item"], inputs.pooled["item2"]], -1
            )
            return x @ dense["w"]

    return TinyShared()


def test_shared_table_sequential_applies_regather():
    """Two features on ONE shared table with overlapping ids: the second
    apply must see the first apply's writes (re-gather), not its own
    pre-apply residual — parity with the legacy apply proves the bundle
    policy (_bundle_reuse_rows) keeps shared tables safe."""
    rng = np.random.default_rng(0)

    def batch():
        ids = rng.integers(0, 20, size=(32,)).astype(np.int32)
        return J({
            "item": ids,
            "item2": ids[::-1].copy(),  # heavy overlap, different layout
            "d": rng.normal(size=(32, 1)).astype(np.float32),
            "label": (rng.random(32) < 0.5).astype(np.float32),
        })

    batches = [batch() for _ in range(3)]
    t_diet = Trainer(_shared_model(), Adagrad(lr=0.2))
    t_leg = LegacyApplyTrainer(_shared_model(), Adagrad(lr=0.2))
    # the bundle is shared (2 features, unstacked) -> both arms re-gather
    b = next(iter(t_diet.bundles.values()))
    assert not t_diet._bundle_reuse_rows(b)
    s_d, s_l = t_diet.init(0), t_leg.init(0)
    for x in batches:
        s_d, m_d = t_diet.train_step(s_d, x)
        s_l, m_l = t_leg.train_step(s_l, x)
        np.testing.assert_allclose(
            float(m_d["loss"]), float(m_l["loss"]), rtol=0, atol=0
        )
    assert_tables_bitwise(s_d, s_l)


# ------------------------------------------------------------ bf16 exchange


def test_bf16_exchange_convergence_a2a(mesh):
    """bf16 wire on the zipf a2a workload: learns, and lands within a small
    epsilon of the fp32-exchange trajectory (the one deliberate numeric
    change of the diet)."""
    gen = SyntheticCriteo(batch_size=512, num_cat=4, num_dense=2,
                          vocab=2000, zipf_a=1.6, seed=13)
    batches = [shard_batch(mesh, J(gen.batch())) for _ in range(20)]

    t_bf = ShardedTrainer(small(), Adagrad(lr=0.2), optax.adam(5e-3),
                          mesh=mesh, comm="a2a")
    t_f32 = ShardedTrainer(
        retable(small(), exchange_dtype="float32"),
        Adagrad(lr=0.2), optax.adam(5e-3), mesh=mesh, comm="a2a",
    )
    assert next(iter(t_bf.bundles.values())).table.cfg.exchange_dtype == "bfloat16"
    s_bf, s_f = t_bf.init(0), t_f32.init(0)
    l_bf, l_f = [], []
    for b in batches:
        s_bf, m = t_bf.train_step(s_bf, b)
        l_bf.append(float(m["loss"]))
        s_f, m = t_f32.train_step(s_f, b)
        l_f.append(float(m["loss"]))
    # both learn
    assert np.mean(l_bf[-5:]) < np.mean(l_bf[:5])
    # and the bf16 tail tracks fp32 within epsilon
    gap = abs(np.mean(l_bf[-5:]) - np.mean(l_f[-5:]))
    assert gap < 0.02 * np.mean(l_f[-5:]), (l_bf[-5:], l_f[-5:])


def test_eval_exchange_stays_fp32(mesh):
    """The exchange_dtype knob must not touch eval: the same trained state
    evaluated under a bf16-exchange trainer and an fp32-exchange trainer
    produces bit-identical losses (both run the exact fp32 eval wire)."""
    gen = SyntheticCriteo(batch_size=256, num_cat=4, num_dense=2,
                          vocab=1500, seed=9)
    t_f32 = ShardedTrainer(
        retable(small(), exchange_dtype="float32"),
        Adagrad(lr=0.2), optax.adam(5e-3), mesh=mesh,
    )
    st = t_f32.init(0)
    for _ in range(6):
        st, _ = t_f32.train_step(st, shard_batch(mesh, J(gen.batch())))
    t_bf = ShardedTrainer(small(), Adagrad(lr=0.2), optax.adam(5e-3),
                          mesh=mesh)
    eval_b = [shard_batch(mesh, J(gen.batch())) for _ in range(2)]
    for b in eval_b:
        l_f, _ = t_f32.eval_step(st, b)
        l_b, _ = t_bf.eval_step(st, b)
        assert float(l_f) == float(l_b)


# ------------------------------------------------------- checkpoint compat


def test_columnar_checkpoint_restores_into_packed_meta(tmp_path):
    """The on-disk format stays columnar (freqs/versions arrays): an
    old-format rows dict — exactly what pre-diet checkpoints hold —
    restores into the packed-meta state unchanged, and a full manager
    round-trip preserves the fused metadata bit-for-bit."""
    from deeprec_tpu.embedding.table import EmbeddingTable
    from deeprec_tpu.training.checkpoint import (
        CheckpointManager, _state_to_np, export_table_arrays, import_rows,
    )

    cfg = TableConfig(name="t", dim=8, capacity=1 << 10)
    t = EmbeddingTable(cfg)
    opt = Adagrad(lr=0.1)
    s = ensure_slots(t, t.create(), opt)
    s, res = t.lookup_unique(s, jnp.arange(40, dtype=jnp.int32) * 7, step=3)
    s = apply_gradients(t, s, opt, res, jnp.ones_like(res.embeddings),
                        step=3)

    rows = export_table_arrays(t, _state_to_np(s), only_dirty=False)
    # the export is the legacy columnar layout — old checkpoints look
    # exactly like this
    assert {"keys", "values", "freqs", "versions"} <= set(rows)
    s2 = import_rows(t, ensure_slots(t, t.create(), opt), rows)
    by_key = {int(k): i for i, k in enumerate(np.asarray(s.keys))
              if int(k) != np.iinfo(np.int32).min}
    k2 = np.asarray(s2.keys)
    f1, v1 = np.asarray(s.freq), np.asarray(s.version)
    f2, v2 = np.asarray(s2.freq), np.asarray(s2.version)
    for slot2, key in enumerate(k2):
        if int(key) == np.iinfo(np.int32).min:
            continue
        slot1 = by_key[int(key)]
        assert f1[slot1] == f2[slot2] and v1[slot1] == v2[slot2]

    # full-manager round trip on a trainer: meta survives save+restore
    tr = Trainer(small(), Adagrad(lr=0.1))
    st = tr.init(0)
    gen = SyntheticCriteo(batch_size=64, num_cat=4, num_dense=2, vocab=300,
                          seed=1)
    for _ in range(3):
        st, _ = tr.train_step(st, J(gen.batch()))
    ck = CheckpointManager(str(tmp_path), tr)
    st_saved, _ = ck.save(st)
    rest = ck.restore()
    for f in ("C1", "C2", "C3", "C4"):
        a, b = tr.table_state(st, f), tr.table_state(rest, f)
        ka, kb = np.asarray(a.keys), np.asarray(b.keys)
        fa, fb = np.asarray(a.freq), np.asarray(b.freq)
        va, vb = np.asarray(a.version), np.asarray(b.version)
        ma = {int(k): (fa[i], va[i]) for i, k in enumerate(ka)
              if int(k) != np.iinfo(np.int32).min}
        mb = {int(k): (fb[i], vb[i]) for i, k in enumerate(kb)
              if int(k) != np.iinfo(np.int32).min}
        assert ma == mb
    # dirty cleared by the save on the RETURNED state
    for bname in st_saved.tables:
        assert int(np.sum(np.asarray(st_saved.tables[bname].dirty))) == 0


# ------------------------------------------------------ tooling satellites


def test_traffic_op_model_matches_lowered_program():
    """In-suite drift gate (the CI smoke asserts the same through
    bench.py + roofline --assert-traffic): the traffic model's expected
    gather/scatter counts must equal what the hot path actually lowers
    to, on both arms and both dedup front-ends."""
    from deeprec_tpu.embedding.table import EmbeddingTable
    from deeprec_tpu.ops import dedup
    from deeprec_tpu.ops.traffic import (
        count_stablehlo_ops, expected_lookup_apply_ops,
    )

    t = EmbeddingTable(TableConfig(name="probe", dim=16, capacity=1 << 12))
    opt = Adagrad(lr=0.05)
    s = ensure_slots(t, t.create(), opt)
    ids = jnp.arange(256, dtype=jnp.int32)

    def prog(s, ids, diet, U):
        s, res = t._lookup_unique_impl(s, ids, jnp.int32(0), True, -1, U)
        g = jnp.ones_like(res.embeddings, jnp.float32)
        return apply_gradients(t, s, opt, res, g, step=0,
                               reuse_rows=diet, stamp_meta=not diet)

    for budgeted in (True, False):
        U = dedup.resolve_size(128, 256) if budgeted else None
        for diet in (True, False):
            txt = jax.jit(
                lambda s, ids, d=diet, u=U: prog(s, ids, d, u)
            ).lower(s, ids).as_text()
            got = count_stablehlo_ops(txt)
            want = expected_lookup_apply_ops(diet=diet, budgeted=budgeted,
                                             n_row_slots=1)
            assert got == want, (diet, budgeted, got, want)
    # the structural claim: the diet removes 4 scatters (3-scatter trio +
    # apply re-stamp pair -> 1 fused scatter) at an unchanged gather count
    d = expected_lookup_apply_ops(diet=True, budgeted=True)
    l = expected_lookup_apply_ops(diet=False, budgeted=True)
    assert l["scatter"] - d["scatter"] == 4 and l["gather"] == d["gather"]
