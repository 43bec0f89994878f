"""In-step pipelining (`pipeline_mode`): the K-step scan with a one-batch
lookahead must be EXACT — bit-identical table ints, values, dense params and
per-step losses vs the sequential `pipeline_mode="off"` scan — across
single-device, sharded-allgather and sharded-a2a, in both "lookahead" and
"chunked" modes, including the hazard case where batch t+1 touches rows
batch t's apply dirties (the reason the value gather/exchange runs AFTER
the apply instead of speculating)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeprec_tpu.data import SyntheticCriteo
from deeprec_tpu.models import WDL
from deeprec_tpu.optim import Adagrad
from deeprec_tpu.training import Trainer


def J(b):
    return {k: jnp.asarray(v) for k, v in b.items()}


@pytest.fixture(scope="module")
def mesh():
    from deeprec_tpu.parallel import make_mesh

    return make_mesh(8)


def model():
    return WDL(emb_dim=8, capacity=1 << 12, hidden=(16,), num_cat=4,
               num_dense=2)


def window_batches(K=4, batch_size=64, seed=7, fresh_ids=True):
    """K batches; fresh_ids=True gives later batches never-seen ids (the
    insert path mid-window), fresh_ids=False keeps every batch in one
    small vocab so consecutive batches HEAVILY overlap — batch t+1 reads
    rows batch t's apply just wrote (the staleness hazard)."""
    gen = SyntheticCriteo(batch_size=batch_size, num_cat=4, num_dense=2,
                          vocab=500 if fresh_ids else 40, seed=seed)
    batches = [J(gen.batch()) for _ in range(K)]
    if fresh_ids:
        for t in range(1, K):
            batches[t]["C1"] = batches[t]["C1"] + jnp.int32(10_000 * t)
    return batches


def _ordered(x):
    """float32 -> int64 that counts representable values in order."""
    i = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
    return np.where(i < 0, -(i & 0x7FFFFFFF), i)


def assert_states_bitwise(s_a, s_b, ulps=0):
    """Full exactness: table ints AND values bitwise, dense/opt bitwise.
    `ulps`: how many representable values a FLOAT leaf may differ by
    (0: bitwise); int leaves (keys, meta, counters, step) are always
    exact."""
    def same(x, y):
        x, y = np.asarray(x), np.asarray(y)
        if ulps and x.dtype == np.float32:
            assert np.abs(_ordered(x) - _ordered(y)).max(initial=0) <= ulps
        else:
            np.testing.assert_array_equal(x, y)

    assert int(s_a.step) == int(s_b.step)
    for bname in s_a.tables:
        a, b = s_a.tables[bname], s_b.tables[bname]
        for name in ("keys", "meta", "insert_fails", "dedup_unique",
                     "values"):
            same(getattr(a, name), getattr(b, name))
        for k in a.slots:
            same(a.slots[k], b.slots[k])
    for x, y in zip(jax.tree.leaves(s_a.dense), jax.tree.leaves(s_b.dense)):
        same(x, y)
    for x, y in zip(
        jax.tree.leaves(s_a.opt_state), jax.tree.leaves(s_b.opt_state)
    ):
        same(x, y)


# --------------------------------------------------------------- single dev


def test_lookahead_matches_off_single_device():
    K = 4
    batches = window_batches(K)
    t_off = Trainer(model(), Adagrad(lr=0.1), optax.adam(2e-3))
    t_la = Trainer(model(), Adagrad(lr=0.1), optax.adam(2e-3),
                   pipeline_mode="lookahead")
    s0, m0 = t_off.train_steps(t_off.init(0), batches)
    s1, m1 = t_la.train_steps(t_la.init(0), batches)
    assert m1["loss"].shape == (K,)
    np.testing.assert_array_equal(np.asarray(m0["loss"]), np.asarray(m1["loss"]))
    np.testing.assert_array_equal(
        np.asarray(m0["accuracy"]), np.asarray(m1["accuracy"])
    )
    assert int(s1.step) == K
    assert_states_bitwise(s0, s1)


def test_lookahead_k1_window():
    """K=1 degenerates to prologue + epilogue (the scan runs zero
    iterations) and still matches the sequential step exactly."""
    batches = window_batches(1)
    t_off = Trainer(model(), Adagrad(lr=0.1))
    t_la = Trainer(model(), Adagrad(lr=0.1), pipeline_mode="lookahead")
    s0, m0 = t_off.train_steps(t_off.init(0), batches)
    s1, m1 = t_la.train_steps(t_la.init(0), batches)
    assert m1["loss"].shape == (1,)
    np.testing.assert_array_equal(np.asarray(m0["loss"]), np.asarray(m1["loss"]))
    assert_states_bitwise(s0, s1)


def test_lookahead_hazard_overlapping_ids_single_device():
    """Tiny vocab: every batch rewrites rows the next batch reads — the
    finish-after-apply placement must make the lookahead see post-apply
    values (a speculative pre-apply gather would diverge here)."""
    batches = window_batches(4, fresh_ids=False)
    t_off = Trainer(model(), Adagrad(lr=0.3))
    t_la = Trainer(model(), Adagrad(lr=0.3), pipeline_mode="lookahead")
    s0, m0 = t_off.train_steps(t_off.init(0), batches)
    s1, m1 = t_la.train_steps(t_la.init(0), batches)
    np.testing.assert_array_equal(np.asarray(m0["loss"]), np.asarray(m1["loss"]))
    assert_states_bitwise(s0, s1)


def test_lookahead_with_unique_budget():
    """The split-phase route carries the budgeted dedup: budgeted
    pipelined scan == budgeted sequential scan exactly."""
    batches = window_batches(3)
    t_off = Trainer(model(), Adagrad(lr=0.1), unique_budget=64)
    t_la = Trainer(model(), Adagrad(lr=0.1), unique_budget=64,
                   pipeline_mode="lookahead")
    s0, m0 = t_off.train_steps(t_off.init(0), batches)
    s1, m1 = t_la.train_steps(t_la.init(0), batches)
    np.testing.assert_array_equal(np.asarray(m0["loss"]), np.asarray(m1["loss"]))
    assert_states_bitwise(s0, s1)


def test_pipeline_mode_validated():
    with pytest.raises(ValueError, match="pipeline_mode"):
        Trainer(model(), Adagrad(lr=0.1), pipeline_mode="sideways")


# ------------------------------------------------------------------ sharded


@pytest.mark.parametrize("comm", ["allgather", "a2a"])
@pytest.mark.parametrize("mode", ["lookahead", "chunked"])
def test_sharded_pipelined_matches_off(mesh, comm, mode):
    from deeprec_tpu.parallel import ShardedTrainer, shard_batch

    K = 3
    batches = [
        shard_batch(mesh, b)
        for b in window_batches(K, batch_size=64, seed=9)
    ]
    t_off = ShardedTrainer(model(), Adagrad(lr=0.1), optax.adam(2e-3),
                           mesh=mesh, comm=comm)
    t_p = ShardedTrainer(model(), Adagrad(lr=0.1), optax.adam(2e-3),
                         mesh=mesh, comm=comm, pipeline_mode=mode,
                         pipeline_chunks=3)
    s0, m0 = t_off.train_steps(t_off.init(0), batches)
    s1, m1 = t_p.train_steps(t_p.init(0), batches)
    assert m1["loss"].shape == (K,)
    np.testing.assert_array_equal(np.asarray(m0["loss"]), np.asarray(m1["loss"]))
    assert_states_bitwise(s0, s1)


def test_sharded_hazard_overlapping_ids(mesh):
    """Sharded hazard case: consecutive batches share most ids, so the
    owner value gather of batch t+1 reads rows batch t's grad exchange +
    apply just updated."""
    from deeprec_tpu.parallel import ShardedTrainer, shard_batch

    batches = [
        shard_batch(mesh, b)
        for b in window_batches(4, batch_size=64, seed=3, fresh_ids=False)
    ]
    t_off = ShardedTrainer(model(), Adagrad(lr=0.3), mesh=mesh)
    t_la = ShardedTrainer(model(), Adagrad(lr=0.3), mesh=mesh,
                          pipeline_mode="lookahead")
    s0, m0 = t_off.train_steps(t_off.init(0), batches)
    s1, m1 = t_la.train_steps(t_la.init(0), batches)
    np.testing.assert_array_equal(np.asarray(m0["loss"]), np.asarray(m1["loss"]))
    assert_states_bitwise(s0, s1)


def test_chunked_single_step_exchange(mesh):
    """pipeline_mode="chunked" splits the value/grad exchanges on EVERY
    path — the single-step program too — bitwise identical to whole
    exchanges."""
    from deeprec_tpu.parallel import ShardedTrainer, shard_batch

    batches = [
        shard_batch(mesh, b) for b in window_batches(3, batch_size=64, seed=5)
    ]
    t_off = ShardedTrainer(model(), Adagrad(lr=0.1), mesh=mesh, comm="a2a")
    t_ch = ShardedTrainer(model(), Adagrad(lr=0.1), mesh=mesh, comm="a2a",
                          pipeline_mode="chunked", pipeline_chunks=4)
    assert all(s.exchange_chunks == 4 for s in t_ch.sharded.values())
    s0, s1 = t_off.init(0), t_ch.init(0)
    for b in batches:
        s0, m0 = t_off.train_step(s0, b)
        s1, m1 = t_ch.train_step(s1, b)
        np.testing.assert_array_equal(
            np.asarray(m0["loss"]), np.asarray(m1["loss"])
        )
    assert_states_bitwise(s0, s1)


# --------------------------------------------------- shared-table sequential


def _shared_model():
    from deeprec_tpu.config import TableConfig
    from deeprec_tpu.features import DenseFeature, SparseFeature

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


def _shared_batches(K=3, n=32):
    rng = np.random.default_rng(0)
    out = []
    for _ in range(K):
        ids = rng.integers(0, 20, size=(n,)).astype(np.int32)
        out.append(J({
            "item": ids,
            "item2": ids[::-1].copy(),  # heavy overlap, different layout
            "d": rng.normal(size=(n, 1)).astype(np.float32),
            "label": (rng.random(n) < 0.5).astype(np.float32),
        }))
    return out


def test_shared_table_pipelined_single_device():
    """Two features on ONE shared table (sequential lookups + sequential
    re-gathering applies) under the pipelined scan: the resolve of both
    features chains inserts exactly as the sequential path, both finishes
    read post-apply values, and the second apply still sees the first's
    writes.

    Floats to 2 ulps, everything else exact. Found (PR 31, XLA:CPU): keys,
    meta, counters, the Adagrad accumulators and the three losses agree
    bit for bit; 2 of 8,192 value elements differ by ONE ulp, first at
    K = 3 (the first window whose scan body runs twice); K sequential
    steps and the unpipelined scan agree exactly, and with
    `--xla_backend_optimization_level=0` the difference is gone. The two
    programs order their arithmetic alike; the compiler's optimised code
    rounds one operation of the row update differently in the differently
    shaped loop, and bitwise floats between two differently compiled
    programs is not a contract it offers."""
    batches = _shared_batches()
    t_off = Trainer(_shared_model(), Adagrad(lr=0.2))
    t_la = Trainer(_shared_model(), Adagrad(lr=0.2), pipeline_mode="lookahead")
    b = next(iter(t_la.bundles.values()))
    assert not t_la._bundle_reuse_rows(b)
    s0, m0 = t_off.train_steps(t_off.init(0), batches)
    s1, m1 = t_la.train_steps(t_la.init(0), batches)
    np.testing.assert_array_equal(np.asarray(m0["loss"]), np.asarray(m1["loss"]))
    assert_states_bitwise(s0, s1, ulps=2)


def test_shared_table_pipelined_sharded(mesh):
    """The same on the mesh, which runs the same pipelined step. Found the
    same (PR 31): ints and losses exact, 2 of 8,192 value elements one ulp
    apart at K = 3 (at K = 2, 4 of 16 elements of Adam's second moment
    instead), nothing at `--xla_backend_optimization_level=0`; the
    unpipelined scan and K sequential steps agree exactly."""
    from deeprec_tpu.parallel import ShardedTrainer, shard_batch

    batches = [shard_batch(mesh, b) for b in _shared_batches(K=3, n=64)]
    t_off = ShardedTrainer(_shared_model(), Adagrad(lr=0.2), mesh=mesh)
    t_la = ShardedTrainer(_shared_model(), Adagrad(lr=0.2), mesh=mesh,
                          pipeline_mode="lookahead")
    s0, m0 = t_off.train_steps(t_off.init(0), batches)
    s1, m1 = t_la.train_steps(t_la.init(0), batches)
    np.testing.assert_array_equal(np.asarray(m0["loss"]), np.asarray(m1["loss"]))
    assert_states_bitwise(s0, s1, ulps=2)


# ------------------------------------------------------- async via split-phase


def test_async_state_is_pipeline_carry():
    """The stale-by-one carry is the generic PipelineCarry (the redundant
    private struct is gone), and its carried lookup results drop the
    owner-side residual (keep_rows=False through the split-phase finish)."""
    from deeprec_tpu.parallel import AsyncState
    from deeprec_tpu.training.trainer import PipelineCarry

    assert AsyncState is PipelineCarry


def test_async_bootstrap_strips_residual(mesh):
    from deeprec_tpu.parallel import AsyncShardedTrainer, shard_batch

    batches = [shard_batch(mesh, b) for b in window_batches(2)]
    asy = AsyncShardedTrainer(model(), Adagrad(lr=0.1), mesh=mesh)
    ast = asy.bootstrap(asy.init(0), batches[0])
    for r in jax.tree.leaves(
        ast.bundle_res, is_leaf=lambda x: hasattr(x, "owner_res")
    ):
        assert r.owner_res.rows.size == 0  # residual not carried
        assert r.embeddings.size > 0  # but the lookup IS finished (stale)
    ast, mets = asy.train_steps_async(ast, batches)
    assert np.isfinite(np.asarray(mets["loss"])).all()


# ------------------------------------------------------------- model pieces


def test_overlap_model_and_buffer_accounting():
    from deeprec_tpu.ops import traffic as T

    off = T.modeled_overlap_step(dense_ms=4.0, route_ms=3.0, other_ms=2.0,
                                 mode="off")
    la = T.modeled_overlap_step(dense_ms=4.0, route_ms=3.0, other_ms=2.0,
                                mode="lookahead")
    assert off == 9.0 and la == 6.0  # route hidden behind dense
    # route longer than dense: only dense's worth hides
    assert T.modeled_overlap_step(dense_ms=2.0, route_ms=5.0, other_ms=1.0,
                                  mode="lookahead") == 6.0
    assert T.pipeline_buffer_bytes(unique=10, dim=4,
                                   pipeline_mode="off") == 0.0
    b = T.pipeline_buffer_bytes(unique=10, dim=4, pipeline_mode="lookahead")
    assert b > 0
    ref = T.dlrm_reference_traffic(pipeline_mode="lookahead")
    assert ref["pipeline_buffer_bytes"] > 0
    assert T.dlrm_reference_traffic(pipeline_mode="off")[
        "pipeline_buffer_bytes"] == 0.0
