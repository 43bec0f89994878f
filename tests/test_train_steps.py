"""Multi-step device loop (`train_steps`): K steps per dispatch via
`lax.scan` must be SEMANTICALLY IDENTICAL to K sequential `train_step`
calls — dense params and optimizer state allclose, hash-table state
(keys, freq, version) exact — including windows where new ids are
inserted mid-window, for Trainer, ShardedTrainer and the async stage."""
import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeprec_tpu.data import SyntheticCriteo
from deeprec_tpu.models import WDL
from deeprec_tpu.optim import Adagrad
from deeprec_tpu.training import Trainer, stack_batches


def J(b):
    return {k: jnp.asarray(v) for k, v in b.items()}


def model():
    return WDL(emb_dim=8, capacity=1 << 12, hidden=(16,), num_cat=4,
               num_dense=2)


def window_batches(K=4, batch_size=64, seed=7):
    """K batches where later batches introduce ids no earlier batch held,
    so the scan body's insert path is exercised mid-window."""
    gen = SyntheticCriteo(batch_size=batch_size, num_cat=4, num_dense=2,
                          vocab=500, seed=seed)
    batches = [J(gen.batch()) for _ in range(K)]
    for t in range(1, K):
        # fresh id range per step: vocab*t offset guarantees first-seen ids
        batches[t]["C1"] = batches[t]["C1"] + jnp.int32(10_000 * t)
    return batches


def assert_tables_equal(tr, s_scan, s_seq):
    for bname in s_scan.tables:
        a, b = s_scan.tables[bname], s_seq.tables[bname]
        np.testing.assert_array_equal(np.asarray(a.keys), np.asarray(b.keys))
        np.testing.assert_array_equal(np.asarray(a.freq), np.asarray(b.freq))
        np.testing.assert_array_equal(
            np.asarray(a.version), np.asarray(b.version)
        )
        np.testing.assert_allclose(
            np.asarray(a.values), np.asarray(b.values), atol=1e-5
        )


def assert_dense_equal(s_scan, s_seq, atol=1e-5):
    for a, b in zip(jax.tree.leaves(s_scan.dense), jax.tree.leaves(s_seq.dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)
    for a, b in zip(
        jax.tree.leaves(s_scan.opt_state), jax.tree.leaves(s_seq.opt_state)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


def test_train_steps_matches_sequential():
    K = 4
    batches = window_batches(K)
    tr = Trainer(model(), Adagrad(lr=0.1), optax.adam(2e-3))

    s_seq = tr.init(0)
    seq_losses = []
    for b in batches:
        s_seq, m = tr.train_step(s_seq, b)
        seq_losses.append(float(m["loss"]))

    s_scan, mets = tr.train_steps(tr.init(0), batches)
    # per-step metric stacks: one entry per inner step, same values
    assert mets["loss"].shape == (K,)
    np.testing.assert_allclose(np.asarray(mets["loss"]), seq_losses, atol=1e-5)
    assert int(s_scan.step) == K == int(s_seq.step)
    assert_tables_equal(tr, s_scan, s_seq)
    assert_dense_equal(s_scan, s_seq)


def test_train_steps_takes_stacked_pytree():
    batches = window_batches(3)
    tr = Trainer(model(), Adagrad(lr=0.1))
    stacked = stack_batches(batches)
    s1, m1 = tr.train_steps(tr.init(0), stacked)
    s2, m2 = tr.train_steps(tr.init(0), batches)
    np.testing.assert_array_equal(np.asarray(m1["loss"]), np.asarray(m2["loss"]))
    assert int(s1.step) == 3


def test_train_steps_inserts_new_ids_mid_window():
    """Ids first seen at inner step t>0 must land in the table with
    freq/version bookkeeping identical to the sequential path."""
    batches = window_batches(4)
    tr = Trainer(model(), Adagrad(lr=0.1))
    s_scan, _ = tr.train_steps(tr.init(0), batches)
    # the offset ids from the last batch are present in the final state
    ts = tr.table_state(s_scan, "C1")
    keys = np.asarray(ts.keys)
    last_ids = np.asarray(batches[3]["C1"]).ravel()
    assert np.isin(last_ids, keys).all()
    # and their version stamp is the step they arrived at (3), not 0
    occupied = {int(k): int(v) for k, v in zip(keys, np.asarray(ts.version))}
    assert all(occupied[int(i)] == 3 for i in last_ids)


def test_sharded_train_steps_matches_sequential():
    from deeprec_tpu.parallel import ShardedTrainer, make_mesh, shard_batch

    K = 3
    mesh = make_mesh(8)
    tr = ShardedTrainer(model(), Adagrad(lr=0.1), optax.adam(2e-3), mesh=mesh)
    batches = [
        shard_batch(mesh, b) for b in window_batches(K, batch_size=64, seed=9)
    ]

    s_seq = tr.init(0)
    seq_losses = []
    for b in batches:
        s_seq, m = tr.train_step(s_seq, b)
        seq_losses.append(float(m["loss"]))

    s_scan, mets = tr.train_steps(tr.init(0), batches)
    assert mets["loss"].shape == (K,)
    np.testing.assert_allclose(np.asarray(mets["loss"]), seq_losses, atol=1e-5)
    assert int(s_scan.step) == K
    assert_tables_equal(tr, s_scan, s_seq)
    assert_dense_equal(s_scan, s_seq)


def test_sharded_train_steps_a2a_comm():
    """The scan body is the single step's (`Trainer._step_impl` on the
    mesh), exchange included — also on the budgeted all2all path."""
    from deeprec_tpu.parallel import ShardedTrainer, make_mesh, shard_batch

    mesh = make_mesh(8)
    tr = ShardedTrainer(model(), Adagrad(lr=0.1), mesh=mesh, comm="a2a")
    batches = [
        shard_batch(mesh, b) for b in window_batches(3, batch_size=64, seed=2)
    ]
    s_seq = tr.init(0)
    for b in batches:
        s_seq, _ = tr.train_step(s_seq, b)
    s_scan, mets = tr.train_steps(tr.init(0), batches)
    assert mets["loss"].shape == (3,)
    assert_tables_equal(tr, s_scan, s_seq)


def test_async_train_steps_matches_sequential():
    """K inner async steps per dispatch keep the stale-by-one pipeline
    semantics of K sequential train_step_async calls."""
    from deeprec_tpu.parallel import AsyncShardedTrainer, make_mesh, shard_batch

    K = 3
    mesh = make_mesh(8)
    tr = AsyncShardedTrainer(model(), Adagrad(lr=0.1), optax.adam(2e-3),
                             mesh=mesh)
    batches = [
        shard_batch(mesh, b) for b in window_batches(K + 1, seed=11)
    ]

    a_seq = tr.bootstrap(tr.init(0), batches[0])
    seq_losses = []
    for b in batches[1:]:
        a_seq, m = tr.train_step_async(a_seq, b)
        seq_losses.append(float(m["loss"]))

    a_scan = tr.bootstrap(tr.init(0), batches[0])
    a_scan, mets = tr.train_steps_async(a_scan, batches[1:])
    assert mets["loss"].shape == (K,)
    np.testing.assert_allclose(np.asarray(mets["loss"]), seq_losses, atol=1e-5)
    assert int(a_scan.inner.step) == K == int(a_seq.inner.step)
    assert_tables_equal(tr, a_scan.inner, a_seq.inner)
    assert_dense_equal(a_scan.inner, a_seq.inner)


def test_train_steps_then_maintain_boundary():
    """Host-side table maintenance composes at K-step boundaries: a grown
    table recompiles the K-path once and training continues."""
    batches = window_batches(4, batch_size=64, seed=13)
    tr = Trainer(model(), Adagrad(lr=0.1))
    st, _ = tr.train_steps(tr.init(0), batches[:2])
    st, report = tr.maintain(st)
    st, mets = tr.train_steps(st, batches[2:])
    assert int(st.step) == 4
    assert np.isfinite(np.asarray(mets["loss"])).all()
