"""All2all (SOK-style) exchange path must match the exact allgather path."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeprec_tpu.data import SyntheticCriteo
from deeprec_tpu.models import WDL
from deeprec_tpu.optim import Adagrad, GradientDescent
from deeprec_tpu.parallel import ShardedTrainer, make_mesh, shard_batch
from deeprec_tpu.training import Trainer


def J(b):
    return {k: jnp.asarray(v) for k, v in b.items()}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def small():
    return WDL(emb_dim=8, capacity=1 << 13, hidden=(32,), num_cat=4, num_dense=2)


def test_a2a_matches_allgather_and_local(mesh):
    gen = SyntheticCriteo(batch_size=256, num_cat=4, num_dense=2, vocab=3000, seed=11)
    batches = [J(gen.batch()) for _ in range(4)]

    t_local = Trainer(small(), GradientDescent(lr=0.1), optax.sgd(0.01))
    s_local = t_local.init(0)
    t_ag = ShardedTrainer(small(), GradientDescent(lr=0.1), optax.sgd(0.01),
                          mesh=mesh, comm="allgather")
    s_ag = t_ag.init(0)
    t_a2a = ShardedTrainer(small(), GradientDescent(lr=0.1), optax.sgd(0.01),
                           mesh=mesh, comm="a2a")
    s_a2a = t_a2a.init(0)

    for b in batches:
        s_local, ml = t_local.train_step(s_local, b)
        sb = shard_batch(mesh, b)
        s_ag, mag = t_ag.train_step(s_ag, sb)
        s_a2a, ma2a = t_a2a.train_step(s_a2a, sb)
        # a2a vs allgather: identical routing math, tiny fp-order differences
        np.testing.assert_allclose(
            float(mag["loss"]), float(ma2a["loss"]), rtol=1e-4
        )
        np.testing.assert_allclose(
            float(ml["loss"]), float(ma2a["loss"]), rtol=2e-2
        )


def test_a2a_learns_with_skewed_ids(mesh):
    """Zipf-skewed ids stress the per-destination budget; training must stay
    healthy and overflow must be (near) zero at slack=2."""
    model = small()
    tr = ShardedTrainer(model, Adagrad(lr=0.2), optax.adam(5e-3), mesh=mesh,
                        comm="a2a")
    st = tr.init(0)
    gen = SyntheticCriteo(batch_size=512, num_cat=4, num_dense=2, vocab=2000,
                          zipf_a=1.6, seed=13)
    losses = []
    for _ in range(30):
        st, m = tr.train_step(st, shard_batch(mesh, J(gen.batch())))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    # overflow counter (separate from insert_fails): sum across shards/groups
    total_overflow = 0
    for bname, ts in st.tables.items():
        total_overflow += int(np.asarray(ts.a2a_overflow).sum())
    assert total_overflow == 0, total_overflow


def test_a2a_overflow_under_zipf_skew_converges(mesh):
    """Round-2 review, weak #8: when per-destination budgets actually BIND
    (zipf-skewed ids + a tight a2a_slack), overflow must be (a) visible in
    the counter, (b) bounded in training impact — loss still trends down
    on a LEARNABLE stream and tracks the exact allgather path within a
    modest gap — and (c) strictly a budget artifact: default slack drives
    overflow to zero on the same stream."""
    gen = SyntheticCriteo(batch_size=2048, num_cat=4, num_dense=2,
                          vocab=3000, zipf_a=1.3, seed=7)
    batches = [J(gen.batch()) for _ in range(12)]

    def total_overflow(st):
        return sum(int(np.asarray(ts.a2a_overflow).sum())
                   for ts in st.tables.values())

    t_ag = ShardedTrainer(small(), Adagrad(lr=0.1), optax.adam(1e-3),
                          mesh=mesh, comm="allgather")
    s_ag = t_ag.init(0)
    t_tight = ShardedTrainer(small(), Adagrad(lr=0.1), optax.adam(1e-3),
                             mesh=mesh, comm="a2a", a2a_slack=0.1)
    s_tight = t_tight.init(0)

    ag_losses, tight_losses = [], []
    for b in batches:
        sb = shard_batch(mesh, b)
        s_ag, m = t_ag.train_step(s_ag, sb)
        ag_losses.append(float(m["loss"]))
        s_tight, m2 = t_tight.train_step(s_tight, sb)
        tight_losses.append(float(m2["loss"]))

    assert total_overflow(s_tight) > 0, \
        "slack=0.1 under zipf skew must bind the budget"

    # (b) training under overflow still learns the LEARNABLE signal, and
    # tracks allgather: mean loss over the last 4 steps within 10% of the
    # exact path (overflowed ids serve defaults + drop grads, but zipf
    # mass concentrates on ids that DO fit their budget)
    assert np.mean(tight_losses[-4:]) < np.mean(tight_losses[:2])
    tail_gap = abs(np.mean(tight_losses[-4:]) - np.mean(ag_losses[-4:]))
    assert tail_gap < 0.1 * np.mean(ag_losses[-4:]), (
        tight_losses, ag_losses)

    # (c) default slack on the same stream: no overflow at all
    t_ok = ShardedTrainer(small(), Adagrad(lr=0.1), optax.adam(1e-3),
                          mesh=mesh, comm="a2a")  # slack=2.0
    s_ok = t_ok.init(0)
    for b in batches[:4]:
        s_ok, _ = t_ok.train_step(s_ok, shard_batch(mesh, b))
    assert total_overflow(s_ok) == 0
