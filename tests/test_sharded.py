"""Sharded-table tests on the virtual 8-device CPU mesh — the distributed
coverage tier (SURVEY.md §4: in-process fake clusters)."""
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


def to_jnp(batch):
    return {k: jnp.asarray(v) for k, v in batch.items()}


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(8)


def small_model():
    return WDL(emb_dim=8, capacity=1 << 13, hidden=(32,), num_cat=4, num_dense=2)


def test_sharded_matches_single_device(mesh):
    """The collective path must produce the same math as the local path:
    same loss trajectory and same embeddings for the same ids."""
    gen = SyntheticCriteo(batch_size=256, num_cat=4, num_dense=2, vocab=3000, seed=3)
    batches = [to_jnp(gen.batch()) for _ in range(5)]

    t_local = Trainer(small_model(), GradientDescent(lr=0.1), optax.sgd(0.01))
    s_local = t_local.init(0)
    t_shard = ShardedTrainer(
        small_model(), GradientDescent(lr=0.1), optax.sgd(0.01), mesh=mesh
    )
    s_shard = t_shard.init(0)

    for b in batches:
        s_local, m_local = t_local.train_step(s_local, b)
        s_shard, m_shard = t_shard.train_step(s_shard, shard_batch(mesh, b))
        # bf16 matmuls + different reduction orders (psum_scatter partial
        # sums) make this approximate; a formula bug diverges by orders of
        # magnitude, not fractions of a percent.
        np.testing.assert_allclose(
            float(m_local["loss"]), float(m_shard["loss"]), rtol=2e-2
        )

    # spot-check an id's embedding across the two worlds
    ids = batches[0]["C1"][:8]
    e_local = t_local.tables["C1"].lookup_readonly(
        t_local.table_state(s_local, "C1"), ids
    )
    # sharded: find each id on its owner shard
    from deeprec_tpu.utils.hashing import hash_shard

    owners = np.asarray(hash_shard(ids, 8))
    sharded_ts = t_shard.table_state(s_shard, "C1")  # [N, C_local, ...]
    got = []
    for i, oid in enumerate(np.asarray(ids)):
        shard_state = jax.tree.map(lambda a: a[owners[i]], sharded_ts)
        got.append(
            np.asarray(
                t_shard.tables["C1"].lookup_readonly(
                    shard_state, jnp.asarray([oid])
                )
            )[0]
        )
    np.testing.assert_allclose(np.asarray(e_local), np.asarray(got), atol=2e-2)


def test_sharded_learns(mesh):
    model = small_model()
    tr = ShardedTrainer(model, Adagrad(lr=0.2), optax.adam(5e-3), mesh=mesh)
    st = tr.init(0)
    gen = SyntheticCriteo(batch_size=512, num_cat=4, num_dense=2, vocab=2000, seed=5)
    losses = []
    for _ in range(60):
        st, m = tr.train_step(st, shard_batch(mesh, to_jnp(gen.batch())))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10])
    # tables sharded: every shard holds some keys, none holds all
    ts = tr.table_state(st, "C1")  # [N, C_local, ...]
    sizes = np.asarray(
        [int(tr.tables["C1"].size(jax.tree.map(lambda a: a[i], ts))) for i in range(8)]
    )
    assert (sizes > 0).all() and sizes.sum() <= 2000 * 1.01


def test_sharded_eval(mesh):
    model = small_model()
    tr = ShardedTrainer(model, Adagrad(lr=0.2), optax.adam(5e-3), mesh=mesh)
    st = tr.init(0)
    gen = SyntheticCriteo(batch_size=256, num_cat=4, num_dense=2, vocab=2000, seed=5)
    for _ in range(20):
        st, _ = tr.train_step(st, shard_batch(mesh, to_jnp(gen.batch())))
    mets = tr.evaluate(st, [shard_batch(mesh, to_jnp(gen.batch())) for _ in range(4)])
    assert 0.4 < mets["auc"] <= 1.0


# ------------------------------------------------- the step bodies' one seam
#
# ShardedTrainer runs the base Trainer's step bodies on its mesh, so what the
# base does with a model holds there too: a model's own loss, and remat.


class SquaredError:
    """A linear read of two pooled fields and a dense one, scored by its OWN
    loss: squared error on the label, and one metric key more than the
    trainer's loss and accuracy."""

    def __init__(self, own_loss=True):
        from deeprec_tpu.config import TableConfig
        from deeprec_tpu.features import DenseFeature, SparseFeature

        self.features = [
            SparseFeature(n, table=TableConfig(name=n, dim=8, capacity=1 << 12))
            for n in ("C1", "C2")
        ] + [DenseFeature("I1", 1)]
        if own_loss:
            self.loss = self._loss

    def init(self, key):
        return {"w": jax.random.normal(key, (17,)) * 0.3}

    def apply(self, params, inputs, train):
        x = jnp.concatenate([inputs.pooled["C1"], inputs.pooled["C2"],
                             inputs.dense["I1"]], -1)
        return x @ params["w"]

    def _loss(self, params, inputs, batch):
        err = self.apply(params, inputs, True) - batch["label"]
        return jnp.mean(err ** 2), {"mae": jnp.mean(jnp.abs(err))}


def _dispatch(tr, path, state, window, place):
    """One dispatch of `window` (two batches) through the named train path;
    (state, mean loss, mean mae or None)."""
    if path == "train_step":
        for b in window:
            state, m = tr.train_step(state, place(b))
    elif path == "train_step_accum":
        whole = {k: jnp.concatenate([b[k] for b in window]) for k in window[0]}
        state, m = tr.train_step_accum(state, place(whole), accum_steps=2)
    else:
        state, m = tr.train_steps(state, [place(b) for b in window])
    mae = float(np.mean(m["mae"])) if "mae" in m else None
    return state, float(np.mean(m["loss"])), mae


@pytest.mark.parametrize("path, mode", [
    ("train_step", "off"), ("train_steps", "off"),
    ("train_steps", "lookahead"), ("train_step_accum", "off")])
def test_sharded_model_owned_loss_matches_single_device(mesh, path, mode):
    """A model with a `loss` of its own is scored by it on a mesh as on one
    device, on every train path, and its extra metric key comes back. The
    binary cross-entropy of the same logits, which the mesh once scored
    every model by, is a different number."""
    gen = SyntheticCriteo(batch_size=256, num_cat=2, num_dense=1, vocab=500,
                          seed=11)
    windows = [[to_jnp(gen.batch()) for _ in range(2)] for _ in range(2)]
    for b in (b for w in windows for b in w):
        b["label"] = 3.0 * b["I1"][:, 0] + 1.0  # a dense target, not a class
    opts = (GradientDescent(lr=0.1), optax.sgd(0.01))
    t_local = Trainer(SquaredError(), *opts)
    t_bce = Trainer(SquaredError(own_loss=False), *opts)
    t_shard = ShardedTrainer(SquaredError(), *opts, mesh=mesh,
                             pipeline_mode=mode)
    s_local, s_bce, s_shard = t_local.init(0), t_bce.init(0), t_shard.init(0)
    for w in windows:
        s_local, want, want_mae = _dispatch(t_local, path, s_local, w, to_jnp)
        s_bce, bce, _ = _dispatch(t_bce, path, s_bce, w, to_jnp)
        s_shard, got, got_mae = _dispatch(
            t_shard, path, s_shard, w, lambda b: shard_batch(mesh, b))
        np.testing.assert_allclose(got, want, rtol=2e-2)
        np.testing.assert_allclose(got_mae, want_mae, rtol=2e-2)
        assert abs(got - bce) > 0.1 * bce, (got, bce)
    assert int(s_shard.step) == int(s_local.step)


def test_sharded_remat_recomputes_the_forward(mesh):
    """`remat=True` on a mesh wraps the model's forward in jax.checkpoint as
    it does on one device: the same losses, and a checkpoint (`remat`)
    equation in the step's jaxpr."""
    gen = SyntheticCriteo(batch_size=256, num_cat=4, num_dense=2, vocab=3000,
                          seed=3)
    batches = [shard_batch(mesh, to_jnp(gen.batch())) for _ in range(3)]
    losses, jaxprs = {}, {}
    for remat in (False, True):
        tr = ShardedTrainer(small_model(), GradientDescent(lr=0.1),
                            optax.sgd(0.01), mesh=mesh, remat=remat)
        st = tr.init(0)
        jaxprs[remat] = str(jax.make_jaxpr(tr._train_step)(
            st, batches[0], jnp.float32(0.1)))
        losses[remat] = []
        for b in batches:
            st, m = tr.train_step(st, b)
            losses[remat].append(float(m["loss"]))
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
    from jax._src.ad_checkpoint import remat_p

    assert f"{remat_p.name}[" in jaxprs[True]
    assert f"{remat_p.name}[" not in jaxprs[False]
