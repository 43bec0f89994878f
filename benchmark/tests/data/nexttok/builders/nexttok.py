"""Builder for the `nexttok` family, which exists as test data only: one
token field through a hashed table, a causal mean over the sequence under a
scope of its own, and two dense layers that score every token of the
vocabulary at every position (the trainer's sigmoid cross-entropy against
the one-hot next token). It shares `Trainer`, its engine and its optimizers
with the benchmark's other family, and nothing else.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

MIX_SCOPE = "nexttok_mix"


def _glorot(key, shape):
    lim = jnp.sqrt(6.0 / (shape[0] + shape[1]))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


@dataclasses.dataclass
class NextTokModel:
    emb_dim: int
    capacity: int
    hidden: int
    vocab: int
    seq_len: int

    def __post_init__(self):
        from deeprec_tpu.config import TableConfig
        from deeprec_tpu.features import SparseFeature

        self.features = [SparseFeature(
            name="tok", pooling="none", max_len=self.seq_len,
            table=TableConfig(name="tok", dim=self.emb_dim,
                              capacity=self.capacity))]

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"hid": {"w": _glorot(k1, (self.emb_dim, self.hidden)),
                        "b": jnp.zeros((self.hidden,))},
                "out": {"w": _glorot(k2, (self.hidden, self.vocab)),
                        "b": jnp.zeros((self.vocab,))}}

    def apply(self, params, inputs, train: bool):
        emb, _ = inputs.seq["tok"]                     # [B, S, D]
        with jax.named_scope(MIX_SCOPE):
            steps = jnp.arange(1, emb.shape[1] + 1, dtype=jnp.float32)
            mixed = jnp.cumsum(emb, axis=1) / steps[None, :, None]
        hp = jax.lax.Precision.HIGHEST
        h = jax.nn.relu(jnp.dot(mixed, params["hid"]["w"], precision=hp)
                        + params["hid"]["b"])
        return jnp.dot(h, params["out"]["w"], precision=hp) \
            + params["out"]["b"]                       # [B, S, V]


class Program:
    COUNTERS = ("insert_fails", "dedup_overflow", "dedup_unique", "dedup_ids")
    FAIL_COUNTERS = ("insert_fails", "dedup_overflow")

    def __init__(self, config: Dict, mix: Dict):
        import optax

        from deeprec_tpu.optim import Adagrad
        from deeprec_tpu.training import Trainer

        self.config, self.mix = config, mix
        self.model = NextTokModel(config["emb_dim"], config["capacity"],
                                  config["hidden"], mix["vocab"],
                                  mix["seq_len"])
        so, do = config["sparse_optimizer"], config["dense_optimizer"]
        self.trainer = Trainer(
            self.model,
            Adagrad(lr=so["lr"],
                    initial_accumulator_value=so["initial_accumulator_value"]),
            optax.adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
            unique_budget=int(mix["unique_budget"]))
        self.fields = ["tok"]
        self._init = jax.jit(self.trainer.init)
        self._counters = jax.jit(lambda tables: jnp.stack([
            sum(jnp.sum(getattr(ts, name)) for ts in tables.values())
            for name in self.COUNTERS]))
        self._rows = jax.jit(self._rows_impl)
        self._occupied = jax.jit(lambda tables: sum(
            jnp.sum(b.table.occupied(tables[name]))
            for name, b in self.trainer.bundles.items()))

    def fresh_state(self, seed: int):
        return self._init(np.int32(seed))

    def put(self, host_batch):
        return self.trainer.stage_batch(host_batch)

    def step(self, state, batch):
        state, mets = self.trainer.train_step(state, batch)
        return state, mets["loss"]

    def counters(self, state):
        return self._counters(state.tables)

    def occupied_rows(self, state) -> int:
        return int(self._occupied(state.tables))

    def capacity_rows(self) -> int:
        return self.config["capacity"]

    def _rows_impl(self, state, batch):
        views, _ = self.trainer.forward_views(state, batch)
        rows, inverse = views["tok"][0], views["tok"][1]
        return jnp.take(rows, inverse.reshape(-1),
                        axis=0).astype(jnp.float32)[None]

    def read_rows(self, state, batch):
        """[1, B x S, D]: the row the state holds for each position."""
        return self._rows(state, batch)

    def dense_leaves(self, tree) -> Dict[str, jnp.ndarray]:
        return {f"{layer}.{k}": tree[layer][k]
                for layer in ("hid", "out") for k in ("w", "b")}

    def dense_params(self, state):
        return self.dense_leaves(state.dense)

    def dense_first_moment(self, state):
        return self.dense_leaves(state.opt_state[0].mu)
