"""Builder for the DLRM family: the program side of a cell.

The only module of the benchmark that imports `deeprec_tpu`. It builds the
model and the `Trainer` a configuration and a traffic mix name, and exposes
what the harness needs of them: a fresh state from a seed, the staging of a
host batch, the train step, the engine's counters, and a read of what the
program's state holds (rows of given ids through `Trainer.forward_views`, the
serving path's own lookup; dense leaves; Adam's first moment) for the
comparison with the plain reference.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


class Program:
    def __init__(self, config: Dict, mix: Dict):
        import optax

        from deeprec_tpu import models
        from deeprec_tpu.optim import Adagrad
        from deeprec_tpu.training import Trainer

        if config["sparse_optimizer"]["name"] != "adagrad":
            raise ValueError("the dlrm builder knows Adagrad rows only")
        if config["dense_optimizer"]["name"] != "adam":
            raise ValueError("the dlrm builder knows a dense Adam only")
        if (mix["num_cat"], mix["num_dense"]) != (config["num_cat"],
                                                  config["num_dense"]):
            raise ValueError("traffic mix and configuration disagree on "
                             "the number of fields")
        kw = dict(emb_dim=config["emb_dim"], capacity=config["capacity"],
                  bottom=tuple(config["bottom_mlp"]),
                  top=tuple(config["top_mlp"]), num_cat=config["num_cat"],
                  num_dense=config["num_dense"])
        if config["model"] == "DLRMDCN":
            kw["cross_depth"] = config["cross_depth"]
        self.config, self.mix = config, mix
        self.model = getattr(models, config["model"])(**kw)
        so, do = config["sparse_optimizer"], config["dense_optimizer"]
        self.trainer = Trainer(
            self.model,
            Adagrad(lr=so["lr"],
                    initial_accumulator_value=so["initial_accumulator_value"]),
            optax.adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
            unique_budget=int(mix["unique_budget"]),
        )
        self.fields = [f"C{c + 1}" for c in range(config["num_cat"])]
        self._init = jax.jit(self.trainer.init)
        self._counters = jax.jit(self._counters_impl)
        self._rows = jax.jit(self._rows_impl)
        self._occupied = jax.jit(self._occupied_impl)

    # ------------------------------------------------------------ the path

    def fresh_state(self, seed: int):
        """Tables (empty) and weights on the device, one jitted call."""
        return self._init(np.int32(seed))

    def put(self, host_batch):
        return self.trainer.stage_batch(host_batch)

    def step(self, state, batch):
        """The timed call. Returns (state, loss) with the loss on the device."""
        state, mets = self.trainer.train_step(state, batch)
        return state, mets["loss"]

    # ------------------------------------------------------------ counters

    def _counters_impl(self, tables):
        tot = lambda name: sum(  # noqa: E731
            jnp.sum(getattr(ts, name)) for ts in tables.values())
        return jnp.stack([tot("insert_fails"), tot("dedup_overflow"),
                          tot("dedup_unique"), tot("dedup_ids")])

    COUNTERS = ("insert_fails", "dedup_overflow", "dedup_unique", "dedup_ids")
    # a step in which one of these rose is a failed step
    FAIL_COUNTERS = ("insert_fails", "dedup_overflow")

    def counters(self, state):
        """Device int32 [4] in COUNTERS' order, summed over the tables."""
        return self._counters(state.tables)

    def _occupied_impl(self, tables):
        return sum(jnp.sum(b.table.occupied(tables[name]))
                   for name, b in self.trainer.bundles.items())

    def occupied_rows(self, state) -> int:
        return int(self._occupied(state.tables))

    def capacity_rows(self) -> int:
        return self.config["capacity"] * self.config["num_cat"]

    # -------------------------------------------------- reading the state

    def _rows_impl(self, state, batch):
        views, _ = self.trainer.forward_views(state, batch)
        return jnp.stack([
            jnp.take(views[f][0], views[f][1].reshape(-1), axis=0)
            for f in self.fields]).astype(jnp.float32)

    def read_rows(self, state, batch):
        """[T, B, D]: the row the state holds for each id of the batch."""
        return self._rows(state, batch)

    def dense_leaves(self, tree) -> Dict[str, jnp.ndarray]:
        """A dense pytree of the model under the reference's leaf names."""
        return {f"{block}.{i}.{k}": layer[k]
                for block, sub in tree.items()
                for i, layer in enumerate(sub["layers"]) for k in ("w", "b")}

    def dense_params(self, state):
        return self.dense_leaves(state.dense)

    def dense_first_moment(self, state):
        """Adam's first moment, which after one step is (1 - b1) x the
        gradient the optimizer was handed."""
        return self.dense_leaves(state.opt_state[0].mu)
