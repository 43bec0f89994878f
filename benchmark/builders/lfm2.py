"""Builder for the `lfm2` family: the program side of a cell.

The only module of this family that imports `deeprec_tpu`. It builds
`models/conv_stack.py::ConvStackLM` at the widths the configuration
states, with the layers (their mixers the configuration's `layer_types`,
the leading dense ones among them), the experts and the vocabulary the
configuration says this chip holds, on `Trainer(model, Adagrad,
optax.adam)` with one `tok` feature (`pooling="none"`), and exposes what
the harness needs. The routers' selection bias is a leaf of the dense tree
that the model's `after_update` moves inside the timed step (the trainer's
hook); nothing here touches it.

Counters are cumulative and live on the device: the engine's four are the
tables' own; the expert layers' four (`moe_pairs`, `moe_overflow`,
`moe_max_load`, `moe_all_max_load`: held pairs, pairs over the static
budget, the fullest held expert's rows, the fullest of ALL the router's
outputs' choices, each summed over the expert layers) come back from every
step with its metrics and are folded into one device array here by a jitted
add, so that reading them costs no host sync of its own. They are int32 and
wrap: a reader takes a window's rise modulo 2^32.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

_MOE = ("moe_pairs", "moe_overflow", "moe_max_load", "moe_all_max_load")


class Program:
    COUNTERS = ("insert_fails", "dedup_overflow", "dedup_unique",
                "dedup_ids") + _MOE
    # a step in which one of these rose is a failed step
    FAIL_COUNTERS = ("insert_fails", "dedup_overflow", "moe_overflow")

    def __init__(self, config: Dict, mix: Dict):
        import optax

        from deeprec_tpu.models import ConvStackLM
        from deeprec_tpu.optim import Adagrad
        from deeprec_tpu.training import Trainer

        if config["sparse_optimizer"]["name"] != "adagrad" \
                or config["dense_optimizer"]["name"] != "adam":
            raise ValueError("the lfm2 builder knows Adagrad rows "
                             "and a dense Adam only")
        if mix["vocab"] != config["vocab_size"]:
            raise ValueError("traffic mix and configuration disagree on the "
                             "vocabulary held here")
        if mix["seq_len"] > config["max_position_embeddings"]:
            raise ValueError("the traffic mix's sequences are longer than "
                             "the configuration's positions")
        if (config["conv_bias"], config["use_expert_bias"]) != (False, True):
            raise ValueError("the lfm2 builder knows short convolutions "
                             "without a bias and a router with a selection "
                             "bias")
        self.config, self.mix = config, mix
        dep = config["deployment"]
        H = config["num_attention_heads"]
        self.model = ConvStackLM(
            vocab=config["vocab_size"], seq_len=mix["seq_len"],
            capacity=config["capacity"], pair_budget=int(mix["pair_budget"]),
            hidden=config["hidden_size"], layers=config["num_hidden_layers"],
            layer_types=tuple(config["layer_types"]),
            dense_layers=config["num_dense_layers"],
            dense_width=config["intermediate_size"],
            conv_kernel=config["conv_L_cache"],
            attn_heads=H, attn_kv_heads=config["num_key_value_heads"],
            head_dim=config["hidden_size"] // H,
            rope_theta=float(config["rope_theta"]),
            num_experts=dep["router_outputs"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            renorm_eps=config["router_renorm_eps"],
            bias_update_rate=config["bias_update_rate"],
            held_experts=(dep["first_expert_held"], config["num_experts"]),
            eps=config["norm_eps"],
            init_std=config["embedding_init"]["stddev"])
        so, do = config["sparse_optimizer"], config["dense_optimizer"]
        self.trainer = Trainer(
            self.model,
            Adagrad(lr=so["lr"],
                    initial_accumulator_value=so["initial_accumulator_value"]),
            optax.adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
            unique_budget=int(mix["unique_budget"]))
        self.fields = ["tok"]
        self._init = jax.jit(self.trainer.init)
        self._counters = jax.jit(lambda tables, folded: jnp.concatenate([
            jnp.stack([sum(jnp.sum(getattr(ts, name))
                           for ts in tables.values()).astype(jnp.int32)
                       for name in self.COUNTERS[:4]]), folded]))
        self._fold = jax.jit(lambda folded, mets: folded + jnp.stack(
            [mets[name].astype(jnp.int32) for name in _MOE]))
        self._rows = jax.jit(self._rows_impl)
        self._occupied = jax.jit(lambda tables: sum(
            jnp.sum(b.table.occupied(tables[name]))
            for name, b in self.trainer.bundles.items()))
        self._folded = jnp.zeros((len(_MOE),), jnp.int32)

    # ------------------------------------------------------------ the path

    def fresh_state(self, seed: int):
        """Table (empty) and weights on the device, one jitted call."""
        self._folded = jnp.zeros((len(_MOE),), jnp.int32)
        return self._init(np.int32(seed))

    def put(self, host_batch):
        return self.trainer.stage_batch(host_batch)

    def step(self, state, batch):
        """The timed call. Returns (state, loss) with the loss on the device."""
        state, mets = self.trainer.train_step(state, batch)
        self._folded = self._fold(self._folded, mets)
        return state, mets["loss"]

    def counters(self, state):
        """Device int32 [8] in COUNTERS' order."""
        return self._counters(state.tables, self._folded)

    def occupied_rows(self, state) -> int:
        return int(self._occupied(state.tables))

    def capacity_rows(self) -> int:
        return self.config["capacity"]

    # -------------------------------------------------- reading the state

    def _rows_impl(self, state, batch):
        views, _ = self.trainer.forward_views(state, batch)
        rows, inverse = views["tok"][0], views["tok"][1]
        return jnp.take(rows, inverse.reshape(-1),
                        axis=0).astype(jnp.float32)[None]

    def read_rows(self, state, batch):
        """[1, B x S, D]: the row the state holds for each position."""
        return self._rows(state, batch)

    def dense_leaves(self, tree) -> Dict[str, jnp.ndarray]:
        """A dense pytree of the model under the reference's leaf names:
        the path's keys joined by dots (`layers.1.moe.bias`)."""
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                         for k in path): leaf for path, leaf in flat}

    def dense_params(self, state):
        return self.dense_leaves(state.dense)

    def dense_first_moment(self, state):
        """Adam's first moment, which after one step is (1 - b1) x the
        gradient the optimizer was handed."""
        return self.dense_leaves(state.opt_state[0].mu)
