"""Builder for the `qwen3next` family: the program side of a cell.

The only module of this family that imports `deeprec_tpu`. It builds
`models/hybrid_stack.py::HybridStackLM` at the widths the configuration
states, with the experts, the vocabulary and the depth the configuration
says this chip holds, on `Trainer(model, Adagrad, optax.adam)` with one
`tok` feature (`pooling="none"`), and exposes what the harness needs.

Counters are cumulative and live on the device: the engine's four are the
tables' own; the expert layer's three (`moe_pairs`, `moe_overflow`,
`moe_max_load`: held pairs, pairs over the static budget, the fullest held
expert's rows, each summed over the layers) come back from every step with
its metrics and are folded into one device array here by a jitted add, so
that reading them costs no host sync of its own.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

_MOE = ("moe_pairs", "moe_overflow", "moe_max_load")


class Program:
    COUNTERS = ("insert_fails", "dedup_overflow", "dedup_unique",
                "dedup_ids") + _MOE
    # a step in which one of these rose is a failed step
    FAIL_COUNTERS = ("insert_fails", "dedup_overflow", "moe_overflow")

    def __init__(self, config: Dict, mix: Dict):
        import optax

        from deeprec_tpu.models import HybridStackLM
        from deeprec_tpu.optim import Adagrad
        from deeprec_tpu.training import Trainer

        if config["sparse_optimizer"]["name"] != "adagrad" \
                or config["dense_optimizer"]["name"] != "adam":
            raise ValueError("the qwen3next builder knows Adagrad rows and "
                             "a dense Adam only")
        if mix["vocab"] != config["vocab_size"]:
            raise ValueError("traffic mix and configuration disagree on the "
                             "vocabulary held here")
        self.config, self.mix = config, mix
        dep = config["deployment"]
        self.model = HybridStackLM(
            vocab=config["vocab_size"], seq_len=mix["seq_len"],
            capacity=config["capacity"], pair_budget=int(mix["pair_budget"]),
            hidden=config["hidden_size"], layers=config["num_hidden_layers"],
            full_attention_interval=config["full_attention_interval"],
            gdn_key_heads=config["linear_num_key_heads"],
            gdn_value_heads=config["linear_num_value_heads"],
            gdn_key_dim=config["linear_key_head_dim"],
            gdn_value_dim=config["linear_value_head_dim"],
            conv_kernel=config["linear_conv_kernel_dim"],
            attn_heads=config["num_attention_heads"],
            attn_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            partial_rotary_factor=config["partial_rotary_factor"],
            rope_theta=float(config["rope_theta"]),
            num_experts=dep["router_outputs"],
            experts_per_token=config["num_experts_per_tok"],
            expert_width=config["moe_intermediate_size"],
            shared_expert_width=config["shared_expert_intermediate_size"],
            norm_topk_prob=config["norm_topk_prob"],
            held_experts=(dep["first_expert_held"], config["num_experts"]),
            eps=config["rms_norm_eps"],
            init_std=config["embedding_init"]["stddev"],
            chunk=config["delta_rule_chunk"])
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
        """Device int32 [7] in COUNTERS' order."""
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
        the path's keys joined by dots (`layers.0.mixer.qkvz`)."""
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                         for k in path): leaf for path, leaf in flat}

    def dense_params(self, state):
        return self.dense_leaves(state.dense)

    def dense_first_moment(self, state):
        """Adam's first moment, which after one step is (1 - b1) x the
        gradient the optimizer was handed."""
        return self.dense_leaves(state.opt_state[0].mu)
