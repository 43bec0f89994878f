"""Sparse optimizers for hash-embedding tables.

DeepRec registers 88 KvResourceSparseApply* ops (/root/reference/tensorflow/
core/ops/training_ali_ops.cc; kernels core/kernels/training_ali_ops.cc) —
per-key slot updates executed inside the PS. Here each optimizer is a pure
row-function: it receives the gathered value/slot rows for the unique touched
keys ([U, D]) plus per-key batch counts, and returns updated rows which the
table scatters back. XLA fuses the whole thing into one pass over [U, D],
where U is the dedup width — the unique BUDGET when the budgeted dedup
(ops/dedup.py) is engaged, so the optimizer pass shrinks with it too.

`*WithCounts` semantics: DeepRec's WithCounts variants thread the per-key
occurrence count through the apply so frequency is recorded and (for some
optimizers) the gradient is de-duplicated. Our tables update `freq` at lookup
time; here `counts` optionally averages the summed duplicate gradients
(`grad_averaging=True`).

Slot layout: slots live in TableState.slots as [C, D] (or [C, 1]) arrays next
to the values — the TPU translation of DeepRec storing slot EVs alongside the
primary EV. Per-table scalar state (AdamAsync beta powers) is kept as [1, 1]
arrays, exempt from rebuild row-moves.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray
Slots = Dict[str, Array]

# Slot names with this prefix are per-table scalars, not per-key rows.
SCALAR_PREFIX = "scalar/"


@dataclasses.dataclass(frozen=True)
class SparseOptimizer:
    """Base: hyperparameters are static floats; `lr` may be overridden per
    apply-call with a traced scalar (for schedules without recompiles)."""

    lr: float = 0.01

    def slot_specs(self, dim: int) -> Dict[str, Tuple[Tuple[int, ...], float]]:
        """name -> (row_shape, init_value). Row shape (dim,) or (1,)."""
        return {}

    def update(
        self,
        value: Array,  # [U, D]
        slots: Slots,  # each [U, D]/[U, 1] (scalars delivered as [1, 1])
        grad: Array,  # [U, D] summed over duplicates
        counts: Array,  # [U] int32
        step: Array,  # [] int32 global step
        lr: Array,  # [] learning rate
    ) -> Tuple[Array, Slots]:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class GradientDescent(SparseOptimizer):
    """KvResourceSparseApplyGradientDescent."""

    def update(self, value, slots, grad, counts, step, lr):
        return value - lr * grad, {}


@dataclasses.dataclass(frozen=True)
class Adagrad(SparseOptimizer):
    """KvResourceSparseApplyAdagrad (training_ali_ops.cc)."""

    initial_accumulator_value: float = 0.1

    def slot_specs(self, dim):
        return {"accum": ((dim,), self.initial_accumulator_value)}

    def update(self, value, slots, grad, counts, step, lr):
        acc = slots["accum"] + grad * grad
        # guard acc==0 (possible after external slot resets + zero grad):
        # rsqrt(0) would turn a zero update into NaN
        new_value = value - lr * grad * jax.lax.rsqrt(jnp.maximum(acc, 1e-30))
        return new_value, {"accum": acc}


@dataclasses.dataclass(frozen=True)
class AdagradDecay(SparseOptimizer):
    """KvResourceSparseApplyAdagradDecay — Adagrad whose accumulator is
    periodically discounted so ancient history fades (semantics:
    docs/docs_en/AdagradDecay-Optimizer.md: every `accumulator_decay_step`
    global steps the accumulator is scaled by `accumulator_decay_rate` with a
    floor of `accumulator_baseline`). Sparse keys apply the decay lazily: the
    number of elapsed decay periods since the key's last update is derived
    from a per-key period slot."""

    initial_accumulator_value: float = 0.1
    accumulator_decay_step: int = 100000
    accumulator_decay_rate: float = 0.9
    accumulator_baseline: float = 0.0

    def slot_specs(self, dim):
        return {
            "accum": ((dim,), self.initial_accumulator_value),
            "decay_period": ((1,), 0.0),
        }

    def update(self, value, slots, grad, counts, step, lr):
        period = (step // jnp.int32(self.accumulator_decay_step)).astype(jnp.float32)
        # decay_period stores (last applied period + 1); 0 marks a
        # never-updated key, whose fresh accumulator must NOT be decayed
        # retroactively by the current global period.
        stored = slots["decay_period"][:, 0]
        elapsed = jnp.where(stored > 0.0, jnp.maximum(period - (stored - 1.0), 0.0), 0.0)
        scale = jnp.power(self.accumulator_decay_rate, elapsed)[:, None]
        acc = jnp.maximum(slots["accum"] * scale, self.accumulator_baseline)
        acc = acc + grad * grad
        new_value = value - lr * grad * jax.lax.rsqrt(jnp.maximum(acc, 1e-30))
        new_period = jnp.full_like(slots["decay_period"], 0.0) + period + 1.0
        return new_value, {"accum": acc, "decay_period": new_period}


@dataclasses.dataclass(frozen=True)
class Adam(SparseOptimizer):
    """KvResourceSparseApplyAdam — bias correction from the global step."""

    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8

    def slot_specs(self, dim):
        return {"m": ((dim,), 0.0), "v": ((dim,), 0.0)}

    def update(self, value, slots, grad, counts, step, lr):
        t = (step + 1).astype(jnp.float32)
        m = self.beta1 * slots["m"] + (1.0 - self.beta1) * grad
        v = self.beta2 * slots["v"] + (1.0 - self.beta2) * grad * grad
        # bias-corrected step size: lr * sqrt(1 - b2^t) / (1 - b1^t)
        alpha = lr * jnp.sqrt(1.0 - jnp.power(self.beta2, t)) / (
            1.0 - jnp.power(self.beta1, t)
        )
        new_value = value - alpha * m / (jnp.sqrt(v) + self.epsilon)
        return new_value, {"m": m, "v": v}


@dataclasses.dataclass(frozen=True)
class AdamAsync(SparseOptimizer):
    """KvResourceSparseApplyAdamAsync (docs/docs_en/AdamAsync-Optimizer.md):
    designed for async-PS training — beta powers live as *per-variable slots*
    advanced on every apply instead of reading the global step, so stale/
    lock-free updates stay well-scaled. With `apply_sparse_rmsprop` the update
    skips momentum bias correction and uses an RMSProp-style step (the doc's
    sparse variant).

    In a synchronous SPMD world the convergence-relevant part is the
    per-variable power schedule, which is reproduced exactly; equivalence with
    the async execution model is at the AUC level (SURVEY.md §7 hard parts e).
    """

    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    apply_sparse_rmsprop: bool = False

    def slot_specs(self, dim):
        return {
            "m": ((dim,), 0.0),
            "v": ((dim,), 0.0),
            SCALAR_PREFIX + "beta1_power": ((1,), self.beta1),
            SCALAR_PREFIX + "beta2_power": ((1,), self.beta2),
        }

    def update(self, value, slots, grad, counts, step, lr):
        b1p = slots[SCALAR_PREFIX + "beta1_power"][0, 0]
        b2p = slots[SCALAR_PREFIX + "beta2_power"][0, 0]
        if self.apply_sparse_rmsprop:
            v = self.beta2 * slots["v"] + (1.0 - self.beta2) * grad * grad
            m = self.beta1 * slots["m"] + (1.0 - self.beta1) * grad
            new_value = value - lr * m * jax.lax.rsqrt(v + self.epsilon)
        else:
            m = self.beta1 * slots["m"] + (1.0 - self.beta1) * grad
            v = self.beta2 * slots["v"] + (1.0 - self.beta2) * grad * grad
            alpha = lr * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
            new_value = value - alpha * m / (jnp.sqrt(v) + self.epsilon)
        return new_value, {
            "m": m,
            "v": v,
            SCALAR_PREFIX + "beta1_power": slots[SCALAR_PREFIX + "beta1_power"]
            * self.beta1,
            SCALAR_PREFIX + "beta2_power": slots[SCALAR_PREFIX + "beta2_power"]
            * self.beta2,
        }


@dataclasses.dataclass(frozen=True)
class AdamW(SparseOptimizer):
    """KvResourceSparseApplyAdamW — Adam with decoupled weight decay."""

    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    weight_decay: float = 0.01

    def slot_specs(self, dim):
        return {"m": ((dim,), 0.0), "v": ((dim,), 0.0)}

    def update(self, value, slots, grad, counts, step, lr):
        t = (step + 1).astype(jnp.float32)
        m = self.beta1 * slots["m"] + (1.0 - self.beta1) * grad
        v = self.beta2 * slots["v"] + (1.0 - self.beta2) * grad * grad
        alpha = lr * jnp.sqrt(1.0 - jnp.power(self.beta2, t)) / (
            1.0 - jnp.power(self.beta1, t)
        )
        new_value = value - alpha * (
            m / (jnp.sqrt(v) + self.epsilon)
        ) - lr * self.weight_decay * value
        return new_value, {"m": m, "v": v}


@dataclasses.dataclass(frozen=True)
class Ftrl(SparseOptimizer):
    """KvResourceSparseApplyFtrl — FTRL-proximal, the classic CTR optimizer."""

    learning_rate_power: float = -0.5
    initial_accumulator_value: float = 0.1
    l1: float = 0.0
    l2: float = 0.0

    def slot_specs(self, dim):
        return {
            "accum": ((dim,), self.initial_accumulator_value),
            "linear": ((dim,), 0.0),
        }

    def update(self, value, slots, grad, counts, step, lr):
        accum, linear = slots["accum"], slots["linear"]
        new_accum = accum + grad * grad
        p = -self.learning_rate_power
        sigma = (jnp.power(new_accum, p) - jnp.power(accum, p)) / lr
        linear = linear + grad - sigma * value
        quad = jnp.power(new_accum, p) / lr + 2.0 * self.l2
        l1_reg = self.l1 * jnp.sign(linear)
        new_value = jnp.where(
            jnp.abs(linear) > self.l1, (l1_reg - linear) / quad, 0.0
        )
        return new_value, {"accum": new_accum, "linear": linear}


REGISTRY = {
    "sgd": GradientDescent,
    "adagrad": Adagrad,
    "adagrad_decay": AdagradDecay,
    "adam": Adam,
    "adam_async": AdamAsync,
    "adamw": AdamW,
    "ftrl": Ftrl,
}


def make(name: str, **kw) -> SparseOptimizer:
    return REGISTRY[name](**kw)
