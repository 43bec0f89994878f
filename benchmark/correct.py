"""The comparison that decides `correct`.

Two sets of readings of the first training steps, the program's and the plain
reference's, each `{"loss": [per step], "grad": {leaf: norm of the first
gradient}, "change": {leaf: norm of the parameters' change after the last
step}}`, are compared number by number, each against a limit of its own
(`limits/<cell>.json`; PERF.md gives the readings each limit was set from).

Norms are compared leaf by leaf: the gap between the program's norm and the
reference's, against the reference's norm of that leaf or of the median leaf,
whichever is larger. `grad_gap` and `change_gap` are the worst leaf's gap,
`grad_median_gap` and `change_median_gap` the median leaf's (steady from seed
to seed where the worst leaf, a bias of a few elements, swings). A leaf whose
reference gradient is under a thousandth of the median leaf's moves under
Adam by round-off alone and is left out of the change. So is a leaf of one
element (the reference's `size`): under Adam it moves by about the learning
rate whatever its gradient's size, so where its gradient is near nought a
different rounding changes its whole step (two seeds in about sixty read a
change gap of 0.56 and 0.63 on the last layer's bias, parent and change
alike: PERF.md section 4); it is held on its gradient, among the leaves of
`grad_gap` and `grad_median_gap`, as every leaf is. A number that a
cell's `limits` file does not hold is read and printed but not compared:
PERF.md names each with its readings. `fill_gap` is the harness's own and
in no file: the share by which the rows the tables hold at the window's
start miss the vocabulary that set-up filled them with, limit 0.
"""
from __future__ import annotations

import json
import math
import os
import statistics
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NUMBERS = ("loss1_gap", "loss2_gap", "loss3_gap", "grad_gap",
           "grad_median_gap", "change_gap", "change_median_gap", "fill_gap")


def load_limits(cell: str, base: str = HERE) -> Dict[str, float]:
    with open(os.path.join(base, "limits", cell + ".json")) as f:
        limits = json.load(f)["limits"]
    unknown = [n for n in limits if n not in NUMBERS or n == "fill_gap"]
    if unknown or not limits:
        raise ValueError(f"limits of cell {cell!r}: none, or unknown numbers "
                         f"{unknown}; the numbers are {NUMBERS}")
    return {n: float(v) for n, v in limits.items()}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              leaves: Sequence[str]) -> Dict[str, float]:
    """{leaf: |program's norm - reference's| over the larger of the
    reference's norm of the leaf and of the median leaf}; a norm that is
    missing or not a number reads as an infinite gap."""
    floor = statistics.median(ref[k] for k in ref)
    out = {}
    for k in leaves:
        gap = abs(prog.get(k, float("nan")) - ref[k]) / max(ref[k], floor,
                                                            1e-30)
        out[k] = gap if gap == gap else float("inf")
    return out


def compare(prog: Dict, ref: Dict) -> Dict[str, Dict]:
    """{number: {"value", "leaf"}} for every compared number."""
    out = {}
    for i, (lp, lr) in enumerate(zip(prog["loss"], ref["loss"]), start=1):
        gap = abs(lp - lr) / max(abs(lr), 1e-30)
        out[f"loss{i}_gap"] = {"value": gap if math.isfinite(gap)
                               else float("inf"), "leaf": ""}
    g_floor = 1e-3 * statistics.median(ref["grad"].values())
    sizes = ref.get("size", {})
    moved = [k for k in ref["change"]
             if ref["grad"][k] >= g_floor and sizes.get(k, 2) > 1]
    for kind, leaves in (("grad", list(ref["grad"])), ("change", moved)):
        gaps = leaf_gaps(prog[kind], ref[kind], leaves)
        where = max(gaps, key=gaps.get)
        out[f"{kind}_gap"] = {"value": gaps[where], "leaf": where}
        out[f"{kind}_median_gap"] = {
            "value": statistics.median(gaps.values()), "leaf": ""}
    return out


def verdict(numbers: Dict[str, Dict], limits: Dict[str, float]):
    """(correct, {number: {"value", "limit"}}) over the numbers that have a
    limit, in NUMBERS' order."""
    table = {n: {"value": numbers[n]["value"], "limit": limits[n]}
             for n in NUMBERS if n in limits}
    ok = all(v["value"] <= v["limit"] for v in table.values())
    return ok, table


# ------------------------------------------------- the program's readings


def multiplicity_weights(id_batches: Sequence[np.ndarray]):
    """For ids [T, B] of several batches: weights [T, B] per batch, each
    1 / (times the id occurs in its field over all the batches), so that a
    sum over positions counts every distinct id once."""
    cat = np.concatenate(id_batches, axis=1)
    w = np.empty(cat.shape, np.float32)
    for t, row in enumerate(cat):
        _, inv, cnt = np.unique(row, return_inverse=True, return_counts=True)
        w[t] = 1.0 / cnt[inv]
    return np.split(w, np.cumsum([b.shape[1] for b in id_batches])[:-1],
                    axis=1)


@jax.jit
def _sumsq_change(rows, init, w):
    d = rows - init
    return jnp.sum(w[..., None] * d * d, axis=(1, 2))


@jax.jit
def _sumsq_adagrad_grad(rows, init, w, lr, accum0):
    # Adagrad's first step moved the row by d = -lr g / sqrt(accum0 + g^2),
    # so g = -d sqrt(accum0) / sqrt(lr^2 - d^2).
    d = rows - init
    g = d * jnp.sqrt(accum0) * jax.lax.rsqrt(jnp.maximum(lr * lr - d * d,
                                                         1e-30))
    return jnp.sum(w[..., None] * g * g, axis=(1, 2))


@jax.jit
def _tree_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


@jax.jit
def _tree_diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))),
                        a, b)


@jax.jit
def _tree_copy(tree):
    return jax.tree.map(jnp.copy, tree)


class ProgramReadings:
    """Collects the program's side of the comparison while set-up drives the
    first steps through the timed call. `ref_init(ids [T, n]) -> [T, n, D]`
    is the reference's own initializer: the program's rows are measured from
    it, so a program that initialized otherwise reads as a gap.

    It covers any model on `Trainer` with Adagrad rows and a dense Adam,
    whatever its fields are called and however their ids are shaped (a
    field's ids are read flat: n is a batch's positions). Of the program
    it needs `fields`, `read_rows(state, batch) -> [T, n, D]`,
    `dense_params(state)` and `dense_first_moment(state)` (both {leaf
    name: array} under the reference's names); of the configuration
    `sparse_optimizer` (`lr`, `initial_accumulator_value`) and
    `dense_optimizer` (`b1`)."""

    def __init__(self, program, config: Dict, ref_init):
        self.program, self.config, self.ref_init = program, config, ref_init
        self.fields = program.fields
        self.loss = []
        self._batches, self._ids = [], []
        self._dense0 = None

    def before_first_step(self, state):
        self._dense0 = _tree_copy(self.program.dense_params(state))

    def _field_ids(self, host_batch):
        return np.stack([np.asarray(host_batch[f]).reshape(-1)
                         for f in self.fields])

    def after_step(self, state, host_batch, dev_batch, loss):
        self.loss.append(loss)
        self._batches.append(dev_batch)
        self._ids.append(self._field_ids(host_batch))
        if len(self.loss) == 1:
            so = self.config["sparse_optimizer"]
            ids = self._ids[0]
            (w,) = multiplicity_weights([ids])
            self._grad_tables = _sumsq_adagrad_grad(
                self.program.read_rows(state, dev_batch),
                self.ref_init(ids), w, np.float32(so["lr"]),
                np.float32(so["initial_accumulator_value"]))
            self._grad_dense = _tree_norms(
                self.program.dense_first_moment(state))

    def after_last_step(self, state):
        ws = multiplicity_weights(self._ids)
        self._change_tables = [
            _sumsq_change(self.program.read_rows(state, dev),
                          self.ref_init(ids), w)
            for ids, w, dev in zip(self._ids, ws, self._batches)]
        self._change_dense = _tree_diff_norms(
            self.program.dense_params(state), self._dense0)
        self._batches, self._dense0 = [], None

    def host(self) -> Dict:
        """The readings as plain floats (waits for the device; every square
        root and scale is taken here, on the host)."""
        b1 = self.config["dense_optimizer"]["b1"]
        grad = {k: float(v) / (1.0 - b1) for k, v in self._grad_dense.items()}
        g_ss = np.asarray(self._grad_tables, np.float64)
        c_ss = sum(np.asarray(x, np.float64) for x in self._change_tables)
        change = {k: float(v) for k, v in self._change_dense.items()}
        for t, f in enumerate(self.fields):
            grad[f"table.{f}"] = float(np.sqrt(g_ss[t]))
            change[f"table.{f}"] = float(np.sqrt(c_ss[t]))
        return {"loss": [float(x) for x in self.loss], "grad": grad,
                "change": change}
