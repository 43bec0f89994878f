"""Plain reference of the `nexttok` family: float32 `jax.numpy`, matrix
products at `highest`, rows made from their ids by the configuration's
stated initializer, autodiff, Adagrad on the rows and Adam on the dense
parameters. It imports nothing of the program. `mode="bf16"` is the control
(bfloat16 operands, the nearest precision below the float32 the
configuration states); `half_batch=True` the planted fault.
"""
from __future__ import annotations

import zlib
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CONTROLS = {"control_bf16": {"mode": "bf16"},
            "fault_half_batch": {"half_batch": True}}


def _mix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def init_rows(ids, salt: int, config: Dict):
    """ids [n] -> rows [n, D]: a normal drawn per element from a hash of
    (id x D + column) and the table's salt."""
    dim, init = config["emb_dim"], config["embedding_init"]
    x = ids.astype(jnp.int32)[:, None] * jnp.int32(dim) \
        + jnp.arange(dim, dtype=jnp.int32)
    bits = _mix32(x.astype(jnp.uint32) ^ _mix32(jnp.uint32(salt)))
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    z = jnp.sqrt(2.0) * jax.scipy.special.erfinv(
        jnp.clip(2.0 * u - 1.0, -1.0 + 1e-6, 1.0 - 1e-6))
    return init["mean"] + init["stddev"] * z


def row_init(config: Dict, fields: Sequence[str]):
    """ids [T, n] -> rows [T, n, D] for the tables of `fields`, jitted."""
    salts = [zlib.crc32(f.encode()) & 0x7FFFFFFF for f in fields]
    return jax.jit(lambda ids: jnp.stack(
        [init_rows(ids[t], s, config) for t, s in enumerate(salts)]))


def _glorot(key, shape):
    lim = jnp.sqrt(6.0 / (shape[0] + shape[1]))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def init_dense(config: Dict, vocab: int, seed: int):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    D, H = config["emb_dim"], config["hidden"]
    return {"hid.w": _glorot(k1, (D, H)), "hid.b": jnp.zeros((H,)),
            "out.w": _glorot(k2, (H, vocab)), "out.b": jnp.zeros((vocab,))}


def _mm(x, w, mode: str):
    if mode == "bf16":
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(x, w, precision=HIGHEST)


def loss_fn(params, rows, idx, labels, mode: str):
    emb = rows[idx]                                         # [B, S, D]
    steps = jnp.arange(1, emb.shape[1] + 1, dtype=jnp.float32)
    mixed = jnp.cumsum(emb, axis=1) / steps[None, :, None]
    h = jax.nn.relu(_mm(mixed, params["hid.w"], mode) + params["hid.b"])
    z = _mm(h, params["out.w"], mode) + params["out.b"]
    return jnp.mean(jnp.maximum(z, 0.0) - z * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(z))))


def run(config: Dict, batches: Sequence[Dict[str, np.ndarray]], seed: int, *,
        mode: str = "highest", half_batch: bool = False) -> Dict:
    """Follow the first len(batches) training steps from the seed: {"loss",
    "grad", "change", "size"} by leaf; the table is the leaf "table.tok"."""
    if half_batch:
        batches = [{k: v[:len(v) // 2] for k, v in b.items()}
                   for b in batches]
    vocab = batches[0]["label"].shape[-1]
    ids = np.unique(np.concatenate([b["tok"].reshape(-1) for b in batches]))
    rows0 = init_rows(jnp.asarray(ids), zlib.crc32(b"tok") & 0x7FFFFFFF,
                      config)
    params0 = init_dense(config, vocab, seed)
    so, do = config["sparse_optimizer"], config["dense_optimizer"]
    accum = jnp.full_like(rows0, so["initial_accumulator_value"])
    m = jax.tree.map(jnp.zeros_like, params0)
    v = jax.tree.map(jnp.zeros_like, params0)
    params, rows, losses, first = params0, rows0, [], None
    step = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)),
                   static_argnums=(4,))
    for t, b in enumerate(batches, start=1):
        loss, (g, g_rows) = step(params, rows, np.searchsorted(ids, b["tok"]),
                                 b["label"], mode)
        losses.append(float(loss))
        first = first or (g, g_rows)
        accum = accum + g_rows * g_rows
        rows = rows - so["lr"] * g_rows * jax.lax.rsqrt(
            jnp.maximum(accum, 1e-30))
        m = jax.tree.map(lambda a, x: do["b1"] * a + (1 - do["b1"]) * x, m, g)
        v = jax.tree.map(lambda a, x: do["b2"] * a + (1 - do["b2"]) * x * x,
                         v, g)
        c1, c2 = 1 - do["b1"] ** t, 1 - do["b2"] ** t
        params = jax.tree.map(
            lambda p, a, b_: p - do["lr"] * (a / c1) / (jnp.sqrt(b_ / c2)
                                                        + do["eps"]),
            params, m, v)
    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))  # noqa: E731
    out = {"loss": losses,
           "grad": {k: norm(x) for k, x in first[0].items()},
           "change": {k: norm(params[k] - params0[k]) for k in params0},
           "size": {k: int(x.size) for k, x in params0.items()}}
    out["grad"]["table.tok"] = norm(first[1])
    out["change"]["table.tok"] = norm(rows - rows0)
    out["size"]["table.tok"] = int(rows0.size)
    return out
