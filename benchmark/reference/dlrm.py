"""Plain reference for DLRM and DLRM-DCNv2 training steps.

Straightforward `jax.numpy` in float32 with every matrix product at
`highest` precision: a table of rows per field (the ids the steps touch, each
row made from its id by the configuration's stated initializer), the dense
forward, the loss, autodiff for the gradients, Adagrad on the rows and Adam on
the dense parameters, as the configuration file states them. It imports
nothing of `deeprec_tpu` and takes nothing the program made: weights and rows
come from the seed and the ids.

`mode` selects the arithmetic of the matrix products:
  "highest"  the reference itself
  "fp8"      the control: operands rounded to float8_e4m3 and their
             gradients to float8_e5m2 (each scaled per tensor to its largest
             magnitude), the nearest precision below the bfloat16 operands
             (and bfloat16 operand gradients) the configurations state
  "bf16"     the second witness: the program's own arithmetic, bfloat16
             operands with float32 accumulation, in this plain code
`half_batch=True` is the planted fault "half of the batch left out, the mean
taken over the rest".
"""
from __future__ import annotations

import functools
import json
import zlib
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# what tools/calibrate.py puts in the program's place, and with which
# arguments of `run`: the control, the planted fault, the second witness
CONTROLS = {"control_fp8": {"mode": "fp8"},
            "fault_half_batch": {"half_batch": True},
            "witness_bf16": {"mode": "bf16"}}


# ------------------------------------------------------------ initializers


def _mix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def field_salt(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def init_rows(ids, salts, dim: int, mean: float, stddev: float):
    """Rows of new ids: a normal(mean, stddev) drawn per element from a hash
    of (id * dim + column) and the field's salt. ids [T, n] int32, salts [T]."""
    col = jnp.arange(dim, dtype=jnp.int32)
    x = ids.astype(jnp.int32)[..., None] * jnp.int32(dim) + col
    salt = _mix32(jnp.asarray(salts).astype(jnp.uint32))[:, None, None]
    bits = _mix32(x.astype(jnp.uint32) ^ salt)
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    z = jnp.sqrt(2.0) * jax.scipy.special.erfinv(
        jnp.clip(2.0 * u - 1.0, -1.0 + 1e-6, 1.0 - 1e-6))
    return mean + stddev * z


def row_init(config: Dict, fields: Sequence[str]):
    """ids [T, n] -> rows [T, n, D] as the configuration's stated
    initializer makes them, for the tables of `fields`, jitted: what the
    comparison measures the program's rows from."""
    salts = np.asarray([field_salt(f) for f in fields], np.uint32)
    init = config["embedding_init"]
    return jax.jit(lambda ids: init_rows(
        ids, salts, config["emb_dim"], init["mean"], init["stddev"]))


def _glorot(key, shape):
    lim = jnp.sqrt(6.0 / (shape[0] + shape[-1]))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def _mlp_init(key, in_dim: int, widths: Sequence[int]):
    layers, d = [], in_dim
    for k, h in zip(jax.random.split(key, len(widths)), widths):
        kw, _ = jax.random.split(k)
        layers.append({"w": _glorot(kw, (d, h)), "b": jnp.zeros((h,))})
        d = h
    return layers


def init_dense(config: Dict, seed: int) -> Dict[str, List[Dict]]:
    """Dense parameters from the seed: glorot-uniform weights, zero biases,
    the key split once per block and once per layer."""
    key = jax.random.PRNGKey(seed)
    n_dense, D = config["num_dense"], config["emb_dim"]
    fields = config["num_cat"] + 1
    if config["interaction"] == "dot":
        k1, k2 = jax.random.split(key)
        top_in = fields * (fields - 1) // 2 + D
        return {"bottom": _mlp_init(k1, n_dense, config["bottom_mlp"]),
                "top": _mlp_init(k2, top_in, config["top_mlp"])}
    k1, k2, k3 = jax.random.split(key, 3)
    w = fields * D
    cross = [{"w": _glorot(k, (w, w)), "b": jnp.zeros((w,))}
             for k in jax.random.split(k2, config["cross_depth"])]
    return {"bottom": _mlp_init(k1, n_dense, config["bottom_mlp"]),
            "cross": cross, "top": _mlp_init(k3, w, config["top_mlp"])}


def leaf_names(params) -> Dict[str, jnp.ndarray]:
    """{"bottom.0.w": array, ...}: the names the comparison speaks in."""
    return {f"{block}.{i}.{k}": layer[k]
            for block, layers in params.items()
            for i, layer in enumerate(layers) for k in ("w", "b")}


# ----------------------------------------------------------------- forward


def _round_to(x, dtype, top: float):
    """x rounded to an 8-bit float after scaling its largest magnitude to
    the format's largest value."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    """A matrix product's operand in float8: the value rounded to e4m3 and,
    as the program rounds an operand's gradient to the operand's bfloat16,
    its gradient rounded to e5m2 (the usual float8 training recipe)."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, ct):
    return (_round_to(ct, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(x, w, mode: str):
    if mode == "fp8":
        x, w = _fp8(x), _fp8(w)
    elif mode == "bf16":
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(x, w, precision=HIGHEST)


def _mlp(layers, x, mode: str, final_relu: bool):
    for i, layer in enumerate(layers):
        x = _mm(x, layer["w"], mode) + layer["b"]
        if i < len(layers) - 1 or final_relu:
            x = jax.nn.relu(x)
    return x


def loss_fn(params, emb, dense, labels, config: Dict, mode: str):
    """emb [T, B, D] one row per field per example (bags of one id, so the
    mean pooling is the row); dense [B, num_dense]; labels [B]."""
    x = jnp.log1p(jnp.maximum(dense, 0.0))
    bottom = _mlp(params["bottom"], x, mode, final_relu=True)
    if config["interaction"] == "dot":
        stack = jnp.concatenate(
            [bottom[:, None, :], jnp.moveaxis(emb, 0, 1)], axis=1)
        s = _fp8(stack) if mode == "fp8" else stack
        z = jnp.einsum("bfd,bgd->bfg", s, s, precision=HIGHEST)
        i, j = jnp.triu_indices(stack.shape[1], k=1)
        top_in = jnp.concatenate([bottom, z[:, i, j]], axis=-1)
    else:
        x0 = jnp.concatenate([bottom] + [emb[t] for t in range(emb.shape[0])],
                             axis=-1)
        xl = x0
        for layer in params["cross"]:
            xl = x0 * (_mm(xl, layer["w"], mode) + layer["b"]) + xl
        top_in = xl
    logits = _mlp(params["top"], top_in, mode, final_relu=False)[:, 0]
    return jnp.mean(jnp.maximum(logits, 0.0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


# -------------------------------------------------------------------- steps


def _train_step(params, adam, rows, accum, idx, dense, labels, t, *,
                config: Dict, mode: str):
    def f(params, rows):
        emb = jnp.take_along_axis(rows, idx[:, :, None], axis=1)
        return loss_fn(params, emb, dense, labels, config, mode)

    loss, (g_dense, g_rows) = jax.value_and_grad(f, argnums=(0, 1))(
        params, rows)
    so = config["sparse_optimizer"]
    accum = accum + g_rows * g_rows
    rows = rows - so["lr"] * g_rows * jax.lax.rsqrt(jnp.maximum(accum, 1e-30))
    do = config["dense_optimizer"]
    b1, b2 = do["b1"], do["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, adam["m"], g_dense)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, adam["v"],
                     g_dense)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - do["lr"] * (m / c1) / (jnp.sqrt(v / c2)
                                                   + do["eps"]),
        params, m, v)
    return params, {"m": m, "v": v}, rows, accum, loss, g_dense, g_rows


def _prepare(ids, salts, seed, *, config: Dict):
    init = config["embedding_init"]
    rows = init_rows(ids, salts, config["emb_dim"], init["mean"],
                     init["stddev"])
    params = init_dense(config, seed)
    accum = jnp.full_like(
        rows, config["sparse_optimizer"]["initial_accumulator_value"])
    zeros = jax.tree.map(jnp.zeros_like, params)
    return params, {"m": zeros, "v": zeros}, rows, accum


def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def _summarise(params0, rows0, params, rows, g_dense, g_rows):
    """Norms per leaf: the first gradient and the parameters' change, the
    table of field c as one leaf (axes 1, 2 of the stacked rows)."""
    table = lambda x: jnp.sqrt(jnp.sum(jnp.square(x), axis=(1, 2)))  # noqa: E731
    change = jax.tree.map(lambda a, b: a - b, params, params0)
    return {"grad": _norms(leaf_names(g_dense)), "grad_tables": table(g_rows),
            "change": _norms(leaf_names(change)),
            "change_tables": table(rows - rows0)}


@functools.lru_cache(maxsize=None)
def _jitted(config_json: str, mode: str):
    config = json.loads(config_json)
    return (jax.jit(functools.partial(_prepare, config=config)),
            jax.jit(functools.partial(_train_step, config=config, mode=mode)),
            jax.jit(_summarise))


def index_batches(batches: Sequence[Dict[str, np.ndarray]], fields):
    """(ids [T, n] int32, idx list of [T, B] int32): the distinct ids of each
    field over the batches and each batch's positions among them. n is the
    number of positions (steps x batch), whatever the ids, so that every seed
    compiles the same programs; the rows past a field's distinct ids repeat
    its first id and nothing points at them."""
    per_field = [np.unique(np.concatenate([b[f] for b in batches]))
                 for f in fields]
    n = sum(len(b[fields[0]]) for b in batches)
    ids = np.stack([np.concatenate([u, np.full(n - len(u), u[0], u.dtype)])
                    for u in per_field]).astype(np.int32)
    idx = [np.stack([np.searchsorted(u, b[f]) for u, f in
                     zip(per_field, fields)]).astype(np.int32)
           for b in batches]
    return ids, idx


def run(config: Dict, batches: Sequence[Dict[str, np.ndarray]], seed: int, *,
        mode: str = "highest", half_batch: bool = False) -> Dict:
    """Follow the first len(batches) training steps from the seed.

    Returns {"loss": [per step], "grad": {leaf: norm of the first step's
    gradient}, "change": {leaf: norm of the parameters' change after the
    last step}, "size": {leaf: its number of elements}}; the table of field
    c is the leaf "table.C<c>".
    """
    fields = [f"C{c + 1}" for c in range(config["num_cat"])]
    dense_keys = [f"I{i + 1}" for i in range(config["num_dense"])]
    if half_batch:
        half = len(batches[0]["label"]) // 2
        batches = [{k: v[:half] for k, v in b.items()} for b in batches]
    ids, idx = index_batches(batches, fields)
    prepare, step, summarise = _jitted(json.dumps(config, sort_keys=True),
                                       mode)
    salts = np.asarray([field_salt(f) for f in fields], np.uint32)
    params0, adam, rows0, accum = prepare(ids, salts, np.int32(seed))
    params, rows, losses, first = params0, rows0, [], None
    for t, (b, ix) in enumerate(zip(batches, idx), start=1):
        dense = np.concatenate([b[k] for k in dense_keys], axis=1)
        params, adam, rows, accum, loss, g_dense, g_rows = step(
            params, adam, rows, accum, ix, dense, b["label"], np.float32(t))
        losses.append(loss)
        if t == 1:
            first = (g_dense, g_rows)
    norms = jax.device_get(summarise(params0, rows0, params, rows, *first))
    out = {"loss": [float(x) for x in losses],
           "size": {k: int(v.size) for k, v in leaf_names(params0).items()}}
    out["size"].update({f"table.{f}": int(rows0[c].size)
                        for c, f in enumerate(fields)})
    for kind in ("grad", "change"):
        out[kind] = {k: float(v) for k, v in norms[kind].items()}
        out[kind].update({f"table.{f}": float(norms[kind + "_tables"][c])
                          for c, f in enumerate(fields)})
    return out
