"""Plain reference of the `smallthinker` family's training steps.

Straightforward `jax.numpy` in float32 with every product at `highest`
precision, written from the layer equations (docs/window_stack.md) and
importing nothing of `deeprec_tpu`: for layer i

    n = rms(x; w_in);  r = n W_router;  (l, e) = top6(r);  w = softmax(l)
    q, k, v = n Wq, n Wk, n Wv;  rotary on all of a head where rope_layout[i]
    mask(t, s) = s <= t and (t - s < window where sliding_window_layout[i])
    h = x + softmax(q k^T / sqrt(D) + mask) v Wo;  m = rms(h; w_post)
    out = h + sum over the chosen experts j held HERE of
              w_j (relu(m Wg_j) * (m Wu_j)) Wd_j

full softmax attention by blocks of queries over all the keys under the
layer's mask (no `[heads, T, T]` array exists); the router on the mixer's
own normed input, BEFORE attention; the experts as a plain loop over the
ones held here, with masks; the same share of the deployment (the router
scores all its outputs, the held experts' part is added up, what the absent
experts would add is left out); rows made from their ids by the
configuration's stated initializer; autodiff; Adagrad on the rows and Adam
on the dense parameters by hand. What is no part of this family's
mathematics (a product in a mode's arithmetic, the rows' initializer, a
tree's leaf names) is the benchmark's other token reference's
(reference/qwen3next.py), which imports nothing of `deeprec_tpu` either.

`mode` selects the arithmetic:
  "highest"  the reference itself
  "fp8"      the control, the nearest precision below the one the
             configuration states: product operands rounded to float8_e4m3
             and their gradients to float8_e5m2 (each scaled per tensor to
             its largest magnitude), and the router, which the
             configuration keeps in float32, in bfloat16
  "bf16"     the second witness: the configuration's own arithmetic
             (bfloat16 operands, float32 accumulation) in this plain code
Two planted faults: `half_positions=True`, the second half of every
sequence's positions left out of the loss, the mean taken over the rest;
`no_window=True`, the window left out of the window layers (every layer
sees the whole causal past), which only a sequence longer than the window
can show.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import qwen3next as _plain
from benchmark.reference.qwen3next import (HIGHEST, _down, _ein, _salt,
                                           init_rows, leaf_names, rms)

CONTROLS = {"control_fp8": {"mode": "fp8"},
            "fault_half_positions": {"half_positions": True},
            "fault_no_window": {"no_window": True},
            "witness_bf16": {"mode": "bf16"}}
_QUERY_BLOCK = 256     # queries a block of the attention
_LOSS_BLOCK = 1024     # positions a block of the loss
_LAYER_KEYS = 14       # keys a layer's leaves are drawn from


def row_init(config: Dict, fields: Sequence[str]):
    """ids [T, n] -> rows [T, n, D] for the tables of `fields`, jitted."""
    return _plain.row_init(config, fields)


def layer_kind(config: Dict, i: int):
    """(window, rotary) of the i-th layer held here."""
    j = config["deployment"].get("first_layer_held", 0) + i
    return (bool(config["sliding_window_layout"][j]),
            bool(config["rope_layout"][j]))


def init_dense(config: Dict, seed: int) -> Dict:
    """Dense parameters from the seed: the key split once a layer (and once
    more for the head), then in 14 for a layer's leaves."""
    d, std = config["hidden_size"], config["embedding_init"]["stddev"]
    L = config["num_hidden_layers"]
    H, Hkv, D = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    E, held = (config["deployment"]["router_outputs"],
               config["moe_num_primary_experts"])
    f = config["moe_ffn_hidden_size"]
    normal = lambda k, shape: std * jax.random.normal(k, shape)  # noqa: E731
    keys = jax.random.split(jax.random.PRNGKey(seed), L + 1)
    layers = []
    for i in range(L):
        ks = jax.random.split(keys[i], _LAYER_KEYS)
        layers.append({
            "in_norm": jnp.ones((d,)),
            "mixer": {"wq": normal(ks[0], (d, H * D)),
                      "wk": normal(ks[1], (d, Hkv * D)),
                      "wv": normal(ks[2], (d, Hkv * D)),
                      "wo": normal(ks[5], (H * D, d))},
            "post_norm": jnp.ones((d,)),
            "moe": {"router": normal(ks[6], (d, E)),
                    "experts": {"wg": normal(ks[7], (held, d, f)),
                                "wu": normal(ks[8], (held, d, f)),
                                "wd": normal(ks[9], (held, f, d))}}})
    return {"layers": layers, "final_norm": jnp.ones((d,)),
            "head": normal(keys[-1], (d, config["vocab_size"]))}


# ----------------------------------------------------------------- attention


def rotary(x, theta: float):
    """x [H, T, D]: rotary on the whole head, half-split form."""
    T, D = x.shape[1], x.shape[2]
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def attention(p: Dict, n, config: Dict, mode: str, window: bool, rope: bool):
    """n [T, d] (normed) -> [T, d]."""
    T = n.shape[0]
    H, Hkv, D = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    W = config["sliding_window_size"]
    q, k, v = (jnp.moveaxis(_ein("td,de->te", n, p[name], mode).reshape(
        T, heads, D), 1, 0) for name, heads in
        (("wq", H), ("wk", Hkv), ("wv", Hkv)))
    if rope:
        q, k = (rotary(t, float(config["rope_theta"])) for t in (q, k))
    # query head h reads key/value head h // (H / Hkv)
    k, v = jnp.repeat(k, H // Hkv, axis=0), jnp.repeat(v, H // Hkv, axis=0)
    bq = math.gcd(T, _QUERY_BLOCK)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        qb, start = args                                   # [H, bq, D]
        s = _ein("hqd,hkd->hqk", qb, k, mode) * (D ** -0.5)
        back = (start + jnp.arange(bq))[:, None] - kpos[None, :]
        seen = (back >= 0) & (back < W) if window else back >= 0
        s = jnp.where(seen[None], s, -1e30)
        return _ein("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v, mode)

    qb = jnp.moveaxis(q.reshape(H, T // bq, bq, D), 1, 0)
    o = jax.lax.map(block, (qb, jnp.arange(T // bq) * bq))   # [nb, H, bq, D]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1).reshape(H, T, D), 0, 1)
    return _ein("te,ed->td", o.reshape(T, H * D), p["wo"], mode)


# --------------------------------------------------------------- the experts


def route(p: Dict, n, config: Dict, mode: str):
    """(weights, experts) [T, top_k]: the top logits of n W_router and the
    softmax over them (= the softmax over all the outputs, its top values
    renormalised)."""
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("the reference routes by a renormalised softmax")
    r = _down(jnp.dot(_down(n, mode), _down(p["router"], mode),
                      precision=HIGHEST), mode)
    top, e = jax.lax.top_k(r, config["moe_num_active_primary_experts"])
    return jax.nn.softmax(top, axis=-1), e


def expert_block(p: Dict, m, w, e, config: Dict, mode: str):
    """m [T, d], routed as (w, e) -> [T, d]: the held experts' part."""
    first = config["deployment"]["first_expert_held"]

    def one(y, xs):
        ws, index = xs
        share = jnp.sum(jnp.where(e == first + index, w, 0.0), axis=-1)

        @jax.checkpoint
        def part(ws, share):
            hidden = jax.nn.relu(_ein("td,df->tf", m, ws["wg"], mode)) \
                * _ein("td,df->tf", m, ws["wu"], mode)
            return share[:, None] * _ein("tf,fd->td", hidden, ws["wd"], mode)

        return y + part(ws, share), None

    held = p["experts"]["wg"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (p["experts"], jnp.arange(held)))
    return y


def layer(p: Dict, x, config: Dict, mode: str, window: bool, rope: bool):
    eps = config["rms_norm_eps"]
    n = rms(x, p["in_norm"], eps, False)
    w, e = route(p["moe"], n, config, mode)          # before attention
    h = x + attention(p["mixer"], n, config, mode, window, rope)
    return h + expert_block(p["moe"], rms(h, p["post_norm"], eps, False),
                            w, e, config, mode)


# ------------------------------------------------------------------ the loss


def loss_fn(params: Dict, rows, idx, labels, config: Dict, mode: str,
            half_positions: bool, no_window: bool):
    """rows [n, d] the table's rows, idx [B, T] each position's row, labels
    [B, T] the token that follows: mean cross-entropy over the positions."""

    def sequence(ix, lab):
        x = rows[ix]                                            # [T, d]
        for i, p in enumerate(params["layers"]):
            window, rope = layer_kind(config, i)
            x = jax.checkpoint(functools.partial(
                layer, config=config, mode=mode,
                window=window and not no_window, rope=rope))(p, x)
        h = rms(x, params["final_norm"], config["rms_norm_eps"], False)
        T = h.shape[0]
        keep = jnp.arange(T) < (T // 2 if half_positions else T)
        blk = math.gcd(T, _LOSS_BLOCK)

        @jax.checkpoint
        def block(total, xs):
            hx, yx, kx = xs
            logits = _ein("td,dv->tv", hx, params["head"], mode)
            nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, yx[:, None], axis=-1)[:, 0]
            return total + jnp.sum(jnp.where(kx, nll, 0.0)), None

        total, _ = jax.lax.scan(
            block, jnp.zeros((), jnp.float32),
            (h.reshape(T // blk, blk, -1), lab.reshape(T // blk, blk),
             keep.reshape(T // blk, blk)))
        return total, jnp.sum(keep)

    totals = [sequence(idx[b], labels[b]) for b in range(idx.shape[0])]
    return sum(t for t, _ in totals) / sum(n for _, n in totals)


# -------------------------------------------------------------------- steps


def _train_step(params, m, v, rows, accum, idx, labels, t, *, config: Dict,
                **how):
    loss, (g, g_rows) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
        params, rows, idx, labels, config, **how)
    so, do = config["sparse_optimizer"], config["dense_optimizer"]
    accum = accum + g_rows * g_rows
    rows = rows - so["lr"] * g_rows * jax.lax.rsqrt(jnp.maximum(accum, 1e-30))
    b1, b2 = do["b1"], do["b2"]
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - do["lr"] * (a / c1) / (jnp.sqrt(b / c2)
                                                   + do["eps"]),
        params, m, v)
    norms = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))),
                         (g, g_rows))
    return params, m, v, rows, accum, loss, norms


@functools.lru_cache(maxsize=None)
def _jitted(config_json: str, mode: str, half_positions: bool,
            no_window: bool):
    return jax.jit(functools.partial(
        _train_step, config=json.loads(config_json), mode=mode,
        half_positions=half_positions, no_window=no_window),
        donate_argnums=(0, 1, 2, 3, 4))


@jax.jit
def _change_norms(new, old):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                        new, old)


def run(config: Dict, batches: Sequence[Dict[str, np.ndarray]], seed: int, *,
        mode: str = "highest", half_positions: bool = False,
        no_window: bool = False) -> Dict:
    """Follow the first len(batches) training steps from the seed: {"loss",
    "grad", "change", "size"} by leaf; the table is the leaf "table.tok"."""
    ids = np.unique(np.concatenate([b["tok"].reshape(-1) for b in batches]))
    # as many rows as positions, whatever the ids, so that every seed
    # compiles the same programs; the rows past the distinct ids repeat the
    # first id and nothing points at them
    n = sum(b["tok"].size for b in batches)
    padded = np.concatenate([ids, np.full(n - len(ids), ids[0], ids.dtype)])
    make_rows = jax.jit(lambda i: init_rows(i, _salt("tok"), config))
    rows0 = make_rows(jnp.asarray(padded, jnp.int32))
    make_params = jax.jit(lambda s: init_dense(config, s))
    params = make_params(np.int32(seed))
    sizes = {k: int(x.size) for k, x in leaf_names(params).items()}
    so = config["sparse_optimizer"]
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    rows = jnp.copy(rows0)
    m, v = zeros(params), zeros(params)
    accum = jnp.full_like(rows0, so["initial_accumulator_value"])
    step = _jitted(json.dumps(config, sort_keys=True), mode, half_positions,
                   no_window)
    losses, first = [], None
    for t, b in enumerate(batches, start=1):
        idx = np.searchsorted(ids, b["tok"]).astype(np.int32)
        params, m, v, rows, accum, loss, norms = step(
            params, m, v, rows, accum, idx, b["label"].astype(np.int32),
            np.float32(t))
        losses.append(float(loss))
        if first is None:
            first = jax.device_get(norms)
    # the start is made anew from the seed (it was not kept over the steps:
    # a second copy of the weights beside the moments and the gradient is
    # memory the step's blocks of scores want)
    del m, v, accum
    change = jax.device_get(_change_norms(
        (params, rows), (make_params(np.int32(seed)), rows0)))
    out = {"loss": losses,
           "grad": {k: float(x) for k, x in leaf_names(first[0]).items()},
           "change": {k: float(x) for k, x in leaf_names(change[0]).items()},
           "size": sizes}
    out["grad"]["table.tok"] = float(first[1])
    out["change"]["table.tok"] = float(change[1])
    out["size"]["table.tok"] = int(len(ids) * rows0.shape[1])
    return out
