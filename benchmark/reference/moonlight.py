"""Plain reference of the `moonlight` family's training steps.

Straightforward `jax.numpy` in float32 with every product at `highest`
precision, written from the layer equations (docs/latent_stack.md) and
importing nothing of `deeprec_tpu` nor of another family's module: for
layer i on x [T, d], with H heads, d_n / d_r the parts of a query or key
without and with position, d_v a value's width and r the latent's:

    n = rms(x; w_in)
    q = n Wq -> q_n [H, T, d_n], q_r = rope(q[..., d_n:]) [H, T, d_r]
    (c, k_r) = n Wkva;  c = rms(c; w_kv);  k_r = rope(k_r) [T, d_r], ONE head
    (k_n, v) = c Wkvb -> [H, T, d_n], [H, T, d_v]
    s_h = (q_n,h k_n,h^T + q_r,h k_r^T) / sqrt(d_n + d_r), causal
    h = x + concat_h(softmax(s_h) v_h) Wo;  m = rms(h; w_post)
    i < first_k_dense_replace:  out = h + (silu(m Wg) * (m Wu)) Wd
    otherwise:  s = sigmoid(m Wr);  chosen = top-k of (s + b)
                w = s[chosen] / (sum of the chosen s + 1e-20) * scale
                out = h + sum over the chosen experts j held HERE of
                          w_j E_j(m)  +  S(m)      (S: the shared experts)

the scores as TWO products added, by blocks of queries over all the keys
(no `[heads, T, T]` array exists); the experts as a plain loop over the ones
held here, with masks; the same share of the deployment (the router scores
all its outputs, the held experts' part and the shared experts are added
up, what the absent experts would add is left out); rows made from their
ids by the configuration's stated initializer; autodiff; Adagrad on the
rows and Adam on the dense parameters by hand; and after every step, in
every expert layer, the rule that owns the selection bias `b` (it has no
gradient): `b_j += gamma sign(mean(c) - c_j)`, `c_j` the step's choices that
fell on output `j` of ALL the router's outputs.

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
`no_routed_scale=True`, the chosen experts' weights left at their
renormalised scores, without the routed scaling factor.

The few helpers that are no part of this family's mathematics (a product in
a mode's arithmetic, the rows' initializer, a tree's leaf names) are
carried here as the other token references carry them, until a `benchmark`
PR lifts them into one place.
"""
from __future__ import annotations

import functools
import json
import math
import zlib
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CONTROLS = {"control_fp8": {"mode": "fp8"},
            "fault_half_positions": {"half_positions": True},
            "fault_no_routed_scale": {"no_routed_scale": True},
            "witness_bf16": {"mode": "bf16"}}
_QUERY_BLOCK = 256     # queries a block of the attention
_LOSS_BLOCK = 1024     # positions a block of the loss
_LAYER_KEYS = 14       # keys a layer's leaves are drawn from


# ------------------------------------------------------------ initializers


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


def _salt(field: str) -> int:
    return zlib.crc32(field.encode()) & 0x7FFFFFFF


def row_init(config: Dict, fields: Sequence[str]):
    """ids [T, n] -> rows [T, n, D] for the tables of `fields`, jitted."""
    salts = [_salt(f) for f in fields]
    return jax.jit(lambda ids: jnp.stack(
        [init_rows(ids[t], s, config) for t, s in enumerate(salts)]))


def is_dense(config: Dict, i: int) -> bool:
    """Whether the i-th layer held here is one of the leading layers whose
    feed-forward is dense."""
    return (config["deployment"].get("first_layer_held", 0) + i
            < config["first_k_dense_replace"])


def init_dense(config: Dict, seed: int) -> Dict:
    """Dense parameters from the seed: the key split once a layer (and once
    more for the head), then in 14 for a layer's leaves."""
    d, std = config["hidden_size"], config["embedding_init"]["stddev"]
    L, H = config["num_hidden_layers"], config["num_attention_heads"]
    dn, dr, dv, r = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"], config["kv_lora_rank"])
    E, held = config["deployment"]["router_outputs"], config["n_routed_experts"]
    f, fd = config["moe_intermediate_size"], config["intermediate_size"]
    fs = config["n_shared_experts"] * f
    normal = lambda k, shape: std * jax.random.normal(k, shape)  # noqa: E731
    keys = jax.random.split(jax.random.PRNGKey(seed), L + 1)
    layers = []
    for i in range(L):
        ks = jax.random.split(keys[i], _LAYER_KEYS)
        layer = {"in_norm": jnp.ones((d,)),
                 "mixer": {"wq": normal(ks[0], (d, H * (dn + dr))),
                           "wkva": normal(ks[1], (d, r + dr)),
                           "kv_norm": jnp.ones((r,)),
                           "wkvb": normal(ks[2], (r, H * (dn + dv))),
                           "wo": normal(ks[5], (H * dv, d))},
                 "post_norm": jnp.ones((d,))}
        if is_dense(config, i):
            layer["mlp"] = {"wg": normal(ks[7], (d, fd)),
                            "wu": normal(ks[8], (d, fd)),
                            "wd": normal(ks[9], (fd, d))}
        else:
            layer["moe"] = {
                "router": normal(ks[6], (d, E)), "bias": jnp.zeros((E,)),
                "experts": {"wg": normal(ks[7], (held, d, f)),
                            "wu": normal(ks[8], (held, d, f)),
                            "wd": normal(ks[9], (held, f, d))},
                "shared": {"wg": normal(ks[10], (d, fs)),
                           "wu": normal(ks[11], (d, fs)),
                           "wd": normal(ks[12], (fs, d))}}
        layers.append(layer)
    return {"layers": layers, "final_norm": jnp.ones((d,)),
            "head": normal(keys[-1], (d, config["vocab_size"]))}


def leaf_names(tree, prefix: str = "") -> Dict[str, jnp.ndarray]:
    """{"layers.0.mixer.wq": array, ...}: the names the comparison speaks
    in, a leaf's path joined by dots."""
    if isinstance(tree, dict):
        items = tree.items()
    elif isinstance(tree, (list, tuple)):
        items = enumerate(tree)
    else:
        return {prefix[:-1]: tree}
    out = {}
    for k, v in items:
        out.update(leaf_names(v, f"{prefix}{k}."))
    return out


# ----------------------------------------------------------------- products


def _round_to(x, dtype, top: float):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    """A product's operand in float8: the value rounded to e4m3 and its
    gradient to e5m2 (the usual float8 training recipe)."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, ct):
    return (_round_to(ct, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _ein(spec: str, a, b, mode: str):
    """One product in the mode's arithmetic."""
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _down(x, mode: str):
    """What the configuration keeps in float32, one step down in the
    control."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if mode == "fp8" else x


def rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(x, p: Dict, mode: str):
    h = jax.nn.silu(_ein("td,df->tf", x, p["wg"], mode)) \
        * _ein("td,df->tf", x, p["wu"], mode)
    return _ein("tf,fd->td", h, p["wd"], mode)


# ----------------------------------------------------------------- attention


def rotary(x, theta: float):
    """x [..., T, D]: rotary on all D dims, half-split form."""
    T, D = x.shape[-2], x.shape[-1]
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def attention(p: Dict, n, config: Dict, mode: str):
    """n [T, d] (normed) -> [T, d]."""
    T = n.shape[0]
    H, theta = config["num_attention_heads"], float(config["rope_theta"])
    dn, dr, dv, r = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"], config["kv_lora_rank"])
    q = jnp.moveaxis(_ein("td,de->te", n, p["wq"], mode).reshape(
        T, H, dn + dr), 1, 0)                                # [H, T, dn + dr]
    q_n, q_r = q[..., :dn], rotary(q[..., dn:], theta)
    ckr = _ein("td,de->te", n, p["wkva"], mode)              # [T, r + dr]
    c = rms(ckr[:, :r], p["kv_norm"], config["rms_norm_eps"])
    k_r = rotary(ckr[:, r:], theta)                          # [T, dr]: 1 head
    kv = jnp.moveaxis(_ein("tr,re->te", c, p["wkvb"], mode).reshape(
        T, H, dn + dv), 1, 0)
    k_n, v = kv[..., :dn], kv[..., dn:]
    bq = math.gcd(T, _QUERY_BLOCK)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        qn, qr, start = args                                 # [H, bq, .]
        s = (_ein("hqd,hkd->hqk", qn, k_n, mode)
             + _ein("hqd,kd->hqk", qr, k_r, mode)) * ((dn + dr) ** -0.5)
        seen = (start + jnp.arange(bq))[:, None] >= kpos[None, :]
        s = jnp.where(seen[None], s, -1e30)
        return _ein("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v, mode)

    blocks = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape(H, T // bq, bq, t.shape[-1]), 1, 0)
    o = jax.lax.map(block, (blocks(q_n), blocks(q_r),
                            jnp.arange(T // bq) * bq))       # [nb, H, bq, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1).reshape(H, T, dv), 0, 1)
    return _ein("te,ed->td", o.reshape(T, H * dv), p["wo"], mode)


# --------------------------------------------------------------- the experts


def route(p: Dict, m, config: Dict, mode: str, no_routed_scale: bool = False):
    """(weights, experts) [T, top_k] and the [E] loads of ALL the router's
    outputs: sigmoid scores, the top of score + bias chosen, each weighing
    by its score alone."""
    if (config["scoring_func"], config["topk_method"], config["n_group"],
            config["topk_group"]) != ("sigmoid", "noaux_tc", 1, 1):
        raise ValueError("the reference routes by sigmoid scores with a "
                         "selection bias over one group")
    s = jax.nn.sigmoid(_down(jnp.dot(_down(m, mode), _down(p["router"], mode),
                                     precision=HIGHEST), mode))
    _, e = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"]),
                         config["num_experts_per_tok"])
    w = jnp.take_along_axis(s, e, axis=-1)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    if not no_routed_scale:
        w = w * config["routed_scaling_factor"]
    load = jnp.sum(e.reshape(-1, 1) == jnp.arange(s.shape[1])[None, :],
                   axis=0, dtype=jnp.int32)
    return w, e, load


def expert_block(p: Dict, m, config: Dict, mode: str,
                 no_routed_scale: bool = False):
    """m [T, d] -> ([T, d], loads [E]): the held experts' part and the
    shared experts."""
    first = config["deployment"]["first_expert_held"]
    w, e, load = route(p, m, config, mode, no_routed_scale)

    def one(y, xs):
        ws, index = xs
        share = jnp.sum(jnp.where(e == first + index, w, 0.0), axis=-1)
        part = jax.checkpoint(
            lambda ws, s: s[:, None] * swiglu(m, ws, mode))
        return y + part(ws, share), None

    held = p["experts"]["wg"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (p["experts"], jnp.arange(held)))
    return y + swiglu(m, p["shared"], mode), load


def layer(p: Dict, x, config: Dict, mode: str, no_routed_scale: bool = False):
    """(out [T, d], the router's loads [E], or None from a dense layer)."""
    eps = config["rms_norm_eps"]
    h = x + attention(p["mixer"], rms(x, p["in_norm"], eps), config, mode)
    m = rms(h, p["post_norm"], eps)
    if "mlp" in p:
        return h + swiglu(m, p["mlp"], mode), None
    y, load = expert_block(p["moe"], m, config, mode, no_routed_scale)
    return h + y, load


# ------------------------------------------------------------------ the loss


def loss_fn(params: Dict, rows, idx, labels, config: Dict, mode: str,
            half_positions: bool, no_routed_scale: bool):
    """rows [n, d] the table's rows, idx [B, T] each position's row, labels
    [B, T] the token that follows: (mean cross-entropy over the positions,
    the routers' loads [expert layers, E] over the whole batch)."""

    def sequence(ix, lab):
        x, loads = rows[ix], []                                  # [T, d]
        for p in params["layers"]:
            x, load = jax.checkpoint(functools.partial(
                layer, config=config, mode=mode,
                no_routed_scale=no_routed_scale))(p, x)
            if load is not None:
                loads.append(load)
        h = rms(x, params["final_norm"], config["rms_norm_eps"])
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
        return total, jnp.sum(keep), jnp.stack(loads)

    totals = [sequence(idx[b], labels[b]) for b in range(idx.shape[0])]
    return (sum(t for t, _, _ in totals) / sum(n for _, n, _ in totals),
            sum(load for _, _, load in totals))


# -------------------------------------------------------------------- steps


def bias_rule(params: Dict, loads, gamma: float) -> Dict:
    """`b_j += gamma sign(mean(c) - c_j)` in every expert layer; loads
    [expert layers, E] in the order of the layers that have experts."""
    c = loads.astype(jnp.float32)
    move = gamma * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)
    layers, j = [], 0
    for p in params["layers"]:
        if "moe" in p:
            p = {**p, "moe": {**p["moe"], "bias": p["moe"]["bias"] + move[j]}}
            j += 1
        layers.append(p)
    return {**params, "layers": layers}


def _train_step(params, m, v, rows, accum, idx, labels, t, *, config: Dict,
                **how):
    (loss, loads), (g, g_rows) = jax.value_and_grad(
        loss_fn, argnums=(0, 1), has_aux=True)(
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
    params = bias_rule(params, loads, config["bias_update_rate"])
    norms = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))),
                         (g, g_rows))
    return params, m, v, rows, accum, loss, norms, loads


@functools.lru_cache(maxsize=None)
def _jitted(config_json: str, mode: str, half_positions: bool,
            no_routed_scale: bool):
    return jax.jit(functools.partial(
        _train_step, config=json.loads(config_json), mode=mode,
        half_positions=half_positions, no_routed_scale=no_routed_scale),
        donate_argnums=(0, 1, 2, 3, 4))


@jax.jit
def _change_norms(new, old):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                        new, old)


def run(config: Dict, batches: Sequence[Dict[str, np.ndarray]], seed: int, *,
        mode: str = "highest", half_positions: bool = False,
        no_routed_scale: bool = False) -> Dict:
    """Follow the first len(batches) training steps from the seed: {"loss",
    "grad", "change", "size"} by leaf; the table is the leaf "table.tok".
    Beside them, for whoever holds the rule to the program's: "bias" {leaf:
    the selection bias after the last step} and "loads" (a step's
    [expert layers, E] counts)."""
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
                   no_routed_scale)
    losses, loads, first = [], [], None
    for t, b in enumerate(batches, start=1):
        idx = np.searchsorted(ids, b["tok"]).astype(np.int32)
        params, m, v, rows, accum, loss, norms, load = step(
            params, m, v, rows, accum, idx, b["label"].astype(np.int32),
            np.float32(t))
        losses.append(float(loss))
        loads.append(np.asarray(load).tolist())
        if first is None:
            first = jax.device_get(norms)
    # the start is made anew from the seed (it was not kept over the steps:
    # a second copy of the weights beside the moments and the gradient is
    # memory the step's blocks of scores want)
    del m, v, accum
    bias = {k: np.asarray(x).tolist() for k, x in leaf_names(params).items()
            if k.endswith(".moe.bias")}
    change = jax.device_get(_change_norms(
        (params, rows), (make_params(np.int32(seed)), rows0)))
    out = {"loss": losses,
           "grad": {k: float(x) for k, x in leaf_names(first[0]).items()},
           "change": {k: float(x) for k, x in leaf_names(change[0]).items()},
           "size": sizes, "bias": bias, "loads": loads}
    out["grad"]["table.tok"] = float(first[1])
    out["change"]["table.tok"] = float(change[1])
    out["size"]["table.tok"] = int(len(ids) * rows0.shape[1])
    return out
