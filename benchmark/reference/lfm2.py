"""Plain reference of the `lfm2` family's training steps.

Straightforward `jax.numpy` in float32 with every product at `highest`
precision, written from the layer equations (docs/conv_stack.md) and
importing nothing of `deeprec_tpu` nor of another family's module: for
layer i on x [T, d], its mixer the i-th of `layer_types`:

    n = rms(x; w_in)
    conv:  [B, C, x~] = n W_in;  u = B x~
           v_t = sum_{j < L} w_conv[j] u_{t - (L - 1) + j}   (0 before t = 0)
           out = (C v) W_out
    attn:  q = rope(rms_h(n Wq; w_q)), k = rope(rms_h(n Wk; w_k)), v = n Wv
           out = concat_h(softmax(q_h k_g^T / sqrt(D), causal) v_g) Wo
                 (query head h reads key/value head g = h // (H / Hkv))
    h = x + out;  m = rms(h; w_post)
    i < num_dense_layers:  y = h + (silu(m Wg) * (m Wu)) Wd
    otherwise:  s = sigmoid(m Wr);  chosen = top-k of (s + b)
                w = s[chosen] / (sum of the chosen s + eps_r) * scale
                y = h + sum over the chosen experts j held HERE of
                        w_j (silu(m Wg_j) * (m Wu_j)) Wd_j

the convolution as a plain sum of shifted copies, one sequence at a time;
the scores by blocks of queries over all the keys (no `[heads, T, T]`
array exists); the experts as a plain loop over the ones held here, with
masks; the same share of the deployment as the program (the router scores
all its outputs, the held experts' part is added up, what the absent
experts would add is left out; the mixers whole); rows made from their ids
by the configuration's stated initializer; autodiff; Adagrad on the rows
and Adam on the dense parameters by hand; and after every step, in every
expert layer, the rule that owns the selection bias `b` (it has no
gradient): `b_j += gamma sign(mean(c) - c_j)`, `c_j` the step's choices
that fell on output `j` of ALL the router's outputs.

`mode` selects the arithmetic:
  "highest"  the reference itself
  "fp8"      the control, the nearest precision below the one the
             configuration states: product operands rounded to float8_e4m3
             and their gradients to float8_e5m2 (each scaled per tensor to
             its largest magnitude), and the router, which the
             configuration keeps in float32, in bfloat16
  "bf16"     the second witness: the configuration's own arithmetic
             (bfloat16 operands, float32 accumulation) in this plain code
Three planted faults: `half_positions=True`, the second half of every
sequence's positions left out of the loss, the mean taken over the rest;
`bf16_residual=True`, the residual stream rounded to bfloat16 after every
layer (the configuration keeps it in float32), read both on the exact
products and in the configuration's own arithmetic ("bf16"), where a
program that rounded its stream would read; `no_out_gate=True`, the short
convolution's output gate `C` left out (`out = v W_out`).

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
            "fault_bf16_residual": {"bf16_residual": True},
            "fault_bf16_residual_bf16_ops": {"mode": "bf16",
                                             "bf16_residual": True},
            "fault_no_out_gate": {"no_out_gate": True},
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


def head_dim(config: Dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def init_dense(config: Dict, seed: int) -> Dict:
    """Dense parameters from the seed: the key split once a layer (and once
    more for the head), then in 14 for a layer's leaves."""
    d, std = config["hidden_size"], config["embedding_init"]["stddev"]
    L, K = config["num_hidden_layers"], config["conv_L_cache"]
    H, Hkv, D = (config["num_attention_heads"],
                 config["num_key_value_heads"], head_dim(config))
    E, held = config["deployment"]["router_outputs"], config["num_experts"]
    f, fd = config["moe_intermediate_size"], config["intermediate_size"]
    normal = lambda k, shape: std * jax.random.normal(k, shape)  # noqa: E731
    lim = 1.0 / math.sqrt(K)
    keys = jax.random.split(jax.random.PRNGKey(seed), L + 1)
    layers = []
    for i, kind in enumerate(config["layer_types"]):
        ks = jax.random.split(keys[i], _LAYER_KEYS)
        if kind == "conv":
            mixer = {"w_in": normal(ks[0], (d, 3 * d)),
                     "conv": jax.random.uniform(ks[2], (K, d), jnp.float32,
                                                -lim, lim),
                     "w_out": normal(ks[5], (d, d))}
        else:
            mixer = {"wq": normal(ks[0], (d, H * D)),
                     "wk": normal(ks[1], (d, Hkv * D)),
                     "wv": normal(ks[2], (d, Hkv * D)),
                     "q_norm": jnp.ones((D,)), "k_norm": jnp.ones((D,)),
                     "wo": normal(ks[5], (H * D, d))}
        layer = {"in_norm": jnp.ones((d,)), "mixer": mixer,
                 "post_norm": jnp.ones((d,))}
        if i < config["num_dense_layers"]:
            layer["mlp"] = {"wg": normal(ks[7], (d, fd)),
                            "wu": normal(ks[8], (d, fd)),
                            "wd": normal(ks[9], (fd, d))}
        else:
            layer["moe"] = {
                "router": normal(ks[6], (d, E)),
                "experts": {"wg": normal(ks[7], (held, d, f)),
                            "wu": normal(ks[8], (held, d, f)),
                            "wd": normal(ks[9], (held, f, d))},
                "bias": jnp.zeros((E,))}
        layers.append(layer)
    return {"layers": layers, "final_norm": jnp.ones((d,)),
            "head": normal(keys[-1], (d, config["vocab_size"]))}


def leaf_names(tree, prefix: str = "") -> Dict[str, jnp.ndarray]:
    """{"layers.0.mixer.w_in": array, ...}: the names the comparison speaks
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


def _bf16(x):
    """x rounded to bfloat16 and kept in float32, by an explicit
    `reduce_precision`: a pair of converts is one the TPU compiler may drop
    as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _round_to(x, mantissa_bits: int, top: float, min_normal: float):
    """x scaled per tensor so that its largest magnitude is `top`, rounded
    to the float8 format that `mantissa_bits`, `top` and `min_normal`
    describe, and scaled back: the normal range by `reduce_precision` with
    5 exponent bits (as wide as e5m2's and wider than e4m3fn's, whose top
    the scale stays under), the format's subnormals (which
    `reduce_precision` flushes to 0) by rounding to their fixed step."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    s = x / scale
    step = min_normal * 2.0 ** -mantissa_bits
    r = jnp.where(jnp.abs(s) < min_normal, jnp.round(s / step) * step,
                  jax.lax.reduce_precision(s, exponent_bits=5,
                                           mantissa_bits=mantissa_bits))
    return r * scale


@jax.custom_vjp
def _fp8(x):
    """A product's operand in float8: the value rounded to e4m3 (e4m3fn:
    top 448, smallest normal 2^-6) and its gradient to e5m2 (top 57344,
    2^-14), the usual float8 training recipe."""
    return _round_to(x, 3, 448.0, 2.0 ** -6)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, ct):
    return (_round_to(ct, 2, 57344.0, 2.0 ** -14),)


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
    return _bf16(x) if mode == "fp8" else x


def rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(x, p: Dict, mode: str):
    h = jax.nn.silu(_ein("td,df->tf", x, p["wg"], mode)) \
        * _ein("td,df->tf", x, p["wu"], mode)
    return _ein("tf,fd->td", h, p["wd"], mode)


# ------------------------------------------------------- the short convolution


def short_conv(p: Dict, n, config: Dict, mode: str,
               no_out_gate: bool = False):
    """n [T, d] (normed) -> [T, d]: in-projection split into B, C and x, the
    input gate, the causal taps (zero before the sequence's start), the
    output gate, the out-projection."""
    T, d, K = n.shape[0], config["hidden_size"], config["conv_L_cache"]
    bcx = _ein("td,de->te", n, p["w_in"], mode)
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    up = jnp.pad(b * x, ((K - 1, 0), (0, 0)))
    v = sum(up[j:j + T] * p["conv"][j] for j in range(K))
    return _ein("te,ed->td", v if no_out_gate else c * v, p["w_out"], mode)


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
    """n [T, d] (normed) -> [T, d]: causal, grouped queries, queries and
    keys normed per head and turned by rotary over the whole head."""
    T = n.shape[0]
    H, Hkv, D = (config["num_attention_heads"],
                 config["num_key_value_heads"], head_dim(config))
    eps, theta = config["norm_eps"], float(config["rope_theta"])
    q, k, v = (jnp.moveaxis(_ein("td,de->te", n, p[w], mode).reshape(
        T, h, D), 1, 0) for w, h in (("wq", H), ("wk", Hkv), ("wv", Hkv)))
    q = rotary(rms(q, p["q_norm"], eps), theta)
    k = rotary(rms(k, p["k_norm"], eps), theta)
    k, v = jnp.repeat(k, H // Hkv, axis=0), jnp.repeat(v, H // Hkv, axis=0)
    bq = math.gcd(T, _QUERY_BLOCK)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        qb, start = args                                     # [H, bq, D]
        s = _ein("hqd,hkd->hqk", qb, k, mode) * (D ** -0.5)
        seen = (start + jnp.arange(bq))[:, None] >= kpos[None, :]
        s = jnp.where(seen[None], s, -1e30)
        return _ein("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v, mode)

    qs = jnp.moveaxis(q.reshape(H, T // bq, bq, D), 1, 0)
    o = jax.lax.map(block, (qs, jnp.arange(T // bq) * bq))   # [nb, H, bq, D]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1).reshape(H, T, D), 0, 1)
    return _ein("te,ed->td", o.reshape(T, H * D), p["wo"], mode)


# --------------------------------------------------------------- the experts


def route(p: Dict, m, config: Dict, mode: str):
    """(weights, experts) [T, top_k] and the [E] loads of ALL the router's
    outputs: sigmoid scores, the top of score + bias chosen, each weighing
    by its score alone, renormalised with the family's epsilon."""
    s = jax.nn.sigmoid(_down(jnp.dot(_down(m, mode), _down(p["router"], mode),
                                     precision=HIGHEST), mode))
    _, e = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"]),
                         config["num_experts_per_tok"])
    w = jnp.take_along_axis(s, e, axis=-1)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True)
                 + config["router_renorm_eps"])
    w = w * config["routed_scaling_factor"]
    load = jnp.sum(e.reshape(-1, 1) == jnp.arange(s.shape[1])[None, :],
                   axis=0, dtype=jnp.int32)
    return w, e, load


def expert_block(p: Dict, m, config: Dict, mode: str):
    """m [T, d] -> ([T, d], loads [E]): the held experts' part."""
    first = config["deployment"]["first_expert_held"]
    w, e, load = route(p, m, config, mode)

    def one(y, xs):
        ws, index = xs
        share = jnp.sum(jnp.where(e == first + index, w, 0.0), axis=-1)
        part = jax.checkpoint(
            lambda ws, s: s[:, None] * swiglu(m, ws, mode))
        return y + part(ws, share), None

    held = p["experts"]["wg"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (p["experts"], jnp.arange(held)))
    return y, load


def layer(p: Dict, x, kind: str, config: Dict, mode: str,
          no_out_gate: bool = False):
    """(out [T, d], the router's loads [E], or None from a dense layer)."""
    eps = config["norm_eps"]
    n = rms(x, p["in_norm"], eps)
    mixed = (short_conv(p["mixer"], n, config, mode, no_out_gate)
             if kind == "conv" else attention(p["mixer"], n, config, mode))
    h = x + mixed
    m = rms(h, p["post_norm"], eps)
    if "mlp" in p:
        return h + swiglu(m, p["mlp"], mode), None
    y, load = expert_block(p["moe"], m, config, mode)
    return h + y, load


# ------------------------------------------------------------------ the loss


def loss_fn(params: Dict, rows, idx, labels, config: Dict, mode: str,
            half_positions: bool, bf16_residual: bool, no_out_gate: bool):
    """rows [n, d] the table's rows, idx [B, T] each position's row, labels
    [B, T] the token that follows: (mean cross-entropy over the positions,
    the routers' loads [expert layers, E] over the whole batch)."""

    def sequence(ix, lab):
        x, loads = rows[ix], []                                  # [T, d]
        for p, kind in zip(params["layers"], config["layer_types"]):
            x, load = jax.checkpoint(functools.partial(
                layer, kind=kind, config=config, mode=mode,
                no_out_gate=no_out_gate))(p, x)
            if bf16_residual:
                x = _bf16(x)
            if load is not None:
                loads.append(load)
        h = rms(x, params["final_norm"], config["norm_eps"])
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
            bf16_residual: bool, no_out_gate: bool):
    return jax.jit(functools.partial(
        _train_step, config=json.loads(config_json), mode=mode,
        half_positions=half_positions, bf16_residual=bf16_residual,
        no_out_gate=no_out_gate), donate_argnums=(0, 1, 2, 3, 4))


@jax.jit
def _change_norms(new, old):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                        new, old)


def run(config: Dict, batches: Sequence[Dict[str, np.ndarray]], seed: int, *,
        mode: str = "highest", half_positions: bool = False,
        bf16_residual: bool = False, no_out_gate: bool = False) -> Dict:
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
                   bf16_residual, no_out_gate)
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
