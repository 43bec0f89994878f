"""Plain reference of the `qwen3next` family's training steps.

Straightforward `jax.numpy` in float32 with every product at `highest`
precision, written from the layer equations (docs/hybrid_stack.md) and
importing nothing of `deeprec_tpu`: the gated delta rule exactly as the
recurrence is written, one token a step (a scan over tokens nested in blocks
under `jax.checkpoint`, so that its backward keeps block-boundary states
only); full softmax attention by blocks of queries; the experts as a plain
loop over the ones held here, with masks; the same share of the deployment
(the router scores all its outputs, the held experts' part and the shared
expert are added up, what the absent experts would add is left out); rows
made from their ids by the configuration's stated initializer; autodiff;
Adagrad on the rows and Adam on the dense parameters by hand.

`mode` selects the arithmetic:
  "highest"  the reference itself
  "fp8"      the control, the nearest precision below the one the
             configuration states: product operands rounded to float8_e4m3
             and their gradients to float8_e5m2 (each scaled per tensor to
             its largest magnitude), and what the configuration keeps in
             float32 one step down: the recurrence's state and gates and
             the router in bfloat16
  "bf16"     the second witness: the configuration's own arithmetic
             (bfloat16 operands, float32 accumulation and state) in this
             plain code
`half_positions=True` is the planted fault: the second half of every
sequence's positions left out of the loss, the mean taken over the rest.
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
            "witness_bf16": {"mode": "bf16"}}
_TOKEN_BLOCK = 64      # tokens a checkpointed block of the recurrence
_QUERY_BLOCK = 512     # queries a block of the attention
_LOSS_BLOCK = 1024     # positions a block of the loss


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


def is_attention(config: Dict, i: int) -> bool:
    return (i + 1) % config["full_attention_interval"] == 0


def init_dense(config: Dict, seed: int) -> Dict:
    """Dense parameters from the seed: the key split once a layer (and once
    more for the head), then in 14 for a layer's leaves."""
    d, std = config["hidden_size"], config["embedding_init"]["stddev"]
    L = config["num_hidden_layers"]
    Hk, Hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    K = config["linear_conv_kernel_dim"]
    H, Hkv, D = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    E, held = config["deployment"]["router_outputs"], config["num_experts"]
    f, fs = (config["moe_intermediate_size"],
             config["shared_expert_intermediate_size"])
    normal = lambda k, shape: std * jax.random.normal(k, shape)  # noqa: E731
    keys = jax.random.split(jax.random.PRNGKey(seed), L + 1)
    layers = []
    for i in range(L):
        ks = jax.random.split(keys[i], 14)
        if is_attention(config, i):
            mixer = {"wq": normal(ks[0], (d, H * 2 * D)),
                     "wk": normal(ks[1], (d, Hkv * D)),
                     "wv": normal(ks[2], (d, Hkv * D)),
                     "q_norm": jnp.zeros((D,)), "k_norm": jnp.zeros((D,)),
                     "wo": normal(ks[5], (H * D, d))}
        else:
            lim = 1.0 / math.sqrt(K)
            dt = jnp.exp(jax.random.uniform(ks[4], (Hv,))
                         * (math.log(0.1) - math.log(0.001))
                         + math.log(0.001))
            dt = jnp.maximum(dt, 1e-4)
            mixer = {"qkvz": normal(ks[0], (d, 2 * Hk * dk + 2 * Hv * dv)),
                     "ba": normal(ks[1], (d, 2 * Hv)),
                     "conv": jax.random.uniform(
                         ks[2], (K, 2 * Hk * dk + Hv * dv), jnp.float32,
                         -lim, lim),
                     "A_log": jnp.log(jax.random.uniform(
                         ks[3], (Hv,), jnp.float32, 1.0, 16.0)),
                     "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                     "norm": jnp.ones((dv,)),
                     "wo": normal(ks[5], (Hv * dv, d))}
        moe = {"router": normal(ks[6], (d, E)),
               "experts": {"wg": normal(ks[7], (held, d, f)),
                           "wu": normal(ks[8], (held, d, f)),
                           "wd": normal(ks[9], (held, f, d))},
               "shared": {"wg": normal(ks[10], (d, fs)),
                          "wu": normal(ks[11], (d, fs)),
                          "wd": normal(ks[12], (fs, d))},
               "shared_gate": normal(ks[13], (d, 1))}
        layers.append({"in_norm": jnp.zeros((d,)), "mixer": mixer,
                       "post_norm": jnp.zeros((d,)), "moe": moe})
    return {"layers": layers, "final_norm": jnp.zeros((d,)),
            "head": std * jax.random.normal(keys[-1],
                                            (d, config["vocab_size"]))}


def leaf_names(tree, prefix: str = "") -> Dict[str, jnp.ndarray]:
    """{"layers.0.mixer.qkvz": array, ...}: the names the comparison
    speaks in, a leaf's path joined by dots."""
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


def rms(x, w, eps: float, zero_centred: bool):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w) if zero_centred else y * w


# ----------------------------------------------------------- gated delta net


def delta_recurrence(q, k, v, g, beta, mode: str):
    """The rule as written. q, k, v [T, H, 128]; g, beta [T, H]:
    S <- exp(g_t) S; delta_t = beta_t (v_t - S^T k_t); S <- S + k_t delta_t^T;
    o_t = S^T q_t, per head, S_0 = 0."""
    T, H, dk = k.shape
    dv = v.shape[-1]
    g, beta = _down(g, mode), _down(beta, mode)
    lowp = mode in ("fp8", "bf16")

    def dot(spec, a, b):
        if lowp:
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    def token(S, xs):
        qt, kt, vt, gt, bt = xs
        S = _down(S * jnp.exp(gt)[:, None, None], mode)
        delta = bt[:, None] * (vt - dot("hkv,hk->hv", S, kt))
        S = _down(S + dot("hk,hv->hkv", kt, delta), mode)
        return S, dot("hkv,hk->hv", S, qt)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    nb = -(-T // _TOKEN_BLOCK)
    pad = nb * _TOKEN_BLOCK - T

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, _TOKEN_BLOCK) + x.shape[1:])

    # padded tokens write nothing (beta = 0) and their outputs are cut off
    _, o = jax.lax.scan(block, jnp.zeros((H, dk, dv), jnp.float32),
                        tuple(blocks(x) for x in (q, k, v, g, beta)))
    return o.reshape(nb * _TOKEN_BLOCK, H, dv)[:T]


def gated_delta_net(p: Dict, x, config: Dict, mode: str):
    """x [T, d] -> [T, d]."""
    T = x.shape[0]
    Hk, Hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    K, r = config["linear_conv_kernel_dim"], Hv // Hk
    qkvz = _ein("td,de->te", x, p["qkvz"], mode).reshape(
        T, Hk, 2 * dk + 2 * r * dv)
    ba = _ein("td,de->te", x, p["ba"], mode).reshape(T, Hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(T, Hv, dv)
    b, a = ba[..., :r].reshape(T, Hv), ba[..., r:].reshape(T, Hv)
    u = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                         v.reshape(T, -1)], axis=-1)
    up = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(up[j:j + T] * p["conv"][j] for j in range(K)))
    q = u[:, :Hk * dk].reshape(T, Hk, dk)
    k = u[:, Hk * dk:2 * Hk * dk].reshape(T, Hk, dk)
    v = u[:, 2 * Hk * dk:].reshape(T, Hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        / math.sqrt(dk)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    o = delta_recurrence(q, k, v, g, beta, mode)
    o = rms(o, p["norm"], config["rms_norm_eps"], False) * jax.nn.silu(z)
    return _ein("te,ed->td", o.reshape(T, Hv * dv), p["wo"], mode)


# ------------------------------------------------------ gated full attention


def rotary(x, config: Dict):
    """x [H, T, D]: rotary on the first `partial_rotary_factor` of the head
    dim, half-split form."""
    T, D = x.shape[1], x.shape[2]
    rot = int(D * config["partial_rotary_factor"])
    half = rot // 2
    inv_freq = float(config["rope_theta"]) ** (
        -jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    xr, rest = x[..., :rot], x[..., rot:]
    turned = jnp.concatenate([-xr[..., half:], xr[..., :half]], axis=-1)
    return jnp.concatenate([xr * cos + turned * sin, rest], axis=-1)


def gated_attention(p: Dict, x, config: Dict, mode: str):
    """x [T, d] -> [T, d]."""
    T = x.shape[0]
    H, Hkv, D = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    eps = config["rms_norm_eps"]
    qg = _ein("td,de->te", x, p["wq"], mode).reshape(T, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:].reshape(T, H * D)
    k = _ein("td,de->te", x, p["wk"], mode).reshape(T, Hkv, D)
    v = _ein("td,de->te", x, p["wv"], mode).reshape(T, Hkv, D)
    q = rotary(jnp.moveaxis(rms(q, p["q_norm"], eps, True), 1, 0), config)
    k = rotary(jnp.moveaxis(rms(k, p["k_norm"], eps, True), 1, 0), config)
    v = jnp.moveaxis(v, 1, 0)
    k, v = jnp.repeat(k, H // Hkv, axis=0), jnp.repeat(v, H // Hkv, axis=0)
    bq = math.gcd(T, _QUERY_BLOCK)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        qb, start = args                                   # [H, bq, D]
        s = _ein("hqd,hkd->hqk", qb, k, mode) * (D ** -0.5)
        qpos = start + jnp.arange(bq)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -1e30)
        return _ein("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v, mode)

    qb = jnp.moveaxis(q.reshape(H, T // bq, bq, D), 1, 0)
    o = jax.lax.map(block, (qb, jnp.arange(T // bq) * bq))   # [nb, H, bq, D]
    o = jnp.moveaxis(o, 0, 1).reshape(H, T, D)
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * D)
    return _ein("te,ed->td", o * jax.nn.sigmoid(gate), p["wo"], mode)


# --------------------------------------------------------------- the experts


def swiglu(x, wg, wu, wd, mode: str):
    h = jax.nn.silu(_ein("td,df->tf", x, wg, mode)) \
        * _ein("td,df->tf", x, wu, mode)
    return _ein("tf,fd->td", h, wd, mode)


def expert_block(p: Dict, x, config: Dict, mode: str):
    """x [T, d] -> [T, d]: the held experts' part and the shared expert."""
    first = config["deployment"]["first_expert_held"]
    top_k = config["num_experts_per_tok"]
    xr, wr = _down(x, mode), _down(p["router"], mode)
    probs = jax.nn.softmax(_down(jnp.dot(xr, wr, precision=HIGHEST), mode),
                           axis=-1)
    w, e = jax.lax.top_k(probs, top_k)
    if config["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def one(y, xs):
        weights, index = xs
        share = jnp.sum(jnp.where(e == first + index, w, 0.0), axis=-1)
        part = jax.checkpoint(
            lambda ws, s: s[:, None] * swiglu(x, ws["wg"], ws["wu"],
                                              ws["wd"], mode))
        return y + part(weights, share), None

    held = p["experts"]["wg"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["experts"], jnp.arange(held)))
    s = p["shared"]
    gate = jax.nn.sigmoid(_ein("td,do->to", x, p["shared_gate"], mode))
    return y + gate * swiglu(x, s["wg"], s["wu"], s["wd"], mode)


# ------------------------------------------------------------------ the loss


def loss_fn(params: Dict, rows, idx, labels, config: Dict, mode: str,
            half_positions: bool):
    """rows [n, d] the table's rows, idx [B, T] each position's row, labels
    [B, T] the token that follows: mean cross-entropy over the positions."""
    eps = config["rms_norm_eps"]

    def sequence(ix, lab):
        x = rows[ix]                                            # [T, d]
        for i, p in enumerate(params["layers"]):
            @jax.checkpoint
            def layer(p, x, i=i):
                mixer = gated_attention if is_attention(config, i) \
                    else gated_delta_net
                h = x + mixer(p["mixer"], rms(x, p["in_norm"], eps, True),
                              config, mode)
                return h + expert_block(
                    p["moe"], rms(h, p["post_norm"], eps, True), config, mode)
            x = layer(p, x)
        h = rms(x, params["final_norm"], eps, True)
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
                mode: str, half_positions: bool):
    loss, (g, g_rows) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
        params, rows, idx, labels, config, mode, half_positions)
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
def _jitted(config_json: str, mode: str, half_positions: bool):
    config = json.loads(config_json)
    return jax.jit(functools.partial(_train_step, config=config, mode=mode,
                                     half_positions=half_positions),
                   donate_argnums=(0, 1, 2, 3, 4))


@jax.jit
def _change_norms(new, old):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                        new, old)


def run(config: Dict, batches: Sequence[Dict[str, np.ndarray]], seed: int, *,
        mode: str = "highest", half_positions: bool = False) -> Dict:
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
    step = _jitted(json.dumps(config, sort_keys=True), mode, half_positions)
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
    # at the cell's size a second copy of the weights does not fit beside
    # the moments and the gradient)
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
