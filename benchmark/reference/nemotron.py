"""Plain reference of the `nemotron` family's training steps.

Straightforward `jax.numpy` in float32 with every product at `highest`
precision, written from the layer equations (docs/mamba_stack.md) and
importing nothing of `deeprec_tpu` nor of another family's module. Layer i
on x [T, d] is ONE block, of the kind the i-th letter of
`hybrid_override_pattern` names: `h = x + block_i(rms(x; w_i))`, with

    M (Mamba-2, H heads of P, G groups, state N, kernel K):
        [z, xBC, dt] = n W_in
        xBC = silu(sum_j w[j] xBC[t - (K - 1) + j] + b_conv)
            -> x [T, H, P], B, C [T, G, N]; head h reads group h // (H / G)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T;  y_t = S_t^T C_t + D x_t
        out = gnorm(y * silu(z)) W_out   (RMS over each group of H P / G)
    * (attention, H query heads over Hkv, no position encoding):
        out = softmax(q k^T / sqrt(D), causal) v Wo
    E (latent experts):
        s = sigmoid(n Wr);  chosen = top-k of (s + b)
        w = s[chosen] / (sum of the chosen s + 1e-20) * scale
        out = (sum over the chosen experts j held HERE of
               w_j relu(n W_down W1_j)^2 W2_j) W_up + relu(n Ws1)^2 Ws2

the scan as its plain recurrence, ONE TOKEN A STEP (no chunks, no
segment sums), in blocks of 128 tokens under `jax.checkpoint` so that its
gradient holds a block's states and not a sequence's; the scores by blocks
of queries over all the keys; the experts as a plain loop over the ones
held here, with masks; the same share of the deployment as the program
(the heads, the group and the experts held here; the router scores all
its outputs; the latent projections and the shared expert whole; what the
absent heads and experts would add is left out); rows made from their ids
by the configuration's stated initializer; autodiff; Adagrad on the rows
and Adam on the dense parameters by hand; and after every step, in every
expert layer, the rule that owns the selection bias `b` (it has no
gradient): `b_j += gamma sign(mean(c) - c_j)`, `c_j` the step's choices
that fell on output `j` of ALL the router's outputs.

`mode` selects the arithmetic:
  "highest"  the reference itself
  "fp8"      the control, the nearest precision below the one the
             configuration states: product operands (the scan's x, B and
             C among them) rounded to float8_e4m3 and their gradients to
             float8_e5m2 (each scaled per tensor to its largest
             magnitude), and the router, which the configuration keeps in
             float32, in bfloat16
  "bf16"     the second witness: the configuration's own arithmetic
             (bfloat16 operands, float32 accumulation) in this plain code
Three planted faults: `half_positions=True`, the second half of every
sequence's positions left out of the loss, the mean taken over the rest;
`bf16_state=True`, the scan's state rounded to bfloat16 after every token
(the configuration keeps it in float32); `no_skip=True`, the `D x` skip
left out.

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
            "fault_bf16_state": {"bf16_state": True},
            "fault_no_skip": {"no_skip": True},
            "witness_bf16": {"mode": "bf16"}}
_QUERY_BLOCK = 256     # queries a block of the attention
_LOSS_BLOCK = 1024     # positions a block of the loss
_SCAN_BLOCK = 128      # tokens a block of the recurrence's checkpoints
_LAYER_KEYS = 14       # keys a layer's leaves are drawn from
_BLOCK_LEAF = {"M": "mamba", "*": "attn", "E": "moe"}


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


def pattern(config: Dict) -> str:
    """The kinds of the layers held here, a letter a layer."""
    first = config["deployment"].get("first_layer_held", 0)
    return config["hybrid_override_pattern"][
        first:first + config["num_hidden_layers"]]


def init_dense(config: Dict, seed: int) -> Dict:
    """Dense parameters from the seed: the key split once a layer (and once
    more for the head), then in 14 for a layer's leaves."""
    d, std = config["hidden_size"], config["embedding_init"]["stddev"]
    L = config["num_hidden_layers"]
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N, K = config["n_groups"], config["ssm_state_size"], config["conv_kernel"]
    Hq, Hkv, D = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    E, held = config["deployment"]["router_outputs"], config["n_routed_experts"]
    lat, f = config["moe_latent_size"], config["moe_intermediate_size"]
    fs = config["n_shared_experts"] * config["moe_shared_expert_intermediate_size"]
    inner, xbc = H * P, H * P + 2 * G * N
    normal = lambda k, shape: std * jax.random.normal(k, shape)  # noqa: E731
    lim = 1.0 / math.sqrt(K)
    lo, hi = (math.log(config["time_step_min"]),
              math.log(config["time_step_max"]))
    first_head = config["deployment"]["first_mamba_head_held"]
    keys = jax.random.split(jax.random.PRNGKey(seed), L + 1)
    layers = []
    for i, kind in enumerate(pattern(config)):
        ks = jax.random.split(keys[i], _LAYER_KEYS)
        if kind == "M":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(ks[4], (H,))
                                     * (hi - lo) + lo),
                             config["time_step_floor"])
            block = {"w_in": normal(ks[0], (d, inner + xbc + H)),
                     "conv": jax.random.uniform(ks[2], (K, xbc), jnp.float32,
                                                -lim, lim),
                     "conv_bias": jax.random.uniform(ks[3], (xbc,),
                                                     jnp.float32, -lim, lim),
                     "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                     "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)
                                      + first_head),
                     "D": jnp.ones((H,)), "norm": jnp.ones((inner,)),
                     "w_out": normal(ks[5], (inner, d))}
        elif kind == "*":
            block = {"wq": normal(ks[0], (d, Hq * D)),
                     "wk": normal(ks[1], (d, Hkv * D)),
                     "wv": normal(ks[2], (d, Hkv * D)),
                     "wo": normal(ks[5], (Hq * D, d))}
        else:
            block = {"router": normal(ks[6], (d, E)), "bias": jnp.zeros((E,)),
                     "w_down": normal(ks[1], (d, lat)),
                     "experts": {"wu": normal(ks[7], (held, lat, f)),
                                 "wd": normal(ks[9], (held, f, lat))},
                     "w_up": normal(ks[2], (lat, d)),
                     "shared": {"wu": normal(ks[10], (d, fs)),
                                "wd": normal(ks[12], (fs, d))}}
        layers.append({"norm": jnp.ones((d,)), _BLOCK_LEAF[kind]: block})
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


def _bf16(x):
    """x rounded to bfloat16 and kept in float32. Every lower precision
    here is an explicit `reduce_precision`: a pair of converts is one the
    TPU compiler may drop as excess precision, and it did (the bf16-state
    fault read 0 on the chip so)."""
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


def relu2(x):
    return jnp.square(jax.nn.relu(x))


# ------------------------------------------------------------------- Mamba-2


def _operand(x, mode: str):
    """A product's operand as the mode's arithmetic rounds it."""
    if mode == "fp8":
        return _fp8(x)
    if mode == "bf16":
        return _bf16(x)
    return x


def recurrence(x, dt, A, b, c, mode: str, bf16_state: bool = False):
    """x [T, H, P], dt [T, H], A [H], b, c [T, G, N] -> y [T, H, P]:
    `S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T`, `y_t = S_t^T C_t`, one
    token a step from S_0 = 0, the state in float32 (rounded to bfloat16
    after every token under the fault `bf16_state`)."""
    T, H, P = x.shape
    G, N = b.shape[1], b.shape[2]
    x, b, c = (_operand(v, mode) for v in (x, b, c))
    b, c = jnp.repeat(b, H // G, axis=1), jnp.repeat(c, H // G, axis=1)

    def step(S, xs):
        xt, dtt, bt, ct = xs                          # [H, P], [H], [H, N]
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * bt)[:, :, None] * xt[:, None, :]
        if bf16_state:
            S = _bf16(S)
        return S, jnp.einsum("hnp,hn->hp", S, ct, precision=HIGHEST)

    blk = math.gcd(T, _SCAN_BLOCK)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    xs = tuple(v.reshape((T // blk, blk) + v.shape[1:]) for v in (x, dt, b, c))
    _, y = jax.lax.scan(block, jnp.zeros((H, N, P), jnp.float32), xs)
    return y.reshape(T, H, P)


def mamba(p: Dict, n, config: Dict, mode: str, bf16_state: bool = False,
          no_skip: bool = False):
    """A Mamba-2 mixer's share: n [T, d] (normed) -> [T, d]."""
    T = n.shape[0]
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N, K = config["n_groups"], config["ssm_state_size"], config["conv_kernel"]
    inner = H * P
    zxbcdt = _ein("td,de->te", n, p["w_in"], mode)
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:-H], zxbcdt[:, -H:]
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[j:j + T] * p["conv"][j] for j in range(K))
                      + p["conv_bias"])
    x = xbc[:, :inner].reshape(T, H, P)
    b = xbc[:, inner:inner + G * N].reshape(T, G, N)
    c = xbc[:, inner + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b, c, mode, bf16_state)
    if not no_skip:
        y = y + p["D"][:, None] * x
    y = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, G, inner // G)
    y = rms(y, 1.0, config["layer_norm_epsilon"]).reshape(T, inner) * p["norm"]
    return _ein("te,ed->td", y, p["w_out"], mode)


# ----------------------------------------------------------------- attention


def attention(p: Dict, n, config: Dict, mode: str):
    """n [T, d] (normed) -> [T, d]: causal, grouped queries, no position
    encoding."""
    T = n.shape[0]
    H, Hkv, D = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    q, k, v = (jnp.moveaxis(_ein("td,de->te", n, p[w], mode).reshape(
        T, h, D), 1, 0) for w, h in (("wq", H), ("wk", Hkv), ("wv", Hkv)))
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
    by its score alone."""
    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("the reference routes over one group")
    s = jax.nn.sigmoid(_down(jnp.dot(_down(m, mode), _down(p["router"], mode),
                                     precision=HIGHEST), mode))
    _, e = jax.lax.top_k(s + jax.lax.stop_gradient(p["bias"]),
                         config["num_experts_per_tok"])
    w = jnp.take_along_axis(s, e, axis=-1)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * config["routed_scaling_factor"]
    load = jnp.sum(e.reshape(-1, 1) == jnp.arange(s.shape[1])[None, :],
                   axis=0, dtype=jnp.int32)
    return w, e, load


def expert_block(p: Dict, m, config: Dict, mode: str):
    """m [T, d] -> ([T, d], loads [E]): the held experts' part in the
    latent space, projected back, and the shared expert."""
    first = config["deployment"]["first_expert_held"]
    w, e, load = route(p, m, config, mode)
    xl = _ein("td,dl->tl", m, p["w_down"], mode)

    def one(y, xs):
        ws, index = xs
        share = jnp.sum(jnp.where(e == first + index, w, 0.0), axis=-1)
        part = jax.checkpoint(lambda ws, s: s[:, None] * _ein(
            "tf,fl->tl", relu2(_ein("tl,lf->tf", xl, ws["wu"], mode)),
            ws["wd"], mode))
        return y + part(ws, share), None

    held = p["experts"]["wu"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(xl),
                        (p["experts"], jnp.arange(held)))
    s = p["shared"]
    return (_ein("tl,ld->td", y, p["w_up"], mode)
            + _ein("tf,fd->td", relu2(_ein("td,df->tf", m, s["wu"], mode)),
                   s["wd"], mode)), load


def layer(p: Dict, x, kind: str, config: Dict, mode: str,
          bf16_state: bool = False, no_skip: bool = False):
    """(out [T, d], the router's loads [E], or None from a mixer)."""
    n = rms(x, p["norm"], config["layer_norm_epsilon"])
    if kind == "E":
        y, load = expert_block(p["moe"], n, config, mode)
        return x + y, load
    if kind == "M":
        return x + mamba(p["mamba"], n, config, mode, bf16_state,
                         no_skip), None
    return x + attention(p["attn"], n, config, mode), None


# ------------------------------------------------------------------ the loss


def loss_fn(params: Dict, rows, idx, labels, config: Dict, mode: str,
            half_positions: bool, bf16_state: bool, no_skip: bool):
    """rows [n, d] the table's rows, idx [B, T] each position's row, labels
    [B, T] the token that follows: (mean cross-entropy over the positions,
    the routers' loads [expert layers, E] over the whole batch)."""
    kinds = pattern(config)

    def sequence(ix, lab):
        x, loads = rows[ix], []                                  # [T, d]
        for p, kind in zip(params["layers"], kinds):
            x, load = jax.checkpoint(functools.partial(
                layer, kind=kind, config=config, mode=mode,
                bf16_state=bf16_state, no_skip=no_skip))(p, x)
            if load is not None:
                loads.append(load)
        h = rms(x, params["final_norm"], config["layer_norm_epsilon"])
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
            bf16_state: bool, no_skip: bool):
    return jax.jit(functools.partial(
        _train_step, config=json.loads(config_json), mode=mode,
        half_positions=half_positions, bf16_state=bf16_state,
        no_skip=no_skip), donate_argnums=(0, 1, 2, 3, 4))


@jax.jit
def _change_norms(new, old):
    return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                        new, old)


def run(config: Dict, batches: Sequence[Dict[str, np.ndarray]], seed: int, *,
        mode: str = "highest", half_positions: bool = False,
        bf16_state: bool = False, no_skip: bool = False) -> Dict:
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
                   bf16_state, no_skip)
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
