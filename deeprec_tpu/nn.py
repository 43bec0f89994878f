"""Minimal functional NN layer library for the modelzoo.

Plain pytree params + pure apply functions — no framework dependency, full
control of dtypes (bf16 compute / f32 params, the TPU translation of
DeepRec's BFloat16 scope: docs/docs_en/BFloat16.md, usage
modelzoo/wide_and_deep/train.py:187-199). All matmuls carry
preferred_element_type=float32 so the MXU accumulates in f32.

Layers cover the reference modelzoo's building blocks: MLP towers, DIN's
local-activation attention (modelzoo/din), DIEN's GRU/AUGRU (modelzoo/dien),
BST's transformer block (modelzoo/bst), DCN's cross network (modelzoo/dcnv2),
DeepFM's FM layer and DLRM's dot interaction.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def _glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[-1]
    lim = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def matmul(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


# ----------------------------------------------------------------- dense / MLP


def dense_init(key, in_dim: int, out_dim: int) -> Params:
    kw, _ = jax.random.split(key)
    return {"w": _glorot(kw, (in_dim, out_dim)), "b": jnp.zeros((out_dim,))}


def dense_apply(p: Params, x, compute_dtype=jnp.bfloat16):
    y = matmul(x.astype(compute_dtype), p["w"].astype(compute_dtype))
    return y.astype(jnp.float32) + p["b"]


def mlp_init(key, in_dim: int, hidden: Sequence[int]) -> Params:
    keys = jax.random.split(key, len(hidden))
    layers = []
    d = in_dim
    for k, h in zip(keys, hidden):
        layers.append(dense_init(k, d, h))
        d = h
    return {"layers": layers}


def mlp_apply(p: Params, x, activation=jax.nn.relu, final_activation=None,
              compute_dtype=jnp.bfloat16):
    n = len(p["layers"])
    for i, layer in enumerate(p["layers"]):
        x = dense_apply(layer, x, compute_dtype)
        if i < n - 1:
            x = activation(x)
        elif final_activation is not None:
            x = final_activation(x)
    return x


def layernorm_init(dim: int) -> Params:
    return {"g": jnp.ones((dim,)), "b": jnp.zeros((dim,))}


def layernorm_apply(p: Params, x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


# ------------------------------------------------------- DIN attention pooling


def din_attention_init(key, dim: int, hidden: Sequence[int] = (36,)) -> Params:
    # scorer input: [item, hist, item-hist, item*hist]
    return {"mlp": mlp_init(key, 4 * dim, list(hidden) + [1])}


def din_attention_apply(p: Params, query, keys, mask):
    """DIN local activation unit (modelzoo/din/train.py attention):
    query [B, D] target item, keys [B, L, D] behavior sequence."""
    B, L, D = keys.shape
    q = jnp.broadcast_to(query[:, None, :], (B, L, D))
    feats = jnp.concatenate([q, keys, q - keys, q * keys], axis=-1)
    scores = mlp_apply(p["mlp"], feats.reshape(B * L, 4 * D)).reshape(B, L)
    scores = jnp.where(mask, scores, -1e9)
    w = jax.nn.softmax(scores, axis=1)
    w = jnp.where(mask, w, 0.0)
    return jnp.einsum("bl,bld->bd", w, keys)


# ----------------------------------------------------------------- GRU / AUGRU


def gru_init(key, in_dim: int, hid: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wz": _glorot(k1, (in_dim + hid, hid)),
        "wr": _glorot(k2, (in_dim + hid, hid)),
        "wh": _glorot(k3, (in_dim + hid, hid)),
        "bz": jnp.zeros((hid,)),
        "br": jnp.zeros((hid,)),
        "bh": jnp.zeros((hid,)),
    }


def _gru_cell(p, h, x, att: Optional[jnp.ndarray] = None):
    xh = jnp.concatenate([x, h], axis=-1)
    z = jax.nn.sigmoid(matmul(xh, p["wz"]) + p["bz"])
    r = jax.nn.sigmoid(matmul(xh, p["wr"]) + p["br"])
    xrh = jnp.concatenate([x, r * h], axis=-1)
    hh = jnp.tanh(matmul(xrh, p["wh"]) + p["bh"])
    if att is not None:
        # AUGRU: attention scales the update gate (DIEN,
        # modelzoo/dien/train.py "augru")
        z = att[:, None] * z
    return (1.0 - z) * h + z * hh


def gru_apply(p: Params, xs, mask, att=None):
    """Run a (AU)GRU over [B, L, D] with [B, L] mask via lax.scan.

    Returns final hidden state [B, H] and all hidden states [B, L, H].
    Masked positions carry the previous state through (standard padded-seq
    handling, compiler-friendly — no dynamic lengths).
    """
    B, L, D = xs.shape
    H = p["bz"].shape[0]
    h0 = jnp.zeros((B, H), jnp.float32)

    def step(h, inp):
        x, m, a = inp
        h_new = _gru_cell(p, h, x, a)
        h = jnp.where(m[:, None], h_new, h)
        return h, h

    xs_t = jnp.moveaxis(xs, 1, 0)  # [L, B, D]
    mask_t = jnp.moveaxis(mask, 1, 0)
    att_t = (
        jnp.moveaxis(att, 1, 0)
        if att is not None
        else jnp.ones((L, B), jnp.float32)
    )
    h_final, hs = jax.lax.scan(step, h0, (xs_t, mask_t, att_t))
    return h_final, jnp.moveaxis(hs, 0, 1)


# ------------------------------------------------------------ transformer (BST)


def transformer_block_init(key, dim: int, heads: int, ff: int) -> Params:
    # NB: `heads` stays static config (apply arg), NOT a params leaf — ints in
    # the differentiated pytree would crash jax.grad.
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "qkv": _glorot(k1, (dim, 3 * dim)),
        "proj": _glorot(k2, (dim, dim)),
        "ff1": dense_init(k3, dim, ff),
        "ff2": dense_init(k4, ff, dim),
        "ln1": layernorm_init(dim),
        "ln2": layernorm_init(dim),
    }


def transformer_block_apply(p: Params, x, mask, heads: int, flash: bool = False):
    """Post-LN transformer encoder block with padding mask: x [B, L, D].

    flash=True routes attention through the Pallas flash kernel (O(L·block)
    memory — for long behavior histories; L must be a multiple of 128)."""
    B, L, D = x.shape
    H = heads
    qkv = matmul(x, p["qkv"]).reshape(B, L, 3, H, D // H)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B, L, H, Dh]
    if flash:
        from deeprec_tpu.ops.flash_attention import flash_attention

        blk = 128
        Lp = ((L + blk - 1) // blk) * blk
        pad = Lp - L
        qh = jnp.moveaxis(q, 2, 1)  # [B, H, L, Dh]
        kh = jnp.moveaxis(k, 2, 1)
        vh = jnp.moveaxis(v, 2, 1)
        if pad:
            zeros = ((0, 0), (0, 0), (0, pad), (0, 0))
            qh = jnp.pad(qh, zeros)
            kh = jnp.pad(kh, zeros)
            vh = jnp.pad(vh, zeros)
            fmask = jnp.pad(mask, ((0, 0), (0, pad)))
        else:
            fmask = mask
        out = jnp.moveaxis(flash_attention(qh, kh, vh, fmask), 1, 2)
        out = out[:, :L].reshape(B, L, D)
    else:
        from deeprec_tpu.ops.flash_attention import attention_reference

        out = attention_reference(
            jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
            mask,
        )
        out = jnp.moveaxis(out, 1, 2).reshape(B, L, D)
    x = layernorm_apply(p["ln1"], x + matmul(out, p["proj"]))
    ff = dense_apply(p["ff2"], jax.nn.relu(dense_apply(p["ff1"], x)))
    x = layernorm_apply(p["ln2"], x + ff)
    return jnp.where(mask[..., None], x, 0.0)


# -------------------------------------------------------------- DCN cross net


def crossnet_init(key, dim: int, depth: int) -> Params:
    keys = jax.random.split(key, depth)
    return {
        "layers": [
            {"w": _glorot(k, (dim, dim)), "b": jnp.zeros((dim,))} for k in keys
        ]
    }


def crossnet_apply(p: Params, x0):
    """DCNv2 cross layer: x_{l+1} = x0 * (W x_l + b) + x_l
    (modelzoo/dcnv2/train.py)."""
    x = x0
    for layer in p["layers"]:
        x = x0 * (matmul(x, layer["w"]) + layer["b"]) + x
    return x


def crossnet_v1_init(key, dim: int, depth: int) -> Params:
    keys = jax.random.split(key, depth)
    return {
        "layers": [
            {"w": _glorot(k, (dim, 1))[:, 0], "b": jnp.zeros((dim,))}
            for k in keys
        ]
    }


def crossnet_v1_apply(p: Params, x0):
    """Original DCN cross layer with VECTOR weights:
    x_{l+1} = x0 * (x_l . w) + b + x_l  (modelzoo/dcn/train.py) —
    rank-1 feature crossing, O(dim) params per layer vs v2's O(dim^2)."""
    x = x0
    for layer in p["layers"]:
        x = x0 * (x @ layer["w"])[:, None] + layer["b"] + x
    return x


# ------------------------------------------------------------------- FM / dot


def fm_apply(emb_stack):
    """Second-order FM interaction over [B, F, D] field embeddings
    (DeepFM, modelzoo/deepfm): 0.5 * ((Σv)² − Σv²) summed over D."""
    s = jnp.sum(emb_stack, axis=1)
    sq = jnp.sum(emb_stack * emb_stack, axis=1)
    return 0.5 * jnp.sum(s * s - sq, axis=1, keepdims=True)


def dot_interaction(emb_stack, keep_diag: bool = False):
    """DLRM pairwise dot interactions over [B, F, D] -> [B, F*(F-1)/2]."""
    B, F, D = emb_stack.shape
    z = jnp.einsum("bfd,bgd->bfg", emb_stack, emb_stack)
    i, j = jnp.triu_indices(F, k=0 if keep_diag else 1)
    return z[:, i, j]


# ------------------------------------------------- sample-aware compression


def group_compress(group_ids, num_groups: int):
    """Dedup rows by a group id (user id) for sample-aware compression.

    The general form of the reference's Sample-awared Graph Compression
    (docs/docs_en/Sample-awared-Graph-Compression.md): ranking batches are
    packed as <user, N candidate items>, so user-side compute repeated N
    times is waste. `num_groups` is the static maximum distinct groups per
    batch (the packer's G).

    Returns (first_ix [G], inverse [B], ok [B]): `x[first_ix]` is one
    representative row per group, `out[inverse]` broadcasts per-group
    results back to the batch, and `ok` marks rows whose group made the
    cut — rows of overflow groups (a packer bug) have ok=False and MUST
    NOT silently receive another group's output.
    """
    group_ids = group_ids.reshape(-1)
    uids, first_ix, inverse = jnp.unique(
        group_ids, size=num_groups, return_index=True, return_inverse=True,
        fill_value=group_ids[0],
    )
    inverse = inverse.reshape(-1)
    ok = inverse < num_groups
    return first_ix, jnp.where(ok, inverse, 0), ok


def apply_grouped(fn, inputs, group_ids, num_groups: int):
    """Run `fn` once per distinct group and broadcast results to the batch:
    fn(tree with leading dim G) on rows deduped by group_ids [B]; output
    leaves regain leading dim B. Equal to fn(full batch) row-for-row when
    fn is row-independent — with G/B of the compute.

    Rows whose group overflowed num_groups come back as NaN: a packer that
    violates its G must fail loudly, not serve one user's scores to
    another."""
    first_ix, inverse, ok = group_compress(group_ids, num_groups)
    compact = jax.tree.map(lambda a: a[first_ix], inputs)
    out = fn(compact)

    def broadcast(a):
        rows = a[inverse]
        mask = ok.reshape(ok.shape + (1,) * (rows.ndim - 1))
        return jnp.where(mask, rows, jnp.nan)

    return jax.tree.map(broadcast, out)


# ------------------------------------------- token-model building blocks


def rms_norm(x, w, eps: float = 1e-6, zero_centred: bool = False):
    """RMS normalisation over the last axis in f32. `zero_centred` scales by
    `1 + w` (a weight initialised at 0), else by `w` (initialised at 1)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * ((1.0 + w) if zero_centred else w)


def rotary_partial(x, positions, rot_dim: int, theta: float):
    """Rotary position embedding on the first `rot_dim` dims of the last
    axis, half-split form (`rot(x) = concat(-x[half:], x[:half])`,
    `x cos + rot(x) sin`, `inv_freq_j = theta^(-2j / rot_dim)`); the other
    dims pass through. x [..., L, D] f32, positions [L]."""
    half = rot_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot_dim)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)               # [L, half]
    x1, x2, rest = x[..., :half], x[..., half:rot_dim], x[..., rot_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def swiglu_apply(x, w_gate, w_up, w_down, compute_dtype=jnp.bfloat16):
    """`(silu(x W_g) * (x W_u)) W_d`, operands in `compute_dtype`, every
    product accumulated in f32."""
    xc = x.astype(compute_dtype)
    g = matmul(xc, w_gate.astype(compute_dtype))
    u = matmul(xc, w_up.astype(compute_dtype))
    h = (jax.nn.silu(g) * u).astype(compute_dtype)
    return matmul(h, w_down.astype(compute_dtype))


def causal_conv1d(x, w, b=None):
    """Depthwise causal convolution over time: x [B, L, C] (any float
    dtype; the sum is f32), w [K, C], an optional bias b [C];
    `y_t = sum_j w[j] x[t - (K - 1) + j] (+ b)` (left padding K - 1, the
    cross-correlation a `[C, 1, K]` conv1d weight computes)."""
    K, L = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + L].astype(jnp.float32) * w[j] for j in range(K))
    return y if b is None else y + b
