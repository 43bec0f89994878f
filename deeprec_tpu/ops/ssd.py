"""The Mamba-2 state-space scan (SSD) in chunked form.

Per head `h`, with a state `S` of shape `[N, P]` that starts at zero, a
step size `dt_t > 0`, a decay rate `A_h < 0` and the group's input and
output projections `B_t`, `C_t` [N]:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t B_t x_t^T;   y_t = S_t^T C_t

(`ssd_recurrence` below, the oracle of the tests: one dependent step a
token). It is gated linear attention with a scalar decay a head and no
delta correction: `C` plays the query, `B` the key, `dt x` the value
(ops/gated_delta.py is the sibling with the correction, whose product
and padding helpers this file uses). Heads read the `B` and `C` of their
group, `H / G` heads a group.

The normal path cuts the sequence into chunks of `chunk` tokens. With
`gam_i` the running sum of `a = dt A` inside a chunk (`gam_C` its last),
`Gam_ij = exp(gam_i - gam_j)` for `j <= i` and `S_0` the state at the
chunk's start:

    Y   = ((C B^T) . Gam . dt_j) X  +  exp(gam) . (C S_0)
    S_C = exp(gam_C) S_0 + B^T (exp(gam_C - gam) dt X)

Everything but the state at each chunk's start is one batched computation
over all chunks; the starts are a weighted sum of the chunks' own states
with the decay between chunk `m` and chunk `n` summed term by term
(`_segsum`: no difference of two long running sums, which would lose the
small exponents to the large), in f32 at `highest`. The decays are f32
throughout and are only exponentiated as sums of `a <= 0`. `compute_dtype`
is the operand dtype of the products that touch `x`, `B`, `C` and the
state (accumulation f32; the states between chunks are f32 and are never
rounded to it); with `float32` every product is at `highest` (the tests'
setting). A length that is no multiple of the chunk is padded with tokens
that write nothing (`dt = 0`).

The backward is jax's own of this form: under the layer's remat it runs
the forward once more and holds one layer's chunk quantities
(`[B, H, T / chunk, chunk, chunk]` f32 decays, 67 MB at 16 heads and
T 8,192), never a state a token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deeprec_tpu.ops.gated_delta import HIGHEST, _dot, _padded


def ssd_recurrence(x, dt, A, B, C):
    """The scan as written, one token a step. x [b, T, H, P]; dt [b, T, H]
    (after the softplus); A [H] (< 0); B, C [b, T, G, N]; returns
    y [b, T, H, P] f32 (no `D` skip: the caller's)."""
    f32 = jnp.float32
    H, G = x.shape[2], B.shape[2]
    x, dt, B, C = (v.astype(f32) for v in (x, dt, B, C))
    B, C = jnp.repeat(B, H // G, axis=2), jnp.repeat(C, H // G, axis=2)

    def step(S, xs):
        xt, dtt, bt, ct = xs                           # [b, H, ...]
        S = jnp.exp(dtt * A)[..., None, None] * S \
            + dtt[..., None, None] * bt[..., :, None] * xt[..., None, :]
        return S, jnp.einsum("bhnp,bhn->bhp", S, ct, precision=HIGHEST)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C))
    S0 = jnp.zeros((x.shape[0], H, B.shape[-1], x.shape[-1]), f32)
    _, y = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(y, 0, 1)


def _segsum(g):
    """g [..., n] -> [..., n, n]: `sum_{k=j+1}^{i} g_k` where `j <= i` (0 on
    the diagonal), each a sum of its own terms; 0 above the diagonal, where
    the caller masks."""
    n = g.shape[-1]
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    terms = jnp.where(i > j, jnp.broadcast_to(g[..., :, None], g.shape + (n,)),
                      0.0)
    return jnp.cumsum(terms, axis=-2)


def ssd_scan(x, dt, A, B, C, chunk: int = 128, compute_dtype=jnp.bfloat16):
    """The scan over whole sequences, chunked: the shapes of
    `ssd_recurrence`, y [b, T, H, P] f32."""
    f32 = jnp.float32
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    r = H // G
    Tp = -(-T // chunk) * chunk
    x, dt, B, C = _padded((x, dt, B, C), T, Tp)
    nc = Tp // chunk

    def heads(v):   # [b, Tp, H, ...] -> [b, G, r, nc, chunk, ...]
        v = v.reshape((b, nc, chunk, G, r) + v.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(v, 3, 1), 4, 2)

    def groups(v):  # [b, Tp, G, N] -> [b, G, nc, chunk, N]
        return jnp.moveaxis(v.reshape(b, nc, chunk, G, N), 3, 1)

    xc, Bc, Cc = heads(x), groups(B), groups(C)
    dtc = heads(dt.astype(f32))                          # [b, G, r, nc, C]
    gam = jnp.cumsum(dtc * A.reshape(G, r, 1, 1), axis=-1)

    # ---- inside every chunk at once
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = gam[..., :, None] - gam[..., None, :]
    decay = jnp.where(i >= j, jnp.exp(jnp.where(i >= j, diff, 0.0)), 0.0)
    cb = _dot("bgnis,bgnjs->bgnij", Cc, Bc, compute_dtype)
    scores = cb[:, :, None] * decay * dtc[..., None, :]
    y = _dot("bgrnij,bgrnjp->bgrnip", scores, xc, compute_dtype)
    # each chunk's own state, written inside it and read at its end
    xw = xc.astype(f32) * (dtc * jnp.exp(gam[..., -1:] - gam))[..., None]
    own = _dot("bgnjs,bgrnjp->bgrnsp", Bc, xw, compute_dtype)

    # ---- the state at every chunk's start, in f32
    seg = _segsum(gam[..., -1])                          # [b, G, r, nc, nc]
    m = jax.lax.broadcasted_iota(jnp.int32, (nc, nc), 1)
    n = jax.lax.broadcasted_iota(jnp.int32, (nc, nc), 0)
    carry = jnp.where(m <= n, jnp.exp(jnp.where(m <= n, seg, 0.0)), 0.0)
    ends = jnp.einsum("bgrnm,bgrmsp->bgrnsp", carry, own, precision=HIGHEST)
    starts = jnp.concatenate([jnp.zeros_like(ends[:, :, :, :1]),
                              ends[:, :, :, :-1]], axis=3)
    y = y + jnp.exp(gam)[..., None] * _dot(
        "bgnis,bgrnsp->bgrnip", Cc, starts, compute_dtype)

    y = jnp.moveaxis(jnp.moveaxis(y, 2, 4), 1, 3)       # [b, nc, C, G, r, P]
    return y.reshape(b, Tp, H, P)[:, :T]
