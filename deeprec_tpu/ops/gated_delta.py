"""The gated delta rule in chunked form, with a chunked backward.

Per head, with a state `S` of shape `[d_k, d_v]` that starts at zero, a log
decay `g_t <= 0` and a write strength `beta_t`:

    S <- exp(g_t) S;  delta_t = beta_t (v_t - S^T k_t);
    S <- S + k_t delta_t^T;  o_t = S^T q_t

(`gated_delta_recurrence` below, the oracle of the tests: one dependent step
a token). The normal path cuts the sequence into chunks of `chunk` tokens.
With `gam_i` the running sum of `g` inside a chunk, `S_0` the state at the
chunk's start and `Gam_ij = exp(gam_i - gam_j)` for `j <= i`:

    (I + A) Delta = beta (V - diag(exp gam) K S_0),
        A_ij = beta_i Gam_ij (k_i . k_j) for j < i, else 0
    Delta = U - W S_0,  U = T (beta V),  W = T (beta exp(gam) K),
        T = (I + A)^-1                       (the WY / UT form)
    O = (exp(gam) Q) S_0 + ((Q K^T) . Gam) Delta
    S_C = exp(gam_C) S_0 + (exp(gam_C - gam) K)^T Delta

`A` is strictly lower triangular and `T` comes by forward substitution, a
row a step, for all chunks at once (`_unit_lower_inverse` says why not by
the product form). Everything but the last two lines is one batched
computation over all chunks; only `Delta`, `O` and `S_C` walk the chunks in order (a `lax.scan`
of `T / chunk` steps carrying the `[d_k, d_v]` states in f32).

The decays are f32 throughout and are only ever exponentiated as
differences `gam_i - gam_j <= 0`. `compute_dtype` is the operand dtype of
the products that touch q, k, v and the state (accumulation is f32; the
state itself is carried in f32); the triangular inverse is always f32. With `compute_dtype=float32` every product is at `highest` (the
tests' setting).

`gated_delta_rule` is a `custom_vjp`. The sequence is walked in SEGMENTS of
`segment` tokens (a whole number of chunks, 32 of them by default); the
forward keeps its five inputs and the state at the start of every segment
(`[T / segment, B, H, d_k, d_v]`, 8 MB a head-batch of 32 at T = 8192) and
nothing else. The backward walks the segments in reverse: for each it
recomputes the chunk quantities and the chunk states from the segment's
start state and differentiates the chunked form, handing the state's
cotangent on to the segment before. So no per-token state is ever held,
and what the backward holds at once is one segment's chunk quantities, not
the sequence's. A length that is no multiple of the segment is padded with
tokens that write nothing (`beta = 0, g = 0`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeprec_tpu.utils import scopes

HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_recurrence(q, k, v, g, beta):
    """The rule as written, one token a step. q, k [B, T, H, dk];
    v [B, T, H, dv]; g, beta [B, T, H]; returns o [B, T, H, dv] in f32."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    B, _, H, dk = k.shape

    def step(S, xs):
        qt, kt, vt, gt, bt = xs                      # [B, H, ...]
        S = S * jnp.exp(gt)[..., None, None]
        pred = jnp.einsum("bhkv,bhk->bhv", S, kt, precision=HIGHEST)
        delta = bt[..., None] * (vt - pred)
        S = S + kt[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=HIGHEST)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), f32)
    _, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1)


def _dot(spec: str, a, b, cdt):
    if cdt == jnp.float32:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return jnp.einsum(spec, a.astype(cdt), b.astype(cdt),
                      preferred_element_type=jnp.float32)


_BASE = 16   # rows of a block inverted by substitution


def _substitution_inverse(A):
    """T = (I + A)^-1 = I + N by forward substitution, a row a step:
    `N_i = -A_i - sum_{j<i} A_ij N_j` (the rows before `i` are final)."""
    C = A.shape[-1]

    def row(i, N):
        a = -jax.lax.dynamic_index_in_dim(A, i, axis=-2, keepdims=False)
        n = a + jnp.einsum("...j,...jk->...k", a, N, precision=HIGHEST)
        return jax.lax.dynamic_update_index_in_dim(N, n, i, axis=-2)

    N = jax.lax.fori_loop(1, C, row, jnp.zeros_like(A))   # row 0 of N is 0
    return N + jnp.eye(C, dtype=jnp.float32)


def _blocked_inverse(A):
    """The same inverse, blocked: the `[16, 16]` blocks on the diagonal by
    substitution (15 dependent steps over a sixteenth of the data), then
    pairs of blocks joined, 16 -> 32 -> 64, by
    `[[T11, 0], [-T22 A21 T11, T22]]`: block forward substitution, which
    is as stable as the row form and an order cheaper."""
    C = A.shape[-1]
    s = min(_BASE, C)
    if C % s or (C // s) & (C // s - 1):
        return _substitution_inverse(A)
    lead = A.shape[:-2]

    def blocks(size):   # A as [..., C/size, size, C/size, size]
        return A.reshape(lead + (C // size, size, C // size, size))

    Ab = blocks(s)
    T = _substitution_inverse(jnp.stack(
        [Ab[..., p, :, p, :] for p in range(C // s)], axis=-3))
    while s < C:
        Ab = blocks(s)
        A21 = jnp.stack([Ab[..., 2 * p + 1, :, 2 * p, :]
                         for p in range(C // (2 * s))], axis=-3)
        T11, T22 = T[..., 0::2, :, :], T[..., 1::2, :, :]
        T21 = -jnp.matmul(jnp.matmul(T22, A21, precision=HIGHEST), T11,
                          precision=HIGHEST)
        T = jnp.concatenate(
            [jnp.concatenate([T11, jnp.zeros_like(T11)], axis=-1),
             jnp.concatenate([T21, T22], axis=-1)], axis=-2)
        s *= 2
    return T[..., 0, :, :]


@jax.custom_vjp
def _unit_lower_inverse(A):
    """T = (I + A)^-1 for strictly lower triangular A [..., C, C], in f32,
    by (blocked) forward substitution. (The product form
    `prod_k (I + (-A)^(2^k))` takes ten products and no dependent step, and
    is NOT usable: where a chunk's keys align and beta nears 1 the powers
    of `A` reach 1e17 and cancel to a result of order 1; in f32 the rule's
    state then overflows inside a sequence, which is how this cell's loss
    went to NaN about thirty steps into training.) Its backward is the
    inverse's own, `dA = -T^T dT T^T` (from `dT = -T dA T`): two products,
    and nothing of the substitution is differentiated."""
    return _blocked_inverse(A)


def _inverse_fwd(A):
    T = _blocked_inverse(A)
    return T, T


def _inverse_bwd(T, dT):
    Tt = jnp.swapaxes(T, -1, -2)
    return (-jnp.matmul(jnp.matmul(Tt, dT, precision=HIGHEST), Tt,
                        precision=HIGHEST),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _segment(S0, q, k, v, g, beta, chunk: int, cdt):
    """One segment (a whole number of chunks) from the state `S0`
    [B, H, dk, dv]: (o [B, Ts, H, dv] f32, the state at its end)."""
    f32 = jnp.float32
    B, Ts, H, _ = k.shape
    dv = v.shape[-1]
    N = Ts // chunk

    def split(x):  # [B, Ts, H, ...] -> [B, H, N, C, ...]
        x = x.reshape((B, N, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qc, kc, vc = split(q), split(k), split(v)
    g_c, b_c = split(g.astype(f32)), split(beta.astype(f32))   # [B, H, N, C]

    # ---- every chunk at once
    gam = jnp.cumsum(g_c, axis=-1)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = gam[..., :, None] - gam[..., None, :]
    decay = jnp.where(i >= j, jnp.exp(jnp.where(i >= j, diff, 0.0)), 0.0)
    kk = _dot("bhnid,bhnjd->bhnij", kc, kc, cdt)
    A = jnp.where(i > j, b_c[..., :, None] * decay * kk, 0.0)
    Tm = _unit_lower_inverse(A)
    egam = jnp.exp(gam)
    U = _dot("bhnij,bhnjd->bhnid", Tm, b_c[..., None] * vc.astype(f32), cdt)
    W = _dot("bhnij,bhnjd->bhnid", Tm,
             (b_c * egam)[..., None] * kc.astype(f32), cdt)
    qk = _dot("bhnid,bhnjd->bhnij", qc, kc, cdt) * decay
    qg = qc.astype(f32) * egam[..., None]
    kg = kc.astype(f32) * jnp.exp(gam[..., -1:] - gam)[..., None]
    glast = egam[..., -1]                                     # [B, H, N]

    # ---- the chunks in order: the state
    def step(S, xs):
        U_n, W_n, qk_n, qg_n, kg_n, gl_n = xs
        delta = U_n - _dot("bhik,bhkv->bhiv", W_n, S, cdt)
        o_n = _dot("bhik,bhkv->bhiv", qg_n, S, cdt) \
            + _dot("bhij,bhjv->bhiv", qk_n, delta, cdt)
        S = gl_n[..., None, None] * S + _dot("bhik,bhiv->bhkv", kg_n, delta,
                                             cdt)
        return S, o_n

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (U, W, qk, qg, kg, glast))
    S_end, o = jax.lax.scan(step, S0, xs)
    o = jnp.moveaxis(o, 0, 2)                                 # [B, H, N, C, dv]
    return jnp.moveaxis(o, 1, 3).reshape(B, Ts, H, dv), S_end


def _segments(x, seg: int):
    """[B, T, ...] -> [T / seg, B, seg, ...]."""
    B, T = x.shape[:2]
    return jnp.moveaxis(x.reshape((B, T // seg, seg) + x.shape[2:]), 1, 0)


def _unsegment(x):
    n, B, seg = x.shape[:3]
    return jnp.moveaxis(x, 0, 1).reshape((B, n * seg) + x.shape[3:])


def _layout(T: int, chunk: int, segment: int):
    """(padded length, segment length): the sequence is padded to whole
    segments, a segment being `segment` tokens or, where the sequence is
    shorter, the sequence padded to whole chunks."""
    seg = min(-(-segment // chunk) * chunk, -(-T // chunk) * chunk)
    return -(-T // seg) * seg, seg


def _padded(xs, T: int, Tp: int):
    if Tp == T:
        return xs
    return tuple(jnp.pad(x, ((0, 0), (0, Tp - T)) + ((0, 0),) * (x.ndim - 2))
                 for x in xs)


def _forward(q, k, v, g, beta, chunk, segment, cdt):
    """(o, the state at the start of every segment)."""
    B, T, H, dk = k.shape
    Tp, seg = _layout(T, chunk, segment)
    xs = tuple(_segments(x, seg)
               for x in _padded((q, k, v, g, beta), T, Tp))

    def body(S, x):
        o, S_end = _segment(S, *x, chunk, cdt)
        return S_end, (o, S)

    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    _, (o, starts) = jax.lax.scan(body, S0, xs)
    return _unsegment(o)[:, :T], starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, segment: int = 2048,
                     compute_dtype=jnp.bfloat16):
    """The gated delta rule over whole sequences, chunked. q, k
    [B, T, H, dk] (already normalised and scaled); v [B, T, H, dv]; g (log
    decay, <= 0) and beta [B, T, H] in f32. Returns o [B, T, H, dv] f32.
    `segment` (tokens, a multiple of `chunk`) bounds what the backward
    holds at once."""
    return _forward(q, k, v, g, beta, chunk, segment, compute_dtype)[0]


def _rule_fwd(q, k, v, g, beta, chunk, segment, compute_dtype):
    o, starts = _forward(q, k, v, g, beta, chunk, segment, compute_dtype)
    # under a caller's remat the states are worth keeping: making them
    # again is a whole forward walk
    starts = checkpoint_name(starts, scopes.KEPT_GDN_STATES)
    return o, (q, k, v, g, beta, starts)


def _rule_bwd(chunk, segment, compute_dtype, res, do):
    *inputs, starts = res
    T = do.shape[1]
    Tp, seg = _layout(T, chunk, segment)
    xs = tuple(_segments(x, seg)
               for x in _padded(tuple(inputs) + (do,), T, Tp))

    def body(dS, x):
        *ins, do_seg, S_in = x
        _, vjp = jax.vjp(
            lambda S, *a: _segment(S, *a, chunk, compute_dtype), S_in, *ins)
        dS_in, *d_ins = vjp((do_seg, dS))
        return dS_in, tuple(d_ins)

    _, grads = jax.lax.scan(body, jnp.zeros_like(starts[0]),
                            xs + (starts,), reverse=True)
    return tuple(_unsegment(dx)[:, :T].astype(x.dtype)
                 for dx, x in zip(grads, inputs))


gated_delta_rule.defvjp(_rule_fwd, _rule_bwd)
