"""Flash attention: Pallas TPU forward + memory-efficient blockwise backward.

Long-context support is first-class in this framework (BST/SIM-style long
behavior histories; DeepRec itself has no attention sharding — SURVEY.md §5).
The forward pass is a classic online-softmax Pallas kernel: Q blocks stream
from HBM to VMEM, K/V blocks iterate in-kernel, running (max, denom, acc)
carry the softmax — O(L·block) VMEM instead of the O(L²) score matrix. The
backward is Pallas too (flash-2 structure, exact gradients from the saved
LSE): a dK/dV kernel where each K/V block accumulates over streamed Q
blocks in VMEM scratch, and a dQ kernel with the forward's access pattern —
no atomics, no [L, S] materialization, blocks above the causal diagonal
(and outside a sliding window) neither multiplied nor fetched.

Grouped-query attention: k and v may carry fewer heads than q (`H` a
multiple of `Hkv`); query head `h` reads key/value head `h // (H / Hkv)`
through the kernels' index maps (no repeated copy of k or v is made), and
the dK/dV kernel walks the Q blocks of all the query heads of its group.
The products take their operands in the dtype they are handed (bf16
operands run the MXU at its bf16 rate; f32 operands as before), always
accumulating in f32; softmax statistics are f32.

A value head dim of its own: q and k share one head dim `D`, v (and so
o, do and dv) may have another, `Dv` (latent attention scores with
192-wide queries and keys and sums 128-wide values). Every block is whole in
its last axis, so neither width need be a multiple of the 128-lane tile;
where `Dv = D` the program is the one it was.

A sliding window (`window=W`, causal only): query `t` sees the keys
`s <= t` with `t - s < W`. The kernels' minor grid axis is then as long as
the most blocks any one block can see, each walk starts at the first block
its owner can see, and a step past the last one is clamped onto that last
block: the pipeline fetches a block only when its index changes, so a block
wholly outside the window (or, window or not, above the causal diagonal)
costs neither products nor a DMA, only an empty grid step.

On non-TPU backends the kernels run in interpreter mode (tests) or fall
back to a blockwise lax.scan implementation with the same memory shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from deeprec_tpu.utils import backend, scopes

NEG_INF = -1e30


# ------------------------------------------------------------ reference impl


def attention_reference(q, k, v, mask=None, causal=False, sm_scale=None,
                        window=None):
    """Plain jnp attention (oracle + CPU fallback). q: [B, H, L, D];
    k: [B, Hkv, S, D], v: [B, Hkv, S, Dv] with H a multiple of Hkv
    -> [B, H, L, Dv]."""
    _check_window(causal, window)
    B, H, Lq, D = q.shape
    S = k.shape[2]
    k, v = _repeat_kv(k, H), _repeat_kv(v, H)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    logits = jnp.einsum("bhld,bhsd->bhls", q, k) * scale
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (Lq, S), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (Lq, S), 1)
        logits = jnp.where(_seen(qi, ki, window)[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhls,bhsd->bhld", p, v)


def _repeat_kv(x, heads: int):
    """[B, Hkv, S, D] -> [B, heads, S, D], each key/value head serving
    `heads / Hkv` consecutive query heads (the non-kernel paths' form)."""
    g = heads // x.shape[1]
    return x if g == 1 else jnp.repeat(x, g, axis=1)


def _check_window(causal, window):
    if window is not None and (not causal or window < 1):
        raise ValueError("a sliding window is causal and at least 1 wide; "
                         f"got causal={causal}, window={window}")


def _seen(qpos, kpos, window):
    """The causal (and window) mask, wherever one is made."""
    seen = kpos <= qpos
    return seen if window is None else seen & (qpos - kpos < window)


# ------------------------------------------------- which blocks see which


def _visible_keys(qb, block_q, block_k, num_kb, causal, window, xp=jnp):
    """(first, last) key block any row of query block `qb` sees. `xp` is
    `jnp` for a traced `qb` (an index map, a kernel) and `np` for the
    static count of the walk's steps (`_walk`)."""
    first = 0 * qb if window is None else \
        xp.maximum(qb * block_q - window + 1, 0) // block_k
    last = ((qb + 1) * block_q - 1) // block_k if causal \
        else 0 * qb + num_kb - 1
    return first, xp.minimum(last, num_kb - 1)


def _visible_queries(kb, block_q, block_k, num_qb, causal, window, xp=jnp):
    """(first, last) query block any row of which sees key block `kb`:
    the walk of `_visible_keys` from the other side."""
    first = (kb * block_k) // block_q if causal else 0 * kb
    last = 0 * kb + num_qb - 1 if window is None else \
        ((kb + 1) * block_k + window - 2) // block_q
    return xp.minimum(first, num_qb - 1), xp.minimum(last, num_qb - 1)


def _walk(visible, owners: int, *args):
    """(steps, block) of the kernels' minor grid axis, for `visible` one of
    the two functions above and `owners` the blocks that walk: `steps` the
    most blocks any owner sees (static), `block(owner, step)` the block an
    index map names at a step: the owner's first visible block onward, a
    step past its last clamped onto the last (no new index, so no DMA)."""
    first, last = visible(np.arange(owners), *args, xp=np)
    steps = int(np.max(last - first)) + 1  # noqa: DRT002 — numpy on static block counts at trace time, no device value

    def block(owner, step):
        first, last = visible(owner, *args)
        return jnp.minimum(first + step, last)

    return steps, block


# ------------------------------------------------------------- pallas forward


def _masked_scores(q, k, mk, qb, kb, block_q, block_k, sm_scale, causal,
                   window):
    """Scaled QK^T with padding + causal + window masking — the one
    definition all three kernels (fwd, dKdV, dQ) share; a drift here would
    silently desynchronize forward and backward. Inlines at trace time.
    q [block_q, D], k [block_k, D] (their own dtype, f32 accumulation),
    mk [1, block_k] int; qb/kb are the Q/K *block* indices."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mk > 0, s, NEG_INF)
    if causal:
        qpos = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(_seen(qpos, kpos, window), s, NEG_INF)
    return s


def _ds_from_p(p, do, v, delta, sm_scale):
    """dS = P ∘ (dO·Vᵀ − Δ)·scale — shared by both backward kernels.
    delta is a [block_q, 1] column."""
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p * (dp - delta) * sm_scale


def _probs_from_lse(s, lse):
    """exp(s − LSE) with the dead-row guard: a row whose visible keys are
    ALL masked stores lse ≈ NEG_INF, and exp(NEG_INF − NEG_INF) = 1 would
    broadcast garbage into dk/dv/dq — such rows attend to nothing, so
    their probabilities are exactly zero. Shared by every backward path;
    lse carries a trailing singleton axis (a column per query row)."""
    return jnp.where(lse <= NEG_INF * 0.5, 0.0, jnp.exp(s - lse))


def _fa_fwd_kernel(
    q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
    block_k: int, sm_scale: float, causal: bool, block_q: int, num_kb: int,
    steps: int, window,
):
    """Grid = (BH, Lq/block_q, steps); only ONE K/V block is resident in
    VMEM per step (O(block) memory), the (m, l, acc) running softmax lives in
    scratch that persists across the sequential grid steps of the walk over
    the key blocks this query block sees."""
    from jax.experimental import pallas as pl

    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qb = pl.program_id(1)
    # K blocks above the diagonal or wholly outside the window contribute
    # nothing: the walk starts at the first block seen, and a step past the
    # last one runs no product (its index map repeats the last block, so it
    # fetches nothing either).
    first, last = _visible_keys(qb, block_q, block_k, num_kb, causal, window)
    kb = first + j

    @pl.when(kb <= last)
    def _step():
        q = q_ref[0]  # [block_q, D]
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]  # [block_k, Dv]
        mk = mask_ref[0]  # [1, block_k]
        s = _masked_scores(q, k, mk, qb, kb, block_q, block_k, sm_scale,
                           causal, window)
        m = m_scr[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    @pl.when(j == steps - 1)
    def _finish():
        l_safe = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _pallas_forward(q, k, v, mask, causal, sm_scale, block_q, block_k,
                    interpret, window):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Lq, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // Hkv  # query heads a key/value head
    BH = B * H
    qr = q.reshape(BH, Lq, D)
    kr = k.reshape(B * Hkv, S, D)
    vr = v.reshape(B * Hkv, S, Dv)
    # Mosaic needs the last two dims of a block (8, 128)-aligned or whole,
    # which a (1, block) block over a 2-D array is not: the key mask rides
    # as [B*Hkv, 1, S] rows and the LSE as [BH, Lq, 1] columns — also the
    # shapes the kernels consume them in.
    maskr = jnp.repeat(mask.astype(jnp.int32), Hkv, axis=0)[:, None, :]

    num_qb, num_kb = Lq // block_q, S // block_k
    steps, key_block = _walk(_visible_keys, num_qb, block_q, block_k, num_kb,
                             causal, window)
    grid = (BH, num_qb, steps)
    kernel = functools.partial(
        _fa_fwd_kernel, block_k=block_k, sm_scale=sm_scale, causal=causal,
        block_q=block_q, num_kb=num_kb, steps=steps, window=window,
    )
    kv = lambda b, i, j: (b // G, key_block(i, j), 0)  # noqa: E731
    with scopes.kernel_trace(scopes.KERNEL_FLASH_FWD):
        o, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), kv),
                pl.BlockSpec((1, block_k, Dv), kv),
                pl.BlockSpec((1, 1, block_k),
                             lambda b, i, j: (b // G, 0, key_block(i, j))),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Lq, Dv), q.dtype),
                jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ],
            interpret=interpret,
            name=scopes.KERNEL_FLASH_FWD,
        )(qr, kr, vr, maskr)
    return o.reshape(B, H, Lq, Dv), lse.reshape(B, H, Lq)


# --------------------------------------------------- blockwise jnp fwd (lse)


def _blockwise_forward(q, k, v, mask, causal, sm_scale, block_k, window):
    """Same math as the kernel, in scanned jnp — the forward on non-TPU
    backends."""
    B, H, Lq, D = q.shape
    S = k.shape[2]
    k, v = _repeat_kv(k, H), _repeat_kv(v, H)
    nb = S // block_k
    qpos = jax.lax.broadcasted_iota(jnp.int32, (Lq, block_k), 0)

    def body(carry, kb):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, kb * block_k, block_k, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(v, kb * block_k, block_k, axis=2)
        mk = jax.lax.dynamic_slice_in_dim(mask, kb * block_k, block_k, axis=1)
        s = jnp.einsum("bhld,bhsd->bhls", q, ks) * sm_scale
        s = jnp.where(mk[:, None, None, :], s, NEG_INF)
        if causal:
            kpos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (Lq, block_k), 1
            )
            s = jnp.where(_seen(qpos, kpos, window)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bhls,bhsd->bhld", p, vs)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Lq, 1), NEG_INF, jnp.float32)  # noqa: DRT003 — keepdims accumulator for the scan's broadcast; one padded sublane, Pallas path owns the real layout
    l0 = jnp.zeros((B, H, Lq, 1), jnp.float32)  # noqa: DRT003 — keepdims accumulator, same contract as m0 above
    a0 = jnp.zeros((B, H, Lq, v.shape[3]), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nb))
    l_safe = jnp.maximum(l, 1e-30)
    o = (acc / l_safe).astype(q.dtype)
    lse = m[..., 0] + jnp.log(l_safe[..., 0])
    return o, lse


# ---------------------------------------------------------- pallas backward


def _fa_bwd_dkdv_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr, *,
    block_q: int, block_k: int, sm_scale: float, causal: bool, num_qb: int,
    steps: int, group: int, window,
):
    """dK/dV: grid = (B*Hkv, S/block_k, group * steps). One K/V block owns
    the kernel instance; the Q blocks that see it, of every query head of
    its group, stream through the sequential minor grid axis, accumulating
    dk/dv in VMEM scratch (flash-2 structure: no atomics, no [L, S]
    materialization)."""
    from jax.experimental import pallas as pl

    t = pl.program_id(2)
    kb = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # The backward mirror of the forward's walk: a head's steps start at the
    # first Q block that sees this K block (the diagonal's) and stop at the
    # last one the window lets see it; a step past it runs and fetches
    # nothing.
    first, last = _visible_queries(kb, block_q, block_k, num_qb, causal,
                                   window)
    qb = first + t % steps

    @pl.when(qb <= last)
    def _step():
        q = q_ref[0]                           # [block_q, D]
        k = k_ref[0]                           # [block_k, D]
        v = v_ref[0]                           # [block_k, Dv]
        do = do_ref[0]                         # [block_q, Dv]
        lse = lse_ref[0]                       # [block_q, 1]
        delta = delta_ref[0]
        mk = mask_ref[0]                       # [1, block_k]
        s = _masked_scores(q, k, mk, qb, kb, block_q, block_k, sm_scale,
                           causal, window)
        p = _probs_from_lse(s, lse)            # exact probs from saved LSE
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = _ds_from_p(p, do, v, delta, sm_scale)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == group * steps - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_scr, *,
    block_q: int, block_k: int, sm_scale: float, causal: bool, num_kb: int,
    steps: int, window,
):
    """dQ: grid = (BH, Lq/block_q, steps), accumulating over the K blocks
    this Q block sees in scratch — the forward kernel's access pattern with
    ds in place of p."""
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    first, last = _visible_keys(qb, block_q, block_k, num_kb, causal, window)
    kb = first + j

    @pl.when(kb <= last)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        mk = mask_ref[0]
        s = _masked_scores(q, k, mk, qb, kb, block_q, block_k, sm_scale,
                           causal, window)
        p = _probs_from_lse(s, lse)
        ds = _ds_from_p(p, do, v, delta, sm_scale)
        dq_scr[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)

    @pl.when(j == steps - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _pallas_backward(q, k, v, mask, causal, sm_scale, block_q, block_k,
                     o, lse, do, interpret, window):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Lq, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // Hkv
    BH, BK = B * H, B * Hkv
    qr = q.reshape(BH, Lq, D)
    kr = k.reshape(BK, S, D)
    vr = v.reshape(BK, S, Dv)
    dor = do.astype(q.dtype).reshape(BH, Lq, Dv)
    lser = lse.astype(jnp.float32).reshape(BH, Lq, 1)
    maskr = jnp.repeat(mask.astype(jnp.int32), Hkv, axis=0)[:, None, :]
    # delta = rowsum(do * o): cheap elementwise+reduce, XLA fuses it; the
    # kernels read it per Q block.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).reshape(BH, Lq, 1)

    num_qb, num_kb = Lq // block_q, S // block_k
    qspec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    dospec = pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0))
    common = dict(interpret=interpret)

    q_steps, query_block = _walk(_visible_queries, num_kb, block_q, block_k,
                                 num_qb, causal, window)
    dkdv_kernel = functools.partial(
        _fa_bwd_dkdv_kernel, block_q=block_q, block_k=block_k,
        sm_scale=sm_scale, causal=causal, num_qb=num_qb, steps=q_steps,
        group=G, window=window,
    )

    def q_side(b, kb, t):  # step t % q_steps of query head t // q_steps
        return (b * G + t // q_steps, query_block(kb, t % q_steps), 0)

    with scopes.kernel_trace(scopes.KERNEL_FLASH_BWD_DKDV):
        dk, dv = pl.pallas_call(
            dkdv_kernel,
            grid=(BK, num_kb, G * q_steps),
            in_specs=[
                pl.BlockSpec((1, block_q, D), q_side),                        # q
                pl.BlockSpec((1, block_k, D), lambda b, kb, t: (b, kb, 0)),   # k
                pl.BlockSpec((1, block_k, Dv), lambda b, kb, t: (b, kb, 0)),  # v
                pl.BlockSpec((1, 1, block_k), lambda b, kb, t: (b, 0, kb)),   # mask
                pl.BlockSpec((1, block_q, Dv), q_side),                       # do
                pl.BlockSpec((1, block_q, 1), q_side),                        # lse
                pl.BlockSpec((1, block_q, 1), q_side),                        # delta
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, D), lambda b, kb, t: (b, kb, 0)),
                pl.BlockSpec((1, block_k, Dv), lambda b, kb, t: (b, kb, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BK, S, D), k.dtype),
                jax.ShapeDtypeStruct((BK, S, Dv), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, Dv), jnp.float32),
            ],
            name=scopes.KERNEL_FLASH_BWD_DKDV,
            **common,
        )(qr, kr, vr, maskr, dor, lser, delta)

    k_steps, key_block = _walk(_visible_keys, num_qb, block_q, block_k,
                               num_kb, causal, window)
    dq_kernel = functools.partial(
        _fa_bwd_dq_kernel, block_q=block_q, block_k=block_k,
        sm_scale=sm_scale, causal=causal, num_kb=num_kb, steps=k_steps,
        window=window,
    )
    kv = lambda b, i, j: (b // G, key_block(i, j), 0)  # noqa: E731
    with scopes.kernel_trace(scopes.KERNEL_FLASH_BWD_DQ):
        (dq,) = pl.pallas_call(
            dq_kernel,
            grid=(BH, num_qb, k_steps),
            in_specs=[
                qspec,                                                        # q
                pl.BlockSpec((1, block_k, D), kv),                            # k
                pl.BlockSpec((1, block_k, Dv), kv),                           # v
                pl.BlockSpec((1, 1, block_k),
                             lambda b, i, j: (b // G, 0, key_block(i, j))),   # mask
                dospec,                                                       # do
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),     # lse
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),     # delta
            ],
            out_specs=[qspec],
            out_shape=[jax.ShapeDtypeStruct((BH, Lq, D), q.dtype)],
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            name=scopes.KERNEL_FLASH_BWD_DQ,
            **common,
        )(qr, kr, vr, maskr, dor, lser, delta)

    return (
        dq.reshape(B, H, Lq, D),
        dk.reshape(B, Hkv, S, D),
        dv.reshape(B, Hkv, S, Dv),
    )


# ------------------------------------------------------------------ backward


def _blockwise_backward(q, k, v, mask, causal, sm_scale, block_k, o, lse, do,
                        window):
    """Flash-style exact backward from the saved LSE; scans K blocks."""
    B, H, Lq, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    k, v = _repeat_kv(k, H), _repeat_kv(v, H)
    nb = S // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B,H,L]
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (Lq, block_k), 0)

    def body(dq, kb):
        ks = jax.lax.dynamic_slice_in_dim(k, kb * block_k, block_k, axis=2).astype(jnp.float32)
        vs = jax.lax.dynamic_slice_in_dim(v, kb * block_k, block_k, axis=2).astype(jnp.float32)
        mk = jax.lax.dynamic_slice_in_dim(mask, kb * block_k, block_k, axis=1)
        s = jnp.einsum("bhld,bhsd->bhls", qf, ks) * sm_scale
        s = jnp.where(mk[:, None, None, :], s, NEG_INF)
        if causal:
            kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (Lq, block_k), 1)
            s = jnp.where(_seen(qpos, kpos, window)[None, None], s, NEG_INF)
        p = _probs_from_lse(s, lse[..., None])  # exact (dead rows -> 0)
        dp = jnp.einsum("bhld,bhsd->bhls", dof, vs)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq = dq + jnp.einsum("bhls,bhsd->bhld", ds, ks)
        dk_b = jnp.einsum("bhls,bhld->bhsd", ds, qf)
        dv_b = jnp.einsum("bhls,bhld->bhsd", p, dof)
        return dq, (dk_b, dv_b)

    dq0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(body, dq0, jnp.arange(nb))
    # scan stacks blocks on axis 0: [nb, B, H, block_k, D] -> [B, H, S, D]
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(B, H, S, D)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(B, H, S, Dv)
    if Hkv != H:  # a key/value head's gradient: the sum over its group
        dk = dk.reshape(B, Hkv, H // Hkv, S, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, H // Hkv, S, Dv).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ------------------------------------------------------------------- public


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9)
)
def flash_attention(
    q, k, v, mask, causal=False, sm_scale=None, block_q=128, block_k=128,
    interpret=False, window=None,
):
    """Masked multi-head attention, O(L·block) memory.

    q: [B, H, Lq, D]; k: [B, Hkv, S, D]; v: [B, Hkv, S, Dv] (Dv = D or
    not) -> [B, H, Lq, Dv], with H a multiple of Hkv (grouped queries;
    Hkv = H is plain multi-head); mask: [B, S] bool (True = real).
    Lq/S must be multiples of the block sizes (pad outside; padded KV rows
    are masked, padded Q rows produce zeros-safe outputs). `window=W`
    (causal only): query t sees the keys s <= t with t - s < W; a block
    wholly outside it is neither multiplied nor fetched.
    """
    return _fa_impl(q, k, v, mask, causal, sm_scale, block_q, block_k,
                    interpret, window)[0]


def _fa_impl(q, k, v, mask, causal, sm_scale, block_q, block_k, interpret,
             window):
    _check_window(causal, window)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    if backend.on_tpu() or interpret:
        return _pallas_forward(q, k, v, mask, causal, scale, block_q, block_k,
                               interpret or not backend.on_tpu(), window)
    return _blockwise_forward(q, k, v, mask, causal, scale, block_k, window)


def _fa_fwd(q, k, v, mask, causal, sm_scale, block_q, block_k, interpret,
            window):
    o, lse = _fa_impl(q, k, v, mask, causal, sm_scale, block_q, block_k,
                      interpret, window)
    # a remat whose policy keeps the name (a token stack's layer) saves both,
    # and its backward does not run the forward again; any other policy
    # makes them again, as before (the name is then the identity)
    o, lse = (checkpoint_name(a, scopes.KEPT_ATTN_OUT) for a in (o, lse))
    return o, (q, k, v, mask, o, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, interpret, window, res, do):
    q, k, v, mask, o, lse = res
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    if backend.on_tpu() or interpret:
        dq, dk, dv = _pallas_backward(
            q, k, v, mask, causal, scale, block_q, block_k, o, lse, do,
            interpret or not backend.on_tpu(), window,
        )
    else:
        dq, dk, dv = _blockwise_backward(
            q, k, v, mask, causal, scale, block_k, o, lse, do, window
        )
    return dq, dk, dv, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)
