"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long sequences shard over the `sp` axis; each device holds its local Q/K/V
slice and K/V blocks rotate around the ring via lax.ppermute (XLA lowers the
rotation to ICI neighbor transfers that overlap with the local attention
compute). Online-softmax accumulation keeps the math exact across steps —
this is standard ring attention, giving O(L/P) activation memory per device
and near-linear scaling of context length with ring size.

DeepRec has no sequence parallelism (SURVEY.md §5: "long-context: not
present") — this is a capability the TPU framework adds because long
behavior histories (SIM-style) need it at scale.

Call inside shard_map with Q/K/V sharded on the sequence axis:
    shard_map(..., in_specs=P(None, None, 'sp', None))(ring_attention)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def ring_attention(
    q: jnp.ndarray,  # [B, H, Lq_local, D]
    k: jnp.ndarray,  # [B, H, S_local, D]
    v: jnp.ndarray,  # [B, H, S_local, D]
    mask: jnp.ndarray,  # [B, S_local] bool
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Exact attention over the full (sharded) sequence. Differentiable via
    autodiff through the ppermute ring (grads flow the reverse ring)."""
    B, H, Lq, D = q.shape
    S = k.shape[2]
    P = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32)

    # Global positions of the local Q rows (for causal masking across shards).
    qpos = me * Lq + jax.lax.broadcasted_iota(jnp.int32, (Lq, S), 0)

    def step(carry, r):
        m, l, acc, ks, vs, mk, src = carry
        # src = shard that originally owned the current K/V block
        s = jnp.einsum("bhld,bhsd->bhls", qf, ks.astype(jnp.float32)) * scale
        s = jnp.where(mk[:, None, None, :], s, NEG_INF)
        if causal:
            kpos = src * S + jax.lax.broadcasted_iota(jnp.int32, (Lq, S), 1)
            s = jnp.where((kpos <= qpos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "bhls,bhsd->bhld", p, vs.astype(jnp.float32)
        )
        # rotate K/V/mask/owner one hop around the ring
        perm = [(i, (i + 1) % P) for i in range(P)]
        ks = jax.lax.ppermute(ks, axis_name, perm)
        vs = jax.lax.ppermute(vs, axis_name, perm)
        mk = jax.lax.ppermute(mk, axis_name, perm)
        src = jax.lax.ppermute(src, axis_name, perm)
        return (m_new, l, acc, ks, vs, mk, src), None

    m0 = jnp.full((B, H, Lq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Lq, 1), jnp.float32)
    a0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    carry = (m0, l0, a0, k, v, mask, me)
    (m, l, acc, *_), _ = jax.lax.scan(step, carry, jnp.arange(P))
    l_safe = jnp.maximum(l, 1e-30)
    return (acc / l_safe).astype(q.dtype)


def ring_attention_sharded(
    mesh, q, k, v, mask, axis: str = "sp", causal: bool = False,
):
    """Convenience wrapper: shard_map over `axis` with Q/K/V/mask sequence-
    sharded, output sequence-sharded."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(ring_attention, axis_name=axis, causal=causal)
    seq = P(None, None, axis, None)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(seq, seq, seq, P(None, axis)),
        out_specs=seq,
        check_vma=False,
    )(q, k, v, mask)
