"""A hybrid token model on the embedding engine: a stack whose layer `i` is
gated full attention where `(i + 1) % full_attention_interval == 0` and a
gated delta net otherwise, every layer's feed-forward a block of sparse
experts of which THIS chip holds a range (`held_experts`) beside a shared
expert, behind one hashed token table and in front of an untied head
(docs/hybrid_stack.md has the equations and the share).

    h = x + mixer_i(rms0(x; w_in));  y = h + moe(rms0(h; w_post))

`rms0` is the zero-centred RMS norm (`nn.rms_norm(..., zero_centred=True)`).
Products take bf16 operands and accumulate in f32; norms, softmaxes, the
router, the delta rule's state and gates and the loss are f32; the residual
stream is f32. The stack, its remat by layer, the loss and the expert
layer's counters are models/token_stack.py's, which models/window_stack.py
(window and global attention layers, the router before the mixer) shares.

Not supported: a state reset at a document boundary (delta rule and
convolution run through the whole sequence), a multi-token-prediction head,
an auxiliary load-balancing loss, the exchange between the chips that share
a layer.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from deeprec_tpu import nn
from deeprec_tpu.models.token_stack import TokenStackLM
from deeprec_tpu.ops.gated_delta import gated_delta_rule
from deeprec_tpu.utils import scopes


@dataclasses.dataclass(kw_only=True)
class HybridStackLM(TokenStackLM):
    hidden: int = 2048
    layers: int = 4
    full_attention_interval: int = 4
    # gated delta net
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    conv_kernel: int = 4
    # gated full attention
    attn_heads: int = 16
    attn_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # experts
    num_experts: int = 512
    experts_per_token: int = 10
    expert_width: int = 512
    shared_expert_width: int = 512
    held_experts: Tuple[int, int] = (0, 32)   # (first, count) held here
    chunk: int = 64                  # the delta rule's chunk
    segment: int = 2048              # ... and what its backward holds at once
    zero_centred_norms = True

    def __post_init__(self):
        super().__post_init__()
        self.key_dim = self.gdn_key_heads * self.gdn_key_dim
        self.value_dim = self.gdn_value_heads * self.gdn_value_dim
        self.rot_dim = int(self.head_dim * self.partial_rotary_factor)

    def is_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    # ------------------------------------------------------------------ init

    def _init_mixer(self, ks, i: int) -> Dict:
        d, normal = self.hidden, self._normal
        if self.is_attention(i):
            H, Hkv, D = self.attn_heads, self.attn_kv_heads, self.head_dim
            return {"wq": normal(ks[0], (d, H * 2 * D)),
                    "wk": normal(ks[1], (d, Hkv * D)),
                    "wv": normal(ks[2], (d, Hkv * D)),
                    "q_norm": jnp.zeros((D,)), "k_norm": jnp.zeros((D,)),
                    "wo": normal(ks[5], (H * D, d))}
        Hv, K = self.gdn_value_heads, self.conv_kernel
        conv_dim = 2 * self.key_dim + self.value_dim
        lim = 1.0 / math.sqrt(K)
        dt = jnp.exp(jax.random.uniform(ks[4], (Hv,))
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        dt = jnp.maximum(dt, 1e-4)
        return {"qkvz": normal(ks[0], (d, 2 * self.key_dim
                                       + 2 * self.value_dim)),
                "ba": normal(ks[1], (d, 2 * Hv)),
                "conv": jax.random.uniform(ks[2], (K, conv_dim), jnp.float32,
                                           -lim, lim),
                "A_log": jnp.log(jax.random.uniform(ks[3], (Hv,), jnp.float32,
                                                    1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": jnp.ones((self.gdn_value_dim,)),
                "wo": normal(ks[5], (self.value_dim, d))}

    def _init_moe(self, ks) -> Dict:
        d, fs, normal = self.hidden, self.shared_expert_width, self._normal
        return {**super()._init_moe(ks),
                "shared": {"wg": normal(ks[10], (d, fs)),
                           "wu": normal(ks[11], (d, fs)),
                           "wd": normal(ks[12], (fs, d))},
                "shared_gate": normal(ks[13], (d, 1))}

    # ---------------------------------------------------------------- mixers

    def gated_delta_net(self, p: Dict, x):
        """x [B, T, d] (normed) -> [B, T, d]."""
        B, T, _ = x.shape
        Hk, Hv = self.gdn_key_heads, self.gdn_value_heads
        dk, dv = self.gdn_key_dim, self.gdn_value_dim
        r = Hv // Hk
        with scopes.scope(scopes.BLOCK_GDN):
            # per key head: [q, k, v of its r value heads, z of the same]
            # activations between the products in the compute dtype; the
            # gates stay f32
            cdt = self.compute_dtype
            qkvz = self._mm(x, p["qkvz"]).astype(cdt).reshape(
                B, T, Hk, 2 * dk + 2 * r * dv)
            ba = self._mm(x, p["ba"]).reshape(B, T, Hk, 2 * r)
            q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
            v = qkvz[..., 2 * dk:2 * dk + r * dv]
            z = qkvz[..., 2 * dk + r * dv:].reshape(B, T, Hv, dv)
            b = ba[..., :r].reshape(B, T, Hv)
            a = ba[..., r:].reshape(B, T, Hv)
            u = jnp.concatenate([q.reshape(B, T, -1), k.reshape(B, T, -1),
                                 v.reshape(B, T, -1)], axis=-1)
            u = jax.nn.silu(nn.causal_conv1d(u, p["conv"]))
            q = u[..., :self.key_dim].reshape(B, T, Hk, dk)
            k = u[..., self.key_dim:2 * self.key_dim].reshape(B, T, Hk, dk)
            v = u[..., 2 * self.key_dim:].reshape(B, T, Hv, dv)
            beta = jax.nn.sigmoid(b)
            g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
            # normalised at the key heads, then repeated: the same vectors
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
                * (dk ** -0.5)
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
            with scopes.scope(scopes.GDN_RULE):
                o = gated_delta_rule(q.astype(cdt), k.astype(cdt),
                                     v.astype(cdt), g, beta, self.chunk,
                                     self.segment, cdt)
            o = nn.rms_norm(o, p["norm"], self.eps) \
                * jax.nn.silu(z.astype(jnp.float32))
            return self._mm(o.reshape(B, T, Hv * dv), p["wo"])

    def gated_attention(self, p: Dict, x):
        """x [B, T, d] (normed) -> [B, T, d]."""
        B, T, _ = x.shape
        H, Hkv, D = self.attn_heads, self.attn_kv_heads, self.head_dim
        with scopes.scope(scopes.BLOCK_ATTN):
            cdt = self.compute_dtype
            qg = self._mm(x, p["wq"]).astype(cdt).reshape(B, T, H, 2 * D)
            q, gate = qg[..., :D], qg[..., D:].reshape(B, T, H * D)
            k = self._mm(x, p["wk"]).astype(cdt).reshape(B, T, Hkv, D)
            v = self._mm(x, p["wv"]).astype(cdt).reshape(B, T, Hkv, D)
            q = nn.rms_norm(q, p["q_norm"], self.eps, zero_centred=True)
            k = nn.rms_norm(k, p["k_norm"], self.eps, zero_centred=True)
            pos = jnp.arange(T)
            q, k, v = (jnp.moveaxis(t, 2, 1) for t in (q, k, v))  # [B,H,T,D]
            q = nn.rotary_partial(q, pos, self.rot_dim, self.rope_theta)
            k = nn.rotary_partial(k, pos, self.rot_dim, self.rope_theta)
            o = self.attend(q, k, v)
            o = jnp.moveaxis(o, 1, 2).reshape(B, T, H * D).astype(jnp.float32)
            return self._mm(o * jax.nn.sigmoid(gate.astype(jnp.float32)),
                            p["wo"])

    def expert_block(self, p: Dict, x):
        """x [B, T, d] (normed) -> ([B, T, d], the layer's counters)."""
        B, T, d = x.shape
        xt = x.reshape(B * T, d)
        with scopes.scope(scopes.BLOCK_MOE):
            w, e = self.route(p["router"], xt)
            y, counters = self.held(p["experts"], xt, w, e)
            s = p["shared"]
            shared = nn.swiglu_apply(xt, s["wg"], s["wu"], s["wd"],
                                     self.compute_dtype)
            y = y + jax.nn.sigmoid(self._mm(xt, p["shared_gate"])) * shared
            return y.reshape(B, T, d), counters

    # ----------------------------------------------------------------- stack

    def _layer(self, i: int, p: Dict, x):
        mixer = self.gated_attention if self.is_attention(i) \
            else self.gated_delta_net
        h = x + mixer(p["mixer"], self._norm(x, p["in_norm"]))
        y, counters = self.expert_block(p["moe"],
                                        self._norm(h, p["post_norm"]))
        return h + y, counters
