"""A token model on the embedding engine whose mixer is latent attention:
keys and values are made from ONE low-rank latent a position, normed, and
a rotary key that all the heads share; queries and keys are wider
(`qk_nope_head_dim + qk_rope_head_dim`) than values (`v_head_dim`). Its
first `dense_layers` layers have a dense gated feed-forward; every other
layer routes by a sigmoid with a selection bias that a rule, and no
gradient, moves, and adds shared experts computed in full
(docs/latent_stack.md has the equations and the share):

    n = rms(x; w_in)
    q = n Wq -> (q_n, rope(q_r)) a head
    (c, k_r) = n Wkva;  c = rms(c; w_kv);  k_r = rope(k_r)   (one head)
    (k_n, v) = c Wkvb a head
    h = x + softmax((q_n k_n^T + q_r k_r^T) / sqrt(d_n + d_r), causal) v Wo
    m = rms(h; w_post)
    dense layer:   out = h + (silu(m Wg) * (m Wu)) Wd
    expert layer:  s = sigmoid(m Wr);  chosen = top-k of (s + b)
                   w = s[chosen] / (sum of the chosen s + 1e-20) * scale
                   out = h + held experts(m; w, chosen) + shared(m)

`b` (a leaf `bias` of the dense tree, [router outputs], starting at 0) is
cut off from the gradient; after every step `after_update` (the trainer's
hook for a leaf a rule owns) moves it by `b_j += gamma sign(mean(c) - c_j)`
where `c_j` is how many of the step's choices fell on output `j`, held here
or not: the router and the rule are models/token_stack.py's
`BiasRoutedStackLM`, which models/mamba_stack.py shares. `rms` is the plain
RMS norm (weight at 1). The stack, its remat by layer, the loss and the
expert layers' counters are models/token_stack.py's.

The flash kernels take the 192-wide queries and keys whole and the 128-wide
values beside them (ops/flash_attention.py; docs/attention.md says why not
as two products): the shared rotary key is repeated to the heads before the
call, 64 columns of each key.

Not supported: a query-side low-rank projection, rotary scaling, routing
limited to groups, a sequence-wise balancing loss, multi-token prediction,
a mask reset at a document boundary, decode through a cache of latents,
the exchange between the chips that share a layer.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax.numpy as jnp

from deeprec_tpu import nn
from deeprec_tpu.models.token_stack import BiasRoutedStackLM
from deeprec_tpu.utils import scopes


@dataclasses.dataclass(kw_only=True)
class LatentStackLM(BiasRoutedStackLM):
    attn_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float
    shared_expert_width: int         # all the shared experts, fused

    def _init_mixer(self, ks, i: int) -> Dict:
        d, H, normal = self.hidden, self.attn_heads, self._normal
        dn, dr, dv, r = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                         self.v_head_dim, self.kv_lora_rank)
        return {"wq": normal(ks[0], (d, H * (dn + dr))),
                "wkva": normal(ks[1], (d, r + dr)),
                "kv_norm": jnp.ones((r,)),
                "wkvb": normal(ks[2], (r, H * (dn + dv))),
                "wo": normal(ks[5], (H * dv, d))}

    def _init_moe(self, ks) -> Dict:
        return {**super()._init_moe(ks),
                "bias": jnp.zeros((self.num_experts,)),
                "shared": self._init_swiglu(ks[10:13],
                                            self.shared_expert_width)}

    # ------------------------------------------------------------ the parts

    def attention(self, p: Dict, n):
        """The mixer: n [B, T, d] (normed) -> [B, T, d]."""
        B, T, _ = n.shape
        H, dn, dr, dv, r = (self.attn_heads, self.qk_nope_head_dim,
                            self.qk_rope_head_dim, self.v_head_dim,
                            self.kv_lora_rank)
        with scopes.scope(scopes.BLOCK_ATTN):
            pos = jnp.arange(T)
            q = jnp.moveaxis(self._mm(n, p["wq"]).reshape(B, T, H, dn + dr),
                             2, 1)                          # [B, H, T, 192]
            q = jnp.concatenate(
                [q[..., :dn], nn.rotary_partial(q[..., dn:], pos, dr,
                                                self.rope_theta)], axis=-1)
            ckr = self._mm(n, p["wkva"])                    # [B, T, r + dr]
            c = nn.rms_norm(ckr[..., :r], p["kv_norm"], self.eps)
            k_r = nn.rotary_partial(ckr[:, None, :, r:], pos, dr,
                                    self.rope_theta)        # [B, 1, T, dr]
            kv = jnp.moveaxis(self._mm(c, p["wkvb"]).reshape(
                B, T, H, dn + dv), 2, 1)                    # [B, H, T, 256]
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_r, (B, H, T, dr))], axis=-1)
            with scopes.scope(scopes.ATTN_LATENT):
                o = self.attend(q, k, kv[..., dn:])         # [B, H, T, dv]
            return self._mm(jnp.moveaxis(o, 1, 2).reshape(B, T, H * dv),
                            p["wo"])

    def expert_block(self, p: Dict, m):
        """m [B, T, d] (normed) -> ([B, T, d], the layer's counters, with
        `load`: the choices that fell on each of ALL the router's
        outputs)."""
        B, T, d = m.shape
        xt = m.reshape(B * T, d)
        with scopes.scope(scopes.BLOCK_MOE):
            w, e, load = self.biased_route(p, xt)
            y, counters = self.held(p["experts"], xt, w, e)
            with scopes.scope(scopes.MOE_SHARED):
                s = p["shared"]
                y = y + nn.swiglu_apply(xt, s["wg"], s["wu"], s["wd"],
                                        self.compute_dtype)
            return y.reshape(B, T, d), {**counters, "load": load}

    # ----------------------------------------------------------------- stack

    def _layer(self, i: int, p: Dict, x):
        h = x + self.attention(p["mixer"], self._norm(x, p["in_norm"]))
        m = self._norm(h, p["post_norm"])
        if self.is_dense(i):
            return h + self.mlp_block(p["mlp"], m), {}
        y, counters = self.expert_block(p["moe"], m)
        return h + y, counters
