"""A token model on the embedding engine whose layers mix two kinds of
causal attention, given layer by layer: inside a sliding window where
`sliding_window_layout[i]` and over the whole sequence elsewhere, with rotary
positions on the whole head where `rope_layout[i]` and NO position encoding
elsewhere; whose router reads the mixer's own normed input, BEFORE the
mixer; and whose experts gate with ReLU, with no shared expert
(docs/window_stack.md has the equations and the share):

    n = rms(x; w_in);  (w, e) = route(n);  h = x + attention_i(n)
    y = h + held experts(rms(h; w_post); w, e)

`rms` is the plain RMS norm (weight initialised at 1). The stack, its remat
by layer, the loss and the expert layer's counters are
models/token_stack.py's, shared with models/hybrid_stack.py.

Not supported: a mask reset at a document boundary, secondary experts, an
auxiliary load-balancing loss, the exchange between the chips that share a
layer.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from deeprec_tpu import nn
from deeprec_tpu.models.token_stack import TokenStackLM
from deeprec_tpu.utils import scopes


@dataclasses.dataclass(kw_only=True)
class WindowStackLM(TokenStackLM):
    # attention, layer by layer: an entry a layer
    sliding_window_layout: Tuple[int, ...]
    rope_layout: Tuple[int, ...]
    sliding_window: int
    attn_heads: int
    attn_kv_heads: int
    head_dim: int
    rope_theta: float

    def is_window(self, i: int) -> bool:
        return bool(self.sliding_window_layout[i])

    def has_rope(self, i: int) -> bool:
        return bool(self.rope_layout[i])

    def _init_mixer(self, ks, i: int) -> Dict:
        d, H, Hkv, D = (self.hidden, self.attn_heads, self.attn_kv_heads,
                        self.head_dim)
        return {"wq": self._normal(ks[0], (d, H * D)),
                "wk": self._normal(ks[1], (d, Hkv * D)),
                "wv": self._normal(ks[2], (d, Hkv * D)),
                "wo": self._normal(ks[5], (H * D, d))}

    # ------------------------------------------------------------ the parts

    def attention(self, i: int, p: Dict, n):
        """Layer i's mixer: n [B, T, d] (normed) -> [B, T, d]."""
        B, T, _ = n.shape
        H, Hkv, D = self.attn_heads, self.attn_kv_heads, self.head_dim
        with scopes.scope(scopes.BLOCK_ATTN):
            q, k, v = (jnp.moveaxis(self._mm(n, p[w]).reshape(B, T, h, D),
                                    2, 1)                     # [B, h, T, D]
                       for w, h in (("wq", H), ("wk", Hkv), ("wv", Hkv)))
            if self.has_rope(i):
                pos = jnp.arange(T)
                q = nn.rotary_partial(q, pos, D, self.rope_theta)
                k = nn.rotary_partial(k, pos, D, self.rope_theta)
            windowed = self.is_window(i)
            with scopes.scope(scopes.ATTN_WINDOW if windowed
                              else scopes.ATTN_GLOBAL):
                o = self.attend(q, k, v,
                                self.sliding_window if windowed else None)
            return self._mm(jnp.moveaxis(o, 1, 2).reshape(B, T, H * D),
                            p["wo"])

    def routed(self, p: Dict, n):
        """The early router: n [B, T, d], the MIXER's normed input ->
        (weights, experts) [B x T, top_k]. It runs before attention and
        stands under the expert block's scopes all the same."""
        with scopes.scope(scopes.BLOCK_MOE):
            return self.route(p["router"], n.reshape(-1, n.shape[-1]))

    def expert_block(self, p: Dict, m, w, e):
        """m [B, T, d] (the post-attention norm), routed earlier as (w, e)
        -> ([B, T, d], the layer's counters): the held experts' part,
        ReLU-gated, and nothing else."""
        with scopes.scope(scopes.BLOCK_MOE):
            y, counters = self.held(p["experts"], m.reshape(-1, m.shape[-1]),
                                    w, e, jax.nn.relu, count_live=True)
            return y.reshape(m.shape), counters

    def _layer(self, i: int, p: Dict, x):
        n = self._norm(x, p["in_norm"])
        w, e = self.routed(p["moe"], n)
        h = x + self.attention(i, p["mixer"], n)
        y, counters = self.expert_block(p["moe"],
                                        self._norm(h, p["post_norm"]), w, e)
        return h + y, counters
