"""A token model on the embedding engine whose main mixer is a gated short
convolution: each layer's mixer is the kind `layer_types` gives, `conv` (a
gated short convolution) or `full_attention` (grouped-query attention with
its queries and keys normed per head); the first `dense_layers` layers have
a dense gated feed-forward, every other layer routes by a sigmoid with a
selection bias that a rule, and no gradient, moves (docs/conv_stack.md has
the equations and the share):

    n = rms(x; w_in)
    conv:  [B, C, x~] = n W_in                 (one product, split in order)
           v_t = sum_j w_conv[j] (B x~)_{t - (L - 1) + j}   (causal, per
                                                 channel, no bias)
           out = (C v) W_out
    attn:  q = rope(rms_h(n Wq; w_q)), k = rope(rms_h(n Wk; w_k)), v = n Wv
           out = softmax(q k^T / sqrt(D), causal) v Wo   (grouped queries,
                                                 rotary on the whole head)
    h = x + out;  m = rms(h; w_post)
    dense layer:   y = h + (silu(m Wg) * (m Wu)) Wd
    expert layer:  s = sigmoid(m Wr);  chosen = top-k of (s + b)
                   w = s[chosen] / (sum of the chosen s + renorm_eps) * scale
                   y = h + held experts(m; w, chosen)

`rms` is the plain RMS norm (weight at 1); `rms_h` normalises each head's
`D` channels. The convolution has no state, no scan and no activation: two
gates round `L` taps. The router and the selection bias' rule are
models/token_stack.py's `BiasRoutedStackLM` (with `renorm_eps` the
router's own); the stack, its remat by layer, the loss and the expert
layers' counters are `TokenStackLM`'s. A chip's share of a layer is its
`held_experts` of the router's `num_experts` outputs; the mixers are
computed whole.

Not supported: a reset of the taps or of the attention mask at a document
boundary, decode through the convolution's cache of `L` positions, an
auxiliary balancing loss, the exchange between the chips that share a
layer.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from deeprec_tpu import nn
from deeprec_tpu.models.token_stack import BiasRoutedStackLM
from deeprec_tpu.utils import scopes

MIXERS = ("conv", "full_attention")


@dataclasses.dataclass(kw_only=True)
class ConvStackLM(BiasRoutedStackLM):
    layer_types: Tuple[str, ...]     # a layer's mixer, one of MIXERS
    conv_kernel: int                 # the taps of a short convolution
    attn_heads: int
    attn_kv_heads: int
    head_dim: int
    rope_theta: float

    def __post_init__(self):
        super().__post_init__()
        if len(self.layer_types) != self.layers or set(
                self.layer_types) - set(MIXERS):
            raise ValueError(f"{self.layers} layer types of {MIXERS}; got "
                             f"{self.layer_types!r}")

    # ------------------------------------------------------------------ init

    def _init_mixer(self, ks, i: int) -> Dict:
        d, normal = self.hidden, self._normal
        if self.layer_types[i] == "conv":
            lim = 1.0 / math.sqrt(self.conv_kernel)
            return {"w_in": normal(ks[0], (d, 3 * d)),
                    "conv": jax.random.uniform(ks[2], (self.conv_kernel, d),
                                               jnp.float32, -lim, lim),
                    "w_out": normal(ks[5], (d, d))}
        H, Hkv, D = self.attn_heads, self.attn_kv_heads, self.head_dim
        return {"wq": normal(ks[0], (d, H * D)),
                "wk": normal(ks[1], (d, Hkv * D)),
                "wv": normal(ks[2], (d, Hkv * D)),
                "q_norm": jnp.ones((D,)), "k_norm": jnp.ones((D,)),
                "wo": normal(ks[5], (H * D, d))}

    def _init_moe(self, ks) -> Dict:
        return {**super()._init_moe(ks),
                "bias": jnp.zeros((self.num_experts,))}

    # ------------------------------------------------------------ the parts

    def short_conv(self, p: Dict, n):
        """The gated short convolution: n [B, T, d] (normed) -> [B, T, d]."""
        d = self.hidden
        with scopes.scope(scopes.BLOCK_SHORTCONV):
            bcx = self._mm(n, p["w_in"])                     # [B, T, 3d] f32
            with scopes.scope(scopes.SHORTCONV_GATE):
                b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
                y = (c * nn.causal_conv1d(b * x, p["conv"])).astype(
                    self.compute_dtype)
            return self._mm(y, p["w_out"])

    def attention(self, p: Dict, n):
        """Grouped-query attention, queries and keys normed per head and
        turned by rotary over the whole head: n [B, T, d] -> [B, T, d]."""
        Bn, T, _ = n.shape
        H, Hkv, D = self.attn_heads, self.attn_kv_heads, self.head_dim
        with scopes.scope(scopes.BLOCK_ATTN):
            pos = jnp.arange(T)
            q, k, v = (jnp.moveaxis(self._mm(n, p[w]).reshape(Bn, T, h, D),
                                    2, 1)                     # [B, h, T, D]
                       for w, h in (("wq", H), ("wk", Hkv), ("wv", Hkv)))
            q, k = (nn.rotary_partial(nn.rms_norm(t, p[w], self.eps), pos, D,
                                      self.rope_theta)
                    for t, w in ((q, "q_norm"), (k, "k_norm")))
            o = self.attend(q, k, v)
            return self._mm(jnp.moveaxis(o, 1, 2).reshape(Bn, T, H * D),
                            p["wo"])

    def expert_block(self, p: Dict, m):
        """m [B, T, d] (normed) -> ([B, T, d], the layer's counters, with
        `load`: the choices that fell on each of ALL the router's
        outputs)."""
        Bn, T, d = m.shape
        xt = m.reshape(Bn * T, d)
        with scopes.scope(scopes.BLOCK_MOE):
            w, e, load = self.biased_route(p, xt)
            y, counters = self.held(p["experts"], xt, w, e)
            return y.reshape(Bn, T, d), {**counters, "load": load}

    # ----------------------------------------------------------------- stack

    def _layer(self, i: int, p: Dict, x):
        mixer = (self.short_conv if self.layer_types[i] == "conv"
                 else self.attention)
        h = x + mixer(p["mixer"], self._norm(x, p["in_norm"]))
        m = self._norm(h, p["post_norm"])
        if self.is_dense(i):
            return h + self.mlp_block(p["mlp"], m), {}
        y, counters = self.expert_block(p["moe"], m)
        return h + y, counters
