"""A token model on the embedding engine whose layer is ONE block, of the
kind a letter of `pattern` gives (`hybrid_override_pattern`): `M` a
Mamba-2 mixer, `*` causal attention with NO position encoding, `E` an
expert block whose routed experts run in a latent space
(docs/mamba_stack.md has the equations and the share):

    h = x + block_i(rms(x; w_i))

    M:  [z, xBC, dt] = n W_in
        xBC = silu(conv_causal(xBC) + b_conv) -> x [H, P], B, C [G, N]
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        y = ssd(x, dt, A, B, C) + D x      (ops/ssd.py; head h reads the
                                            group h // (H / G))
        out = gnorm(y * silu(z)) W_out     (an RMS norm over each group of
                                            H P / G channels, times w)
    *:  out = softmax(q k^T / sqrt(D), causal) v Wo   (grouped queries)
    E:  s = sigmoid(n Wr);  chosen = top-k of (s + b)
        w = s[chosen] / (sum of the chosen s + 1e-20) * scale
        out = (sum over the chosen experts j held HERE of
               w_j relu(n W_down W1_j)^2 W2_j) W_up + relu(n Ws1)^2 Ws2

`rms` is the plain RMS norm (weight at 1). A chip's share of a layer is
what it holds: `mamba_heads` of the Mamba-2 heads and the `mamba_groups`
whose `B` and `C` they read (a group's heads whole, so that the group's
norm is whole too), `attn_heads` query heads and the `attn_kv_heads` they
read, the `held_experts` of the router's `num_experts` outputs. The
mixers' output products give that share's part of the layer's result,
which goes on to the next layer as the held experts' part does; the
router, the latent projections and the shared expert every chip of the
layer computes alike. The selection bias `b` and the rule that moves it
are models/token_stack.py's `BiasRoutedStackLM`; the stack, its remat by
layer, the loss and the expert layers' counters are `TokenStackLM`'s.

Not supported: a multi-token-prediction block, a reset of the scan, the
convolution or the attention mask at a document boundary, a clamp on the
step size (`time_step_limit`), the exchange between the chips that share
a layer.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

from deeprec_tpu import nn
from deeprec_tpu.models.token_stack import _LAYER_KEYS, BiasRoutedStackLM
from deeprec_tpu.ops.ssd import ssd_scan
from deeprec_tpu.utils import scopes

BLOCK_LEAF = {"M": "mamba", "*": "attn", "E": "moe"}   # a layer's block


def relu2(x):
    return jnp.square(jax.nn.relu(x))


@dataclasses.dataclass(kw_only=True)
class MambaStackLM(BiasRoutedStackLM):
    pattern: str                     # a layer's kind, a letter a layer
    # Mamba-2: what is held here
    mamba_heads: int
    mamba_head_dim: int
    mamba_groups: int
    ssm_state: int
    conv_kernel: int
    chunk: int                       # the scan's chunk
    first_mamba_head: int = 0        # A_h starts at -(h + 1)
    time_step_min: float = 1e-3      # dt_bias starts at softplus^-1 of a
    time_step_max: float = 0.1       # step drawn log-uniformly between
    time_step_floor: float = 1e-4    # these, and no smaller than the floor
    # attention: what is held here
    attn_heads: int
    attn_kv_heads: int
    head_dim: int
    # the latent experts
    latent: int                      # the routed experts' space
    shared_expert_width: int

    def __post_init__(self):
        super().__post_init__()
        if len(self.pattern) != self.layers or set(self.pattern) - set(
                BLOCK_LEAF):
            raise ValueError(f"a pattern of {self.layers} letters of "
                             f"{''.join(BLOCK_LEAF)}; got {self.pattern!r}")

    def has_experts(self, i: int) -> bool:
        return self.pattern[i] == "E"

    # ------------------------------------------------------------------ init

    def _init_mamba(self, ks) -> Dict:
        d, H = self.hidden, self.mamba_heads
        inner = H * self.mamba_head_dim
        xbc = inner + 2 * self.mamba_groups * self.ssm_state
        lim = 1.0 / math.sqrt(self.conv_kernel)
        lo, hi = math.log(self.time_step_min), math.log(self.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(ks[4], (H,))
                                 * (hi - lo) + lo), self.time_step_floor)
        return {"w_in": self._normal(ks[0], (d, inner + xbc + H)),
                "conv": jax.random.uniform(ks[2], (self.conv_kernel, xbc),
                                           jnp.float32, -lim, lim),
                "conv_bias": jax.random.uniform(ks[3], (xbc,), jnp.float32,
                                                -lim, lim),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)
                                 + self.first_mamba_head),
                "D": jnp.ones((H,)),
                "norm": jnp.ones((inner,)),
                "w_out": self._normal(ks[5], (inner, d))}

    def _init_attention(self, ks) -> Dict:
        d, H, Hkv, D = (self.hidden, self.attn_heads, self.attn_kv_heads,
                        self.head_dim)
        return {"wq": self._normal(ks[0], (d, H * D)),
                "wk": self._normal(ks[1], (d, Hkv * D)),
                "wv": self._normal(ks[2], (d, Hkv * D)),
                "wo": self._normal(ks[5], (H * D, d))}

    def _init_moe(self, ks) -> Dict:
        d, lat, f, fs = (self.hidden, self.latent, self.expert_width,
                         self.shared_expert_width)
        held, normal = self.held_experts[1], self._normal
        return {"router": normal(ks[6], (d, self.num_experts)),
                "bias": jnp.zeros((self.num_experts,)),
                "w_down": normal(ks[1], (d, lat)),
                "experts": {"wu": normal(ks[7], (held, lat, f)),
                            "wd": normal(ks[9], (held, f, lat))},
                "w_up": normal(ks[2], (lat, d)),
                "shared": {"wu": normal(ks[10], (d, fs)),
                           "wd": normal(ks[12], (fs, d))}}

    def init(self, key) -> Dict:
        keys = jax.random.split(key, self.layers + 1)
        make = {"M": self._init_mamba, "*": self._init_attention,
                "E": self._init_moe}
        layers = [{"norm": self._norm_init(), BLOCK_LEAF[kind]: make[kind](
                      jax.random.split(keys[i], _LAYER_KEYS))}
                  for i, kind in enumerate(self.pattern)]
        return {"layers": layers, "final_norm": self._norm_init(),
                "head": self._normal(keys[-1], (self.hidden, self.vocab))}

    # ------------------------------------------------------------ the blocks

    def mamba(self, p: Dict, n):
        """A Mamba-2 mixer's share: n [B, T, d] (normed) -> [B, T, d]."""
        Bn, T, _ = n.shape
        H, P, G, N = (self.mamba_heads, self.mamba_head_dim,
                      self.mamba_groups, self.ssm_state)
        inner, cdt = H * P, self.compute_dtype
        with scopes.scope(scopes.BLOCK_MAMBA):
            zxbcdt = self._mm(n, p["w_in"])
            z, dt = zxbcdt[..., :inner], zxbcdt[..., -H:]
            with scopes.scope(scopes.MAMBA_CONV):
                xbc = jax.nn.silu(nn.causal_conv1d(
                    zxbcdt[..., inner:-H], p["conv"], p["conv_bias"]))
            x = xbc[..., :inner].reshape(Bn, T, H, P)
            b = xbc[..., inner:inner + G * N].reshape(Bn, T, G, N)
            c = xbc[..., inner + G * N:].reshape(Bn, T, G, N)
            dt = jax.nn.softplus(dt + p["dt_bias"])
            with scopes.scope(scopes.SSD_SCAN):
                y = ssd_scan(x.astype(cdt), dt, -jnp.exp(p["A_log"]),
                             b.astype(cdt), c.astype(cdt), self.chunk, cdt)
            y = (y + p["D"][:, None] * x).reshape(Bn, T, inner) \
                * jax.nn.silu(z)
            y = nn.rms_norm(y.reshape(Bn, T, G, inner // G), 1.0,
                            self.eps).reshape(Bn, T, inner) * p["norm"]
            return self._mm(y, p["w_out"])

    def attention(self, p: Dict, n):
        """The attention layer's share: n [B, T, d] (normed) -> [B, T, d];
        no position encoding."""
        Bn, T, _ = n.shape
        H, Hkv, D = self.attn_heads, self.attn_kv_heads, self.head_dim
        with scopes.scope(scopes.BLOCK_ATTN):
            q, k, v = (jnp.moveaxis(self._mm(n, p[w]).reshape(Bn, T, h, D),
                                    2, 1)                     # [B, h, T, D]
                       for w, h in (("wq", H), ("wk", Hkv), ("wv", Hkv)))
            o = self.attend(q, k, v)
            return self._mm(jnp.moveaxis(o, 1, 2).reshape(Bn, T, H * D),
                            p["wo"])

    def expert_block(self, p: Dict, n):
        """n [B, T, d] (normed) -> ([B, T, d], the layer's counters, with
        `load`: the choices that fell on each of ALL the router's
        outputs)."""
        Bn, T, d = n.shape
        xt = n.reshape(Bn * T, d)
        with scopes.scope(scopes.BLOCK_MOE):
            w, e, load = self.biased_route(p, xt)
            with scopes.scope(scopes.MOE_LATENT):
                xl = self._mm(xt, p["w_down"])
            # relu^2 is 0 below 0, as a ReLU gate is: the live hidden
            # units are counted as the window stack's are
            y, counters = self.held(p["experts"], xl, w, e, relu2,
                                    count_live=True)
            with scopes.scope(scopes.MOE_LATENT):
                y = self._mm(y, p["w_up"])
            with scopes.scope(scopes.MOE_SHARED):
                s = p["shared"]
                y = y + self._mm(relu2(self._mm(xt, s["wu"])), s["wd"])
            return y.reshape(Bn, T, d), {**counters, "load": load}

    # ----------------------------------------------------------------- stack

    def _layer(self, i: int, p: Dict, x):
        n = self._norm(x, p["norm"])
        kind = self.pattern[i]
        if kind == "E":
            y, counters = self.expert_block(p["moe"], n)
        else:
            mixer = self.mamba if kind == "M" else self.attention
            y, counters = mixer(p[BLOCK_LEAF[kind]], n), {}
        # the barrier keeps the residual stream the layer remat saves as
        # the sums: without it XLA keeps each block's output y instead and
        # remakes all eleven sums at once where the backward starts, two
        # copies of the stream live together (1.48e9 B at the cell's size)
        return jax.lax.optimization_barrier(x + y), counters
