"""A hybrid token model on the embedding engine: a stack whose layer `i` is
gated full attention where `(i + 1) % full_attention_interval == 0` and a
gated delta net otherwise, every layer's feed-forward a block of sparse
experts of which THIS chip holds a range (`held_experts`), behind one hashed
token table and in front of an untied head (docs/hybrid_stack.md has the
equations and the share).

    h = x + mixer_i(rms0(x; w_in));  y = h + moe(rms0(h; w_post))

`rms0` is the zero-centred RMS norm (`nn.rms_norm(..., zero_centred=True)`).
Products take bf16 operands and accumulate in f32; norms, softmaxes, the
router, the delta rule's state and gates and the loss are f32; the residual
stream is f32.

The model owns its loss (`loss`, the trainer's hook): next-token softmax
cross-entropy against integer labels, computed over blocks of positions so
that no whole `[positions, vocab]` logits array (nor its gradient) ever
exists, and remat BY LAYER inside the model (`Trainer(remat=True)` wraps the
whole `apply` and then keeps every layer's recomputed activations alive at
once). Its metrics carry the expert layer's counters, summed over the
layers: `moe_pairs`, `moe_overflow` (pairs over the static budget: a step in
which it is not 0 left work out), `moe_max_load`; and `moe_pairs_max`, the
fullest layer's pairs, which is what the budget has to hold.

Not supported: a state reset at a document boundary (delta rule and
convolution run through the whole sequence), a multi-token-prediction head,
an auxiliary load-balancing loss, a sliding window, the exchange between
the chips that share a layer.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeprec_tpu import nn
from deeprec_tpu.config import (EmbeddingVariableOption, InitializerOption,
                                TableConfig)
from deeprec_tpu.features import SparseFeature
from deeprec_tpu.ops import moe
from deeprec_tpu.ops.flash_attention import (attention_reference,
                                             flash_attention)
from deeprec_tpu.ops.gated_delta import gated_delta_rule
from deeprec_tpu.utils import scopes


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def head_loss(h, head, labels, block: int, compute_dtype):
    """(sum over the positions of the cross-entropy of `softmax(h head)`
    against `labels`, the positions whose largest logit is the label), by
    blocks of `block` positions: h [n, d] f32, head [d, V] f32, labels [n]
    int32. No `[n, V]` array exists in the forward or in the backward: the
    forward keeps each position's log-sum-exp, the backward makes a block's
    logits again and adds the head's gradient up in f32 block by block."""
    return _head_loss_fwd(h, head, labels, block, compute_dtype)[0]


def _blocks(x, block: int):
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def _head_loss_fwd(h, head, labels, block, cdt):
    hc, wc = _blocks(h.astype(cdt), block), head.astype(cdt)

    def one(carry, xs):
        hx, yx = xs
        logits = nn.matmul(hx, wc)                            # [block, V] f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yx[:, None], axis=-1)[:, 0]
        hit = jnp.sum(jnp.argmax(logits, axis=-1) == yx)
        return (carry[0] + jnp.sum(lse - ll), carry[1] + hit), lse

    (nll, hits), lse = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (hc, _blocks(labels, block)))
    return (nll, hits), (h, head, labels, lse)


def _head_loss_bwd(block, cdt, res, cts):
    h, head, labels, lse = res
    scale = cts[0]
    hc, wc = _blocks(h.astype(cdt), block), head.astype(cdt)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, head.shape[1]), 1)

    def one(dw, xs):
        hx, yx, lx = xs
        p = jnp.exp(nn.matmul(hx, wc) - lx[:, None])
        dl = (scale * (p - (cols == yx[:, None]))).astype(cdt)
        dh = jax.lax.dot_general(dl, wc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dw = dw + jax.lax.dot_general(hx, dl, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dw, dh

    dw, dh = jax.lax.scan(one, jnp.zeros(head.shape, jnp.float32),
                          (hc, _blocks(labels, block), lse))
    return dh.reshape(h.shape), dw, None


head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


@dataclasses.dataclass
class HybridStackLM:
    vocab: int                       # ids, head columns and labels: [0, vocab)
    seq_len: int
    capacity: int                    # rows of the token table
    pair_budget: int                 # held (token, expert) pairs a layer
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
    norm_topk_prob: bool = True
    held_experts: Tuple[int, int] = (0, 32)   # (first, count) held here
    # numerics and sizes of the implementation
    eps: float = 1e-6
    init_std: float = 0.02
    chunk: int = 64                  # the delta rule's chunk
    segment: int = 2048              # ... and what its backward holds at once
    flash_block: int = 512           # flash kernel iff seq_len divides by it
    moe_block: int = 128             # rows of one expert block
    loss_block: int = 1024           # positions a block of the loss
    interpret: bool = False          # Pallas kernels interpreted (tests)
    # operands of every product: a constant of the model and no option of
    # its constructor (the tests that hold the model to the float32
    # reference set float32 on their instance)
    compute_dtype = jnp.bfloat16

    def __post_init__(self):
        init = InitializerOption(stddev=self.init_std)
        self.features = [SparseFeature(
            name="tok", pooling="none", max_len=self.seq_len,
            table=TableConfig(name="tok", dim=self.hidden,
                              capacity=self.capacity,
                              ev=EmbeddingVariableOption(init=init)))]
        self.key_dim = self.gdn_key_heads * self.gdn_key_dim
        self.value_dim = self.gdn_value_heads * self.gdn_value_dim
        self.rot_dim = int(self.head_dim * self.partial_rotary_factor)

    def is_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    # ------------------------------------------------------------------ init

    def _init_mixer(self, ks, i: int) -> Dict:
        d, std = self.hidden, self.init_std
        normal = lambda k, shape: std * jax.random.normal(k, shape)  # noqa: E731
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
        d, f, fs = self.hidden, self.expert_width, self.shared_expert_width
        held, std = self.held_experts[1], self.init_std
        normal = lambda k, shape: std * jax.random.normal(k, shape)  # noqa: E731
        return {"router": normal(ks[6], (d, self.num_experts)),
                "experts": {"wg": normal(ks[7], (held, d, f)),
                            "wu": normal(ks[8], (held, d, f)),
                            "wd": normal(ks[9], (held, f, d))},
                "shared": {"wg": normal(ks[10], (d, fs)),
                           "wu": normal(ks[11], (d, fs)),
                           "wd": normal(ks[12], (fs, d))},
                "shared_gate": normal(ks[13], (d, 1))}

    def init(self, key) -> Dict:
        keys = jax.random.split(key, self.layers + 1)
        layers = []
        for i in range(self.layers):
            ks = jax.random.split(keys[i], 14)
            layers.append({"in_norm": jnp.zeros((self.hidden,)),
                           "mixer": self._init_mixer(ks, i),
                           "post_norm": jnp.zeros((self.hidden,)),
                           "moe": self._init_moe(ks)})
        return {"layers": layers, "final_norm": jnp.zeros((self.hidden,)),
                "head": self.init_std * jax.random.normal(
                    keys[-1], (self.hidden, self.vocab))}

    # ---------------------------------------------------------------- mixers

    def _mm(self, x, w):
        cdt = self.compute_dtype
        return nn.matmul(x.astype(cdt), w.astype(cdt))

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
            scale = D ** -0.5
            if T % self.flash_block == 0:
                o = flash_attention(
                    q.astype(cdt), k.astype(cdt), v.astype(cdt),
                    jnp.ones((B, T), bool), True, scale, self.flash_block,
                    self.flash_block, self.interpret)
            else:
                o = attention_reference(q, k, v, causal=True, sm_scale=scale)
            o = jnp.moveaxis(o, 1, 2).reshape(B, T, H * D).astype(jnp.float32)
            return self._mm(o * jax.nn.sigmoid(gate.astype(jnp.float32)),
                            p["wo"])

    def expert_block(self, p: Dict, x):
        """x [B, T, d] (normed) -> ([B, T, d], the layer's counters)."""
        B, T, d = x.shape
        xt = x.reshape(B * T, d)
        with scopes.scope(scopes.BLOCK_MOE):
            with scopes.scope(scopes.MOE_DISPATCH):
                w, e = moe.route_topk(xt, p["router"], self.experts_per_token,
                                      self.norm_topk_prob)
                e = checkpoint_name(e, scopes.KEPT_MOE_ROUTE)
            y, counters = moe.held_experts_apply(
                p["experts"], xt, w, e, held=self.held_experts,
                pair_budget=self.pair_budget, block=self.moe_block,
                compute_dtype=self.compute_dtype, interpret=self.interpret)
            s = p["shared"]
            shared = nn.swiglu_apply(xt, s["wg"], s["wu"], s["wd"],
                                     self.compute_dtype)
            y = y + jax.nn.sigmoid(self._mm(xt, p["shared_gate"])) * shared
            return y.reshape(B, T, d), counters

    # ----------------------------------------------------------------- stack

    def _layer(self, i: int, p: Dict, x):
        mixer = self.gated_attention if self.is_attention(i) \
            else self.gated_delta_net
        h = x + mixer(p["mixer"], nn.rms_norm(x, p["in_norm"], self.eps,
                                              zero_centred=True))
        y, counters = self.expert_block(
            p["moe"], nn.rms_norm(h, p["post_norm"], self.eps,
                                  zero_centred=True))
        return h + y, counters

    def hidden_states(self, params: Dict, inputs):
        """([B, T, d] after the last layer, before the final norm; the
        expert layers' counters summed over the layers)."""
        x, _ = inputs.seq["tok"]
        x = x.astype(jnp.float32)
        counters = []
        for i, p in enumerate(params["layers"]):
            # a layer's backward makes its forward again, but for the few
            # small arrays that are dear to make and cheap to keep
            layer = jax.checkpoint(
                functools.partial(self._layer, i),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *scopes.REMAT_KEPT))
            x, c = layer(p, x)
            counters.append(c)
        total = {k: sum(c[k] for c in counters) for k in counters[0]}
        total["pairs_max"] = functools.reduce(
            jnp.maximum, (c["pairs"] for c in counters))
        return x, total

    def apply(self, params: Dict, inputs, train: bool):
        """Logits [B, T, vocab] f32, whole: for small sizes and inspection;
        training goes through `loss`."""
        x, _ = self.hidden_states(params, inputs)
        with scopes.scope(scopes.BLOCK_HEAD_LOSS):
            h = nn.rms_norm(x, params["final_norm"], self.eps,
                            zero_centred=True)
            return self._mm(h, params["head"])

    def loss(self, params: Dict, inputs, batch) -> Tuple[jnp.ndarray, Dict]:
        """(mean next-token cross-entropy over every position, metrics);
        `batch["label"]` int32 [B, T], the token that follows each position."""
        x, counters = self.hidden_states(params, inputs)
        with scopes.scope(scopes.BLOCK_HEAD_LOSS):
            h = nn.rms_norm(x, params["final_norm"], self.eps,
                            zero_centred=True)
            n = h.shape[0] * h.shape[1]
            nll, hits = head_loss(
                h.reshape(n, self.hidden), params["head"],
                batch["label"].reshape(n).astype(jnp.int32),
                math.gcd(n, self.loss_block), self.compute_dtype)
            mets = {"accuracy": hits.astype(jnp.float32) / n,
                    "moe_pairs": counters["pairs"],
                    "moe_pairs_max": counters["pairs_max"],
                    "moe_overflow": counters["overflow"],
                    "moe_max_load": counters["max_load"]}
            return nll / n, mets
