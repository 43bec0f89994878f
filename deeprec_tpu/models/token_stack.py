"""What the token models on the embedding engine share: one hashed token
table (`pooling="none"`) in front of a stack of layers

    h = x + mixer_i(norm(x; w_in));  y = h + experts(norm(h; w_post))

whose feed-forward is a block of sparse experts of which THIS chip holds a
range (`held_experts`), a final norm and an untied head. A layer has a
kind: the first `dense_layers` of the stack (default 0) have a dense gated
feed-forward `dense_width` wide in the experts' place (`mlp_block`, scope
`block_mlp`) and no router, no experts and no counters. A model gives its
mixers, what its expert block adds to the held experts' part, and its
initialiser (`_init_mixer`, `_init_moe`, `_layer`); the stack, the remat BY
LAYER, the loss and the expert layers' counters are written here, once.

Products take bf16 operands and accumulate in f32; norms, softmaxes, the
router and the loss are f32; the residual stream is f32.

The model owns its loss (`loss`, the trainer's hook): next-token softmax
cross-entropy against integer labels, computed over blocks of positions so
that no whole `[positions, vocab]` logits array (nor its gradient) ever
exists, and remat by layer inside the model (`Trainer(remat=True)` wraps
the whole `apply` and then keeps every layer's recomputed activations alive
at once). Its metrics carry the expert layer's counters, summed over the
layers that have experts: `moe_pairs`, `moe_overflow` (pairs over the static
budget: a step in which it is not 0 left work out), `moe_max_load`;
`moe_pairs_max`, the fullest layer's pairs, which is what the budget has to
hold; from a model whose gate is a ReLU, `moe_hidden_live` (hidden units of
the held pairs that the gate leaves above 0); and whatever else a model's
`_total` makes of its layers' counters.
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
from deeprec_tpu.utils import scopes

_LAYER_KEYS = 14   # keys a layer's leaves are drawn from


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


@dataclasses.dataclass(kw_only=True)
class TokenStackLM:
    vocab: int                       # ids, head columns and labels: [0, vocab)
    seq_len: int
    capacity: int                    # rows of the token table
    pair_budget: int                 # held (token, expert) pairs a layer
    hidden: int
    layers: int
    # experts
    num_experts: int                 # the router's outputs
    experts_per_token: int
    expert_width: int
    held_experts: Tuple[int, int]    # (first, count) held here
    norm_topk_prob: bool = True
    # leading layers whose feed-forward is dense, and its width
    dense_layers: int = 0
    dense_width: int = 0
    # numerics and sizes of the implementation
    eps: float = 1e-6
    init_std: float = 0.02
    flash_block: int = 512           # flash kernel iff seq_len divides by it
    moe_block: int = 128             # rows of one expert block
    loss_block: int = 1024           # positions a block of the loss
    interpret: bool = False          # Pallas kernels interpreted (tests)
    # operands of every product: a constant of the model and no option of
    # its constructor (the tests that hold the model to the float32
    # reference set float32 on their instance)
    compute_dtype = jnp.bfloat16
    # a norm scales by `1 + w` (w starts at 0) or by `w` (starts at 1)
    zero_centred_norms = False

    def __post_init__(self):
        init = InitializerOption(stddev=self.init_std)
        self.features = [SparseFeature(
            name="tok", pooling="none", max_len=self.seq_len,
            table=TableConfig(name="tok", dim=self.hidden,
                              capacity=self.capacity,
                              ev=EmbeddingVariableOption(init=init)))]

    # ------------------------------------------------------------------ init

    def _normal(self, key, shape):
        return self.init_std * jax.random.normal(key, shape)

    def _norm_init(self):
        return (jnp.zeros if self.zero_centred_norms else jnp.ones)(
            (self.hidden,))

    def _init_moe(self, ks) -> Dict:
        d, f, held = self.hidden, self.expert_width, self.held_experts[1]
        return {"router": self._normal(ks[6], (d, self.num_experts)),
                "experts": {"wg": self._normal(ks[7], (held, d, f)),
                            "wu": self._normal(ks[8], (held, d, f)),
                            "wd": self._normal(ks[9], (held, f, d))}}

    def is_dense(self, i: int) -> bool:
        return i < self.dense_layers

    def has_experts(self, i: int) -> bool:
        """Whether layer i holds an expert block (whose counters the step
        sums): every layer past the leading dense ones, here."""
        return not self.is_dense(i)

    def _init_swiglu(self, ks, width: int) -> Dict:
        """A gated feed-forward `width` wide from three keys."""
        d = self.hidden
        return {"wg": self._normal(ks[0], (d, width)),
                "wu": self._normal(ks[1], (d, width)),
                "wd": self._normal(ks[2], (width, d))}

    def init(self, key) -> Dict:
        keys = jax.random.split(key, self.layers + 1)
        layers = []
        for i in range(self.layers):
            ks = jax.random.split(keys[i], _LAYER_KEYS)
            ffn = {"mlp": self._init_swiglu(ks[7:10], self.dense_width)} \
                if self.is_dense(i) else {"moe": self._init_moe(ks)}
            layers.append({"in_norm": self._norm_init(),
                           "mixer": self._init_mixer(ks, i),
                           "post_norm": self._norm_init(), **ffn})
        return {"layers": layers, "final_norm": self._norm_init(),
                "head": self._normal(keys[-1], (self.hidden, self.vocab))}

    # ---------------------------------------------------------------- pieces

    def _mm(self, x, w):
        cdt = self.compute_dtype
        return nn.matmul(x.astype(cdt), w.astype(cdt))

    def _norm(self, x, w):
        return nn.rms_norm(x, w, self.eps,
                           zero_centred=self.zero_centred_norms)

    def attend(self, q, k, v, window=None):
        """Causal softmax attention, inside `window` keys where one is
        given: q [B, H, T, D], k and v [B, Hkv, T, D] -> [B, H, T, D]."""
        B, _, T, D = q.shape
        scale = D ** -0.5
        if T % self.flash_block == 0:
            cdt = self.compute_dtype
            return flash_attention(
                q.astype(cdt), k.astype(cdt), v.astype(cdt),
                jnp.ones((B, T), bool), True, scale, self.flash_block,
                self.flash_block, self.interpret, window)
        return attention_reference(q, k, v, causal=True, sm_scale=scale,
                                   window=window)

    def route(self, router, xt, **scoring):
        """(weights, experts) [T, top_k] of the tokens xt [T, d], scored as
        `moe.route_topk` scores under `scoring`: the router's part of
        `moe_dispatch`, wherever in the layer it runs."""
        with scopes.scope(scopes.MOE_DISPATCH):
            w, e = moe.route_topk(xt, router, self.experts_per_token,
                                  self.norm_topk_prob, **scoring)
            return w, checkpoint_name(e, scopes.KEPT_MOE_ROUTE)

    def held(self, experts, xt, w, e, activation=jax.nn.silu,
             count_live: bool = False):
        """The held experts' part for routed tokens: (y [T, d], the
        layer's counters)."""
        return moe.held_experts_apply(
            experts, xt, w, e, held=self.held_experts,
            pair_budget=self.pair_budget, block=self.moe_block,
            compute_dtype=self.compute_dtype, interpret=self.interpret,
            activation=activation, count_live=count_live)

    def mlp_block(self, p: Dict, m):
        """A dense layer's feed-forward: m [B, T, d] (normed) -> [B, T, d]."""
        with scopes.scope(scopes.BLOCK_MLP):
            return nn.swiglu_apply(m, p["wg"], p["wu"], p["wd"],
                                   self.compute_dtype)

    # ----------------------------------------------------------------- stack

    def _total(self, counters) -> Dict:
        """The step's counters of the layers' (one dict an expert layer)."""
        total = {k: sum(c[k] for c in counters) for k in counters[0]}
        total["pairs_max"] = functools.reduce(
            jnp.maximum, (c["pairs"] for c in counters))
        return total

    def hidden_states(self, params: Dict, inputs):
        """([B, T, d] after the last layer, before the final norm; the
        counters of the layers that have experts, summed over them)."""
        x, _ = inputs.seq["tok"]
        x = x.astype(jnp.float32)
        counters = []
        for i, p in enumerate(params["layers"]):
            # a layer's backward makes its forward again, but for what is
            # dear to make and cheap to keep: the routes, the delta rule's
            # states, the attention's output and log-sum-exp
            layer = jax.checkpoint(
                functools.partial(self._layer, i),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *scopes.REMAT_KEPT))
            x, c = layer(p, x)
            if self.has_experts(i):
                counters.append(c)
        return x, self._total(counters)

    def apply(self, params: Dict, inputs, train: bool):
        """Logits [B, T, vocab] f32, whole: for small sizes and inspection;
        training goes through `loss`."""
        x, _ = self.hidden_states(params, inputs)
        with scopes.scope(scopes.BLOCK_HEAD_LOSS):
            return self._mm(self._norm(x, params["final_norm"]),
                            params["head"])

    def loss(self, params: Dict, inputs, batch) -> Tuple[jnp.ndarray, Dict]:
        """(mean next-token cross-entropy over every position, metrics);
        `batch["label"]` int32 [B, T], the token that follows each position."""
        x, counters = self.hidden_states(params, inputs)
        with scopes.scope(scopes.BLOCK_HEAD_LOSS):
            h = self._norm(x, params["final_norm"])
            n = h.shape[0] * h.shape[1]
            nll, hits = head_loss(
                h.reshape(n, self.hidden), params["head"],
                batch["label"].reshape(n).astype(jnp.int32),
                math.gcd(n, self.loss_block), self.compute_dtype)
            mets = {"accuracy": hits.astype(jnp.float32) / n,
                    **{f"moe_{k}": v for k, v in counters.items()}}
            return nll / n, mets


@dataclasses.dataclass(kw_only=True)
class BiasRoutedStackLM(TokenStackLM):
    """A token stack whose expert layers route by a sigmoid with a
    selection bias that a rule, and no gradient, moves (the latent, the
    Mamba and the short-convolution stacks):

        s = sigmoid(n Wr);  chosen = top-k of (s + b)
        w = s[chosen] / (sum of the chosen s + renorm_eps) * scale

    `b` (a leaf `bias` of every expert block, [router outputs], starting at
    0) is cut off from the gradient; after every step `after_update` (the
    trainer's hook for a leaf a rule owns) moves it by
    `b_j += gamma sign(mean(c) - c_j)`, where `c_j` is how many of the
    step's choices fell on output `j`, held here or not."""
    routed_scaling_factor: float
    bias_update_rate: float          # gamma of the selection bias' rule
    renorm_eps: float = 1e-20        # added to the chosen scores' sum

    def biased_route(self, p: Dict, xt):
        """(weights, experts) [T, top_k] of the tokens xt [T, d], and
        `load` [router outputs]: the choices that fell on each of ALL the
        router's outputs."""
        w, e = self.route(p["router"], xt, scoring="sigmoid", bias=p["bias"],
                          scale=self.routed_scaling_factor,
                          renorm_eps=self.renorm_eps)
        with scopes.scope(scopes.MOE_DISPATCH):
            load = jnp.sum(e.reshape(-1, 1) == jnp.arange(self.num_experts),
                           axis=0, dtype=jnp.int32)
        return w, e, load

    def _total(self, counters) -> Dict:
        """`load` [expert layers, router outputs] stays layer by layer (the
        rule moves each layer's bias by its own loads); `all_max_load` is
        the fullest output's count, summed over the layers."""
        load = jnp.stack([c["load"] for c in counters])
        total = super()._total([{k: v for k, v in c.items() if k != "load"}
                                for c in counters])
        return {**total, "load": load,
                "all_max_load": jnp.sum(jnp.max(load, axis=-1))}

    def after_update(self, dense: Dict, metrics: Dict) -> Dict:
        """The rule that owns the routers' selection bias, run by the
        trainer once a step after the dense optimizer's update (whose
        update of a leaf without a gradient is exactly 0):
        `b_j += gamma sign(mean(c) - c_j)`, `c = metrics["moe_load"]`
        [expert layers, router outputs]. Only the sign of a load's distance
        from the mean is read, so the micro-batches' sum and the replicas'
        mean give what one batch on one device gives."""
        with scopes.scope(scopes.ROUTER_BIAS_UPDATE):
            load = metrics["moe_load"].astype(jnp.float32)
            move = self.bias_update_rate * jnp.sign(
                jnp.mean(load, axis=-1, keepdims=True) - load)
            layers = list(dense["layers"])
            expert_layers = [i for i in range(self.layers)
                             if self.has_experts(i)]
            for j, i in enumerate(expert_layers):
                moe = layers[i]["moe"]
                layers[i] = {**layers[i],
                             "moe": {**moe, "bias": moe["bias"] + move[j]}}
            return {**dense, "layers": layers}
