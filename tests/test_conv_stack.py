"""The short-convolution token stack (models/conv_stack.py) and what it
stands on: the gated short convolution, attention with its queries and
keys normed per head at rotary over the whole head, the router's
renormalisation epsilon (`route_topk(renorm_eps=)`), the share of a
layer's chips, the whole stack's loss and gradients, causality and the
batch's sequences kept apart, each against the benchmark's plain reference
(benchmark/reference/lfm2.py, which imports nothing of deeprec_tpu). Small
sizes, CPU, seeded random weights with norm weights moved off 1; train
steps through `Trainer` and the reference's planted faults are rehearsed
in benchmark/tests/test_lfm2.py."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import lfm2 as builder
from benchmark.reference import lfm2 as reference
from deeprec_tpu.models import ConvStackLM
from deeprec_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2-8b-a1b.json")) as f:
    FULL = json.load(f)
GAMMA = 0.001
KINDS = ["conv", "full_attention", "conv"]
CONFIG = dict(
    FULL, name="tiny-lfm2", layer_types=KINDS, num_hidden_layers=3,
    num_dense_layers=1, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=48, moe_intermediate_size=24,
    num_experts=2, vocab_size=48, emb_dim=64, capacity=128,
    bias_update_rate=GAMMA,
    deployment=dict(FULL["deployment"], router_outputs=8,
                    first_expert_held=2),
    dense_optimizer=dict(FULL["dense_optimizer"], lr=1e-3))
MIX = {"name": "tiny-seq", "generator": "tokens", "batch": 2, "seq_len": 32,
       "vocab": 48, "zipf_a": 1.1, "unique_budget": 40, "pair_budget": 128}
BIAS = ("layers.1.moe.bias", "layers.2.moe.bias")


def program(**mix):
    p = builder.Program(CONFIG, dict(MIX, **mix))
    p.model.compute_dtype = jnp.float32
    return p


def model(**kw) -> ConvStackLM:
    m = program().model
    for k, v in kw.items():
        setattr(m, k, v)
    return m


def params(seed: int = 0, config=CONFIG):
    """The reference's own weights from a seed, the norms moved off 1, the
    matrices widened and the selection bias moved off 0, so that softmaxes,
    gates and the router are far from flat."""
    p = reference.init_dense(config, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(100 + seed), 64))
    d = config["hidden_size"]
    for layer in p["layers"]:
        for name in ("in_norm", "post_norm"):
            layer[name] = 1.0 + 0.3 * jax.random.normal(next(keys), (d,))
        mixer = layer["mixer"]
        for name in mixer:
            if name.startswith("w"):
                mixer[name] = 5.0 * mixer[name]
            elif name.endswith("_norm"):
                mixer[name] = 1.0 + 0.3 * jax.random.normal(
                    next(keys), mixer[name].shape)
        if "moe" in layer:
            block = layer["moe"]
            block["router"] = 10.0 * block["router"]
            block["experts"] = jax.tree.map(lambda w: 5.0 * w,
                                            block["experts"])
            block["bias"] = 0.2 * jax.random.normal(next(keys),
                                                    block["bias"].shape)
    return p


def close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b))) + 1e-30
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale, (
        float(jnp.max(jnp.abs(a - b))), scale)


def both(fn, w, argnums):
    return jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *b: jnp.sum(fn(*b) * w), argnums=argnums)(*a)))


def same_tree(ours, want, tol, but=()):
    ours, want = reference.leaf_names(ours), reference.leaf_names(want)
    assert ours.keys() == want.keys()
    for name in want:
        if name in but:
            continue
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
        close(ours[name], want[name], tol)


def per_sequence(fn, x):
    return jnp.stack([fn(x[b]) for b in range(x.shape[0])])


# ---------------------------------------------------------------- the mixers


@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_the_mixers_equal_the_reference(kind):
    """The gated short convolution, and the attention (32 positions, 4
    query heads of 16 over 2 key/value heads, per-head norms, rotary over
    the whole head) through the interpreted flash kernels: forward and the
    gradients of every weight and of the input."""
    m = model(flash_block=8, interpret=True)
    lp = params()["layers"][KINDS.index(kind)]["mixer"]
    ours = m.short_conv if kind == "conv" else m.attention
    fn = reference.short_conv if kind == "conv" else reference.attention
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    ref = lambda lp, x: per_sequence(  # noqa: E731
        lambda s: fn(lp, s, CONFIG, "highest"), x)
    with jax.default_matmul_precision("highest"):
        (out, g), (want, g_ref) = (both(ours, w, (0, 1))(lp, x),
                                   both(ref, w, (0, 1))(lp, x))
    close(out, want, 2e-5)
    same_tree(g[0], g_ref[0], 1e-4)
    close(g[1], g_ref[1], 1e-4)


def test_a_conv_layer_reads_exactly_its_three_taps():
    """Output t of the short convolution moves with input s exactly where
    t - 2 <= s <= t: causal, and no farther back than the L = 3 taps."""
    m, lp = model(), params()["layers"][0]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 64))
    jac = jax.jit(jax.jacfwd(lambda x: m.short_conv(lp, x)))(x)
    reach = np.asarray(jnp.max(jnp.abs(jac[0]), axis=(1, 2, 4))) > 0  # [t, s]
    t, s = np.indices(reach.shape)
    np.testing.assert_array_equal(reach, (s <= t) & (s >= t - 2))


# ------------------------------------------------------------------- router


def test_the_renorm_epsilon_keyword_leaves_the_default_alone():
    """`route_topk` without `renorm_eps` is what it was, 1e-20 added, bit
    for bit; the family's 1e-6 is what it says."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(ks[0], (40, 16))
    router = jax.random.normal(ks[1], (16, 8))
    bias = 0.3 * jax.random.normal(ks[2], (8,))

    def by_hand(eps):
        s = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
        _, e = jax.lax.top_k(s + bias, 4)
        w = jnp.take_along_axis(s, e, axis=-1)
        return 2.5 * (w / (jnp.sum(w, axis=-1, keepdims=True) + eps)), e

    route = jax.jit(lambda **kw: moe.route_topk(
        x, router, 4, scoring="sigmoid", bias=bias, scale=2.5, **kw),
        static_argnames="renorm_eps")
    for kw, eps in (({}, 1e-20), ({"renorm_eps": 1e-6}, 1e-6)):
        (w, e), (w_ref, e_ref) = route(**kw), jax.jit(by_hand)(eps)
        np.testing.assert_array_equal(e, e_ref)
        np.testing.assert_array_equal(w, w_ref)
    assert float(jnp.max(jnp.abs(route()[0] - route(renorm_eps=1e-6)[0]))) > 0


# ------------------------------------------------------------------ the share


def test_the_four_expert_ranges_add_up_to_the_uncut_layer():
    """The routed part of an expert layer over all 8 of the router's
    outputs (the reference, every expert held) is the sum of the parts the
    four chips' ranges of 2 experts give; nothing else of the layer is
    shared out (the mixers are computed whole)."""
    cfg = dict(CONFIG, num_experts=8,
               deployment=dict(CONFIG["deployment"], first_expert_held=0))
    e = params(7, cfg)["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 32, 64))
    want, _ = reference.expert_block(e, x[0], cfg, "highest")
    m = model()
    parts = []
    with jax.default_matmul_precision("highest"):
        for first in range(0, 8, 2):
            m.held_experts = (first, 2)
            share = dict(e, experts=jax.tree.map(
                lambda v: v[first:first + 2], e["experts"]))
            y, c = jax.jit(m.expert_block)(share, x)
            assert int(c["overflow"]) == 0 and int(c["pairs"]) > 0
            parts.append(y[0])
    close(sum(parts), want, 1e-5)


# ---------------------------------------------------------------- the stack


def _loss(m, p, rows, labels):
    from deeprec_tpu.training.trainer import ModelInputs

    inputs = ModelInputs(pooled={}, seq={"tok": (rows, None)}, dense={})
    return m.loss(p, inputs, {"label": labels})


def test_the_model_equals_the_reference():
    """The whole stack (a dense conv layer, then an attention and a conv
    layer with experts) under its layer remat: the loss and the gradient
    of every leaf and of the rows, against the reference's `loss_fn`; the
    loads come from the two expert layers alone."""
    m, p = model(), params(2)
    rows = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64))
    labels = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 48)

    def ref(p, rows):
        return reference.loss_fn(p, rows.reshape(64, 64),
                                 jnp.arange(64).reshape(2, 32), labels,
                                 CONFIG, "highest", False, False, False)

    (loss, mets), g = jax.jit(jax.value_and_grad(
        lambda p, r: _loss(m, p, r, labels), (0, 1), has_aux=True))(p, rows)
    (want, loads), g_ref = jax.jit(jax.value_and_grad(
        ref, (0, 1), has_aux=True))(p, rows)
    close(loss, want, 1e-6)
    same_tree(g[0], g_ref[0], 1e-4, but=BIAS)
    close(g[1], g_ref[1], 1e-4)
    assert (np.asarray(mets["moe_load"]) == np.asarray(loads)).all()
    assert mets["moe_load"].shape == (2, 8)


def test_causality_and_the_batchs_sequences_kept_apart():
    """Changing sequence 0's token at t moves none of its logits before t
    and none of sequence 1's (the taps' zero padding is a sequence's own:
    no tap reads across the batch)."""
    m, p = model(), params(4)
    rows = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64))
    moved = rows.at[0, 20].set(jax.random.normal(jax.random.PRNGKey(9),
                                                 (64,)))

    def logits(r):
        from deeprec_tpu.training.trainer import ModelInputs

        return m.apply(p, ModelInputs(pooled={}, seq={"tok": (r, None)},
                                      dense={}), False)

    a, b = jax.jit(logits)(rows), jax.jit(logits)(moved)
    np.testing.assert_array_equal(a[0, :20], b[0, :20])
    np.testing.assert_array_equal(a[1], b[1])
    assert float(jnp.max(jnp.abs(a[0, 20:] - b[0, 20:]))) > 1e-3
