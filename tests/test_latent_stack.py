"""The latent-attention token stack (models/latent_stack.py) and what it
stands on: the mixer (queries and keys wider than values, one rotary key
shared by the heads), the sigmoid router with its selection bias, a stack
with a leading dense layer, the shares of a layer's chips, and train steps
through `Trainer` with the leaf a rule owns, each against the benchmark's
plain reference (benchmark/reference/moonlight.py, which imports nothing of
deeprec_tpu); the flash kernels at a value head dim of their own. Small
sizes, CPU, seeded random weights with norm weights moved off 1."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import correct, harness
from benchmark.builders import moonlight as builder
from benchmark.generators import tokens
from benchmark.reference import moonlight as reference
from deeprec_tpu.models import LatentStackLM
from deeprec_tpu.ops import moe
from deeprec_tpu.ops.flash_attention import (attention_reference,
                                             flash_attention)
from deeprec_tpu.optim import Adagrad
from deeprec_tpu.parallel import ShardedTrainer, make_mesh, shard_batch
from deeprec_tpu.training import Trainer
from deeprec_tpu.training.trainer import ModelInputs
from deeprec_tpu.utils import scopes

GAMMA = 0.001
CONFIG = {
    "name": "tiny-latent", "builder": "moonlight", "reference": "moonlight",
    "work": "moonlight", "hidden_size": 32, "intermediate_size": 48,
    "kv_lora_rank": 16, "max_position_embeddings": 64,
    "moe_intermediate_size": 16, "n_group": 1, "n_routed_experts": 2,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "topk_group": 1, "topk_method": "noaux_tc",
    "vocab_size": 48, "bias_update_rate": GAMMA,
    "deployment": {"router_outputs": 16, "first_expert_held": 4,
                   "first_layer_held": 0},
    "emb_dim": 32, "capacity": 128, "table_dtype": "float32",
    "embedding_init": {"kind": "stateless_normal", "mean": 0.0,
                       "stddev": 0.02},
    "sparse_optimizer": {"name": "adagrad", "lr": 0.05,
                         "initial_accumulator_value": 0.1},
    "dense_optimizer": {"name": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.95,
                        "eps": 1e-8},
}
MIX = {"name": "tiny-seq", "generator": "tokens", "batch": 2, "seq_len": 32,
       "vocab": 48, "zipf_a": 1.1, "unique_budget": 40, "pair_budget": 256}
LIMITS = {"grad_gap": 0.015, "grad_median_gap": 0.002, "change_gap": 0.5}
BIAS = ("layers.1.moe.bias", "layers.2.moe.bias")


def program(**mix):
    p = builder.Program(CONFIG, dict(MIX, **mix))
    p.model.compute_dtype = jnp.float32
    return p


def model(**kw) -> LatentStackLM:
    m = program().model
    for k, v in kw.items():
        setattr(m, k, v)
    return m


def params(seed: int = 0):
    """The reference's own weights from a seed, the norms moved off 1, the
    matrices widened and the selection bias moved off 0, so that softmaxes
    and the router are far from flat."""
    p = reference.init_dense(CONFIG, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(100 + seed), 16))
    for layer in p["layers"]:
        for name in ("in_norm", "post_norm"):
            layer[name] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                        layer[name].shape)
        layer["mixer"] = jax.tree.map(lambda w: 10.0 * w, layer["mixer"])
        layer["mixer"]["kv_norm"] = 1.0 + 0.3 * jax.random.normal(
            next(keys), layer["mixer"]["kv_norm"].shape)
        ffn = "mlp" if "mlp" in layer else "moe"
        layer[ffn] = jax.tree.map(lambda w: 10.0 * w, layer[ffn])
        if ffn == "moe":
            layer["moe"]["bias"] = 0.2 * jax.random.normal(next(keys), (16,))
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


# ------------------------------------------------------------------ the mixer


@pytest.mark.parametrize("T,path", [(12, "plain"), (40, "plain"),
                                    (40, "blockwise"), (40, "kernels")])
def test_the_mixer_equals_the_reference(T, path):
    """Queries and keys 24 wide (16 + 8 rotary), values 12, the rotary key
    one head that all four share: by `attention_reference`, the blockwise
    fallback and the interpreted kernels, forward and the gradients of the
    weights and the input, against the reference's two products added."""
    m = model(flash_block=512 if path == "plain" else 8,
              interpret=path == "kernels")
    lp = params()["layers"][1]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    ref = lambda lp, x: jnp.stack([reference.attention(  # noqa: E731
        lp, x[b], CONFIG, "highest") for b in range(2)])
    with jax.default_matmul_precision("highest"):
        (out, g), (want, g_ref) = (both(m.attention, w, (0, 1))(lp, x),
                                   both(ref, w, (0, 1))(lp, x))
    close(out, want, 2e-5)
    same_tree(g[0], g_ref[0], 1e-4)
    close(g[1], g_ref[1], 1e-4)
    # the rotary key is seen: positions moved, the result moves
    other = reference.attention(lp, x[0, ::-1], CONFIG, "highest")[::-1]
    assert float(jnp.max(jnp.abs(other - want[0]))) > 1e-2


@pytest.mark.parametrize("H,Hkv,D,Dv", [(4, 4, 192, 128), (4, 2, 64, 128),
                                        (2, 2, 128, 128)])
def test_flash_kernels_at_a_value_head_dim_of_their_own(H, Hkv, D, Dv):
    """Keys wider than values (the latent cell's 192 / 128), narrower, and
    equal: the interpreted kernels and the blockwise fallback against
    `attention_reference`, forward and the gradients of q, k and v."""
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    B, L = 1, 128
    q = jax.random.normal(keys[0], (B, H, L, D))
    k = jax.random.normal(keys[1], (B, Hkv, L, D))
    v = jax.random.normal(keys[2], (B, Hkv, L, Dv))
    w = jax.random.normal(keys[3], (B, H, L, Dv))
    mask = jnp.ones((B, L), bool)
    scale = D ** -0.5

    def plain(q, k, v):
        return attention_reference(q, k, v, causal=True, sm_scale=scale)

    with jax.default_matmul_precision("highest"):
        ref, want = both(plain, w, (0, 1, 2))(q, k, v)
        for interpret in (True, False):
            out, ours = both(lambda q, k, v: flash_attention(
                q, k, v, mask, True, scale, 64, 64, interpret),
                w, (0, 1, 2))(q, k, v)
            assert out.shape == (B, H, L, Dv)
            close(out, ref, 2e-5)
            for a, b in zip(ours, want):
                assert a.shape == b.shape
                close(a, b, 5e-5)


# ----------------------------------------------------------------- the router


def test_the_bias_chooses_and_does_not_weigh():
    """Sigmoid scores; the top of score + bias is chosen; a chosen expert's
    weight is its score over the chosen scores' sum times the scale,
    whatever the bias (the loads of all the outputs are the expert
    block's: the next test)."""
    T, E, K, scale = 64, 16, 4, 2.446
    x = jax.random.normal(jax.random.PRNGKey(0), (T, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, E))
    bias = jnp.zeros((E,)).at[3].set(5.0).at[7].set(-5.0)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, router, precision="highest")))
    w0, e0 = moe.route_topk(x, router, K, True, scoring="sigmoid",
                            bias=jnp.zeros((E,)), scale=scale)
    w1, e1 = moe.route_topk(x, router, K, True, scoring="sigmoid",
                            bias=bias, scale=scale)
    e0, e1 = np.asarray(e0), np.asarray(e1)
    assert (np.sort(e0, 1) == np.sort(np.argsort(-s, 1)[:, :K], 1)).all()
    # who is chosen changes: 3 always, 7 never
    assert (e1 == 3).any(1).all() and not (e1 == 7).any()
    assert not (e0 == 3).any(1).all() and (e0 == 7).any()
    for w, e in ((w0, e0), (w1, e1)):
        chosen = np.take_along_axis(s, e, 1)
        np.testing.assert_allclose(
            np.asarray(w), scale * chosen / chosen.sum(1, keepdims=True),
            rtol=1e-6)
        np.testing.assert_allclose(np.asarray(w).sum(1), scale, rtol=1e-6)
    # no renormalisation: the scores themselves, scaled
    w2, e2 = moe.route_topk(x, router, K, False, scoring="sigmoid",
                            bias=bias, scale=scale)
    np.testing.assert_allclose(
        np.asarray(w2), scale * np.take_along_axis(s, np.asarray(e2), 1),
        rtol=1e-6)
    # the bias receives no gradient; the router's weights do
    g_b, g_r = jax.grad(lambda b, r: jnp.sum(moe.route_topk(
        x, r, K, True, scoring="sigmoid", bias=b, scale=scale)[0] ** 2),
        argnums=(0, 1))(bias, router)
    assert float(jnp.max(jnp.abs(g_b))) == 0.0
    assert float(jnp.max(jnp.abs(g_r))) > 0.0
    # softmax, the default, is what it was
    ws, es = moe.route_topk(x, router, K)
    top = np.sort(np.argsort(-np.asarray(jnp.dot(x, router)), 1)[:, :K], 1)
    assert (np.sort(np.asarray(es), 1) == top).all()
    np.testing.assert_allclose(np.asarray(ws).sum(1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route_topk(x, router, K, scoring="tanh")


def test_the_expert_block_equals_the_reference():
    """Routed by score + bias, weighed by the score, the shared experts
    added in full: forward, the loads, and the gradients of the router, the
    experts, the shared experts and the input; none for the bias."""
    m_, lp = model(), params()["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def ours(lp, x):
        return m_.expert_block(lp, x)[0]

    def ref(lp, x):
        return jnp.stack([reference.expert_block(lp, x[b], CONFIG,
                                                 "highest")[0]
                          for b in range(2)])

    (out, g), (want, g_ref) = (both(ours, w, (0, 1))(lp, x),
                               both(ref, w, (0, 1))(lp, x))
    close(out, want, 2e-5)
    same_tree(g[0], g_ref[0], 1e-4, but=("bias",))
    assert float(jnp.max(jnp.abs(g[0]["bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g_ref[0]["bias"]))) == 0.0
    close(g[1], g_ref[1], 1e-4)
    _, counters = jax.jit(m_.expert_block)(lp, x)
    want_load = sum(reference.expert_block(lp, x[b], CONFIG, "highest")[1]
                    for b in range(2))
    assert (np.asarray(counters["load"]) == np.asarray(want_load)).all()
    assert int(counters["load"].sum()) == 2 * 32 * 4
    assert int(counters["pairs"]) == int(counters["load"][4:6].sum())
    # without the bias another choice is made: the bias is seen
    flat = reference.expert_block(dict(lp, bias=jnp.zeros((16,))), x[0],
                                  CONFIG, "highest")[0]
    assert float(jnp.max(jnp.abs(flat - want[0]))) > 1e-2


# ------------------------------------------------------------------ the stack


def test_a_leading_dense_layer_has_no_router_and_no_counters():
    """Layer 0 has a dense feed-forward and neither router, experts nor
    bias; the step's counters are the two expert layers' alone, and the
    loads stay layer by layer."""
    m = model()
    p = jax.jit(m.init)(jax.random.PRNGKey(0))
    assert set(p["layers"][0]) == {"in_norm", "mixer", "post_norm", "mlp"}
    assert p["layers"][0]["mlp"]["wg"].shape == (32, 48)
    for layer in p["layers"][1:]:
        assert set(layer) == {"in_norm", "mixer", "post_norm", "moe"}
        assert set(layer["moe"]) == {"router", "bias", "experts", "shared"}
        assert float(jnp.max(jnp.abs(layer["moe"]["bias"]))) == 0.0
    assert m.is_dense(0) and not m.is_dense(1)
    p = params()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32))
    out, c = jax.jit(lambda p, x: m._layer(0, p, x))(p["layers"][0], x)
    assert c == {}
    want = jnp.stack([reference.layer(p["layers"][0], x[b], CONFIG,
                                      "highest")[0] for b in range(2)])
    close(out, want, 2e-5)

    class Inputs:
        seq = {"tok": (x, None)}

    _, total = jax.jit(lambda p: m.hidden_states(p, Inputs))(p)
    assert total["load"].shape == (2, 16)
    assert (np.asarray(total["load"]).sum(1) == 2 * 32 * 4).all()
    assert int(total["all_max_load"]) == int(
        np.asarray(total["load"]).max(1).sum())
    assert int(total["pairs"]) == int(np.asarray(total["load"])[:, 4:6].sum())
    assert int(total["pairs_max"]) == int(
        np.asarray(total["load"])[:, 4:6].sum(1).max())
    # a stack without leading dense layers is what it was
    from tests.test_window_stack import model as window_model
    wp = jax.jit(window_model().init)(jax.random.PRNGKey(0))
    assert all("moe" in layer and "mlp" not in layer
               for layer in wp["layers"])


def test_the_shares_of_eight_chips_add_up_to_the_uncut_layer():
    """16 experts, top 4, cut in 8 shares of 2: the eight chips' layers,
    with what every chip computes alike (the residual stream after
    attention and the shared experts) counted once, equal the layer of a
    chip that holds all 16; and the reference's uncut layer says the
    same."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    lp = params(3)["layers"][2]
    whole = {k: 0.2 * jax.random.normal(keys[j], (16,) + v.shape[1:])
             for j, (k, v) in enumerate(lp["moe"]["experts"].items())}
    x = jax.random.normal(keys[3], (1, 32, 32))
    m = model(pair_budget=32 * 4)

    def layer(first, count):
        m.held_experts = (first, count)
        p = dict(lp, moe=dict(lp["moe"], experts={
            k: v[first:first + count] for k, v in whole.items()}))
        return jax.jit(lambda p, x: m._layer(2, p, x))(p, x)

    uncut, counters = layer(0, 16)
    assert int(counters["pairs"]) == 32 * 4
    parts = [layer(first, 2) for first in range(0, 16, 2)]
    assert sum(int(c["pairs"]) for _, c in parts) == 32 * 4
    assert all(int(c["overflow"]) == 0 for _, c in parts)
    assert all((np.asarray(c["load"]) == np.asarray(counters["load"])).all()
               for _, c in parts)

    def alike(p, x):     # what every chip computes alike
        h = x + m.attention(p["mixer"], m._norm(x, p["in_norm"]))
        s = p["moe"]["shared"]
        mm = m._norm(h, p["post_norm"])
        return h + reference.swiglu(mm[0], s, "highest")[None]

    close(sum(y for y, _ in parts) - 7 * jax.jit(alike)(lp, x), uncut, 1e-5)
    config = dict(CONFIG, n_routed_experts=16,
                  deployment={"router_outputs": 16, "first_expert_held": 0})
    want, _ = reference.layer(dict(lp, moe=dict(lp["moe"], experts=whole)),
                              x[0], config, "highest")
    close(uncut[0], want, 2e-5)


# --------------------------------------------------- what the remat keeps


def loss_and_grad(m, p, x, labels):
    """(the gradient's jaxpr, printed; the loss; its gradients in the
    weights and the rows) of the model's own loss, the stack under its
    layer remat."""
    def f(p, x):
        inputs = ModelInputs(pooled={}, seq={"tok": (x, None)}, dense={})
        return m.loss(p, inputs, {"label": labels})[0]

    grad = jax.value_and_grad(f, argnums=(0, 1))
    return str(jax.make_jaxpr(grad)(p, x)), *jax.jit(grad)(p, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_layer_remat_runs_the_attention_forward_once(monkeypatch, dtype):
    """The remat keeps the flash forward's output and log-sum-exp by name
    (values narrower than keys: the output is Dv wide), so the gradient's
    program holds ONE forward kernel a layer, the dense one included,
    where the parent's (a remat that keeps nothing of attention) holds
    two; loss and gradients are the parent's. Interpreted kernels."""
    m = model(flash_block=8, interpret=True, compute_dtype=dtype)
    p, layers = params(), CONFIG["num_hidden_layers"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 48)
    text, loss, grads = loss_and_grad(m, p, x, labels)
    monkeypatch.setattr(scopes, "REMAT_KEPT", tuple(
        n for n in scopes.REMAT_KEPT if n != scopes.KEPT_ATTN_OUT))
    parent, loss0, grads0 = loss_and_grad(m, p, x, labels)
    assert text.count("name=flash_attention_fwd") == layers
    assert parent.count("name=flash_attention_fwd") == 2 * layers
    for kernel in ("flash_attention_bwd_dkdv", "flash_attention_bwd_dq"):
        assert text.count(f"name={kernel}") == parent.count(
            f"name={kernel}") == layers
    assert float(loss) == float(loss0)
    jax.tree.map(np.testing.assert_array_equal, grads, grads0)


# ---------------------------------------------------------- through Trainer


@pytest.fixture(scope="module")
def three_steps():
    p = program()
    state = p.fresh_state(5)
    k = iter(range(harness.CHECK_STEPS))

    def next_batch():
        host = tokens.make_batch(MIX, 5, next(k))
        return host, p.put(host)

    state, got, batches = harness.check_steps(p, state, next_batch, CONFIG,
                                              reference)
    counters = dict(zip(p.COUNTERS, np.asarray(p.counters(state)).tolist()))
    leaves = {"bias": {k: np.asarray(v) for k, v in
                       p.dense_params(state).items() if k in BIAS}}
    for name, tree in (("mu", state.opt_state[0].mu),
                       ("nu", state.opt_state[0].nu)):
        leaves[name] = {k: np.asarray(v) for k, v in
                        p.dense_leaves(tree).items() if k in BIAS}
    return got, batches, reference.run(CONFIG, batches, 5), counters, leaves


def test_three_train_steps_equal_the_reference(three_steps):
    """The tiny stack on `Trainer` (the model's own loss, Adagrad rows, the
    dense Adam, the rule's leaf) against the reference's `run`, as the
    benchmark compares a cell: the losses, every leaf's first gradient and
    its change."""
    got, _, want, counters, _ = three_steps
    assert got["grad"].keys() == want["grad"].keys()
    assert set(BIAS) < set(want["grad"]) and "layers.0.mlp.wg" in want["grad"]
    numbers = correct.compare(got, want)
    for name in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert numbers[name]["value"] < 1e-5, numbers
    assert numbers["grad_gap"]["value"] < 1e-4, numbers
    assert numbers["change_gap"]["value"] < 1e-3, numbers
    assert correct.verdict(numbers, LIMITS)[0]
    assert counters["moe_pairs"] > 0
    assert counters["moe_all_max_load"] >= counters["moe_max_load"] > 0
    assert counters["moe_overflow"] == counters["dedup_overflow"] == 0
    still = copy.deepcopy(want)
    still["change"] = {k: 0.0 for k in want["change"]}
    assert correct.compare(still, want)["change_gap"]["value"] == 1.0


def test_the_bias_leaves_are_the_rules_exactly(three_steps):
    """No gradient reaches the selection bias, so Adam's moments of it are
    exactly 0 and so is Adam's update; after three steps every entry is the
    reference's to the bit, a whole number of steps of gamma between -3 and
    3 of the parity of three moves (or fewer where a load sat on the mean),
    and the comparison leaves the leaves out of the change."""
    got, _, want, _, leaves = three_steps
    for name in BIAS:
        assert got["grad"][name] == want["grad"][name] == 0.0
        assert not leaves["mu"][name].any() and not leaves["nu"][name].any()
        ours = leaves["bias"][name]
        assert np.array_equal(ours, np.asarray(want["bias"][name],
                                               np.float32))
        moves = np.round(ours / GAMMA).astype(int)
        np.testing.assert_allclose(ours, moves * np.float32(GAMMA),
                                   rtol=1e-6)
        assert set(moves.tolist()) <= {-3, -2, -1, 0, 1, 2, 3}
        assert np.abs(moves).max() >= 1
        assert want["change"][name] > 0
    # the first step alone: +gamma under the mean, -gamma over it
    load = np.asarray(want["loads"][0])           # [expert layers, E]
    assert load.shape == (2, 16) and (load.sum(1) == 2 * 32 * 4).all()
    first = reference.run(CONFIG, three_steps[1][:1], 5)
    for j, name in enumerate(BIAS):
        np.testing.assert_array_equal(
            np.asarray(first["bias"][name], np.float32),
            np.float32(GAMMA) * np.sign(load[j].mean() - load[j]).astype(
                np.float32))


@pytest.mark.parametrize("fault", ["fault_no_routed_scale",
                                   "fault_half_positions", "control_fp8"])
def test_a_fault_of_the_reference_fails_the_limits(three_steps, fault):
    """The routed scaling factor left out, half the positions left out of
    the loss, and float8 operands each read as not correct against limits
    the program passes."""
    _, batches, want, _, _ = three_steps
    assert correct.verdict(correct.compare(want, want), LIMITS)[0]
    read = correct.compare(
        reference.run(CONFIG, batches, 5, **reference.CONTROLS[fault]), want)
    assert not correct.verdict(read, LIMITS)[0], read


def _bias(p, state):
    return np.stack([np.asarray(p.dense_params(state)[name])
                     for name in BIAS])


@pytest.mark.parametrize("path", ["train_steps", "train_step_accum"])
def test_every_step_body_runs_the_rule(path):
    """K steps in one dispatch move the bias as K single steps do; a step
    of two micro-batches moves it once, by the sign of the SUMMED loads'
    distance from their mean (the rows' learning rate at 0, so that both
    micro-batches see the rows a single step sees)."""
    batches = [tokens.make_batch(MIX, 9, k) for k in range(2)]
    p = program()
    state = p.fresh_state(3)
    if path == "train_steps":
        want = state
        for b in batches:
            want, _ = p.trainer.train_step(want, p.put(b))
        want = _bias(p, want)
        state, _ = p.trainer.train_steps(p.fresh_state(3),
                                         [p.put(b) for b in batches])
        np.testing.assert_array_equal(_bias(p, state), want)
        assert int(state.step) == 2
        return
    loads = []
    for b in batches:     # the micro-batches see the same weights
        _, mets = p.trainer.train_step(p.fresh_state(3), p.put(b), lr=0.0)
        loads.append(np.asarray(mets["moe_load"]))
    total = loads[0] + loads[1]
    whole = {k: np.concatenate([b[k] for b in batches]) for k in batches[0]}
    assert (loads[0] != loads[1]).any()
    state, mets = p.trainer.train_step_accum(state, p.put(whole),
                                             accum_steps=2, lr=0.0)
    np.testing.assert_array_equal(
        _bias(p, state), np.float32(GAMMA) * np.sign(
            total.mean(1, keepdims=True) - total).astype(np.float32))
    assert int(state.step) == 1


def test_the_bias_on_a_two_device_mesh_is_the_one_devices():
    """Data-parallel over two devices, a sequence each: the metrics are the
    replicas' mean, the sign of a load's distance from the mean is the
    whole batch's, and the bias after a step equals one device's."""
    assert len(jax.devices()) >= 2
    mesh = make_mesh(2)
    p = program()
    do = CONFIG["dense_optimizer"]
    opts = lambda: (Adagrad(lr=0.05, initial_accumulator_value=0.1),  # noqa: E731
                    optax.adam(do["lr"], b1=do["b1"], b2=do["b2"],
                               eps=do["eps"]))
    batch = {k: jnp.asarray(v) for k, v in
             tokens.make_batch(MIX, 9, 0).items()}
    local = Trainer(p.model, *opts(), unique_budget=40)
    s_local, m_local = local.train_step(local.init(3), batch)
    sharded = ShardedTrainer(p.model, *opts(), mesh=mesh, unique_budget=40)
    s_shard, m_shard = sharded.train_step(sharded.init(3),
                                          shard_batch(mesh, batch))
    np.testing.assert_allclose(float(m_shard["loss"]), float(m_local["loss"]),
                               rtol=1e-4)
    np.testing.assert_array_equal(2 * np.asarray(m_shard["moe_load"]),
                                  np.asarray(m_local["moe_load"]))
    moved = 0
    for a, b in zip(s_local.dense["layers"][1:], s_shard.dense["layers"][1:]):
        np.testing.assert_array_equal(np.asarray(a["moe"]["bias"]),
                                      np.asarray(b["moe"]["bias"]))
        moved += int(np.count_nonzero(np.asarray(a["moe"]["bias"])))
    assert moved > 0


def test_a_model_without_the_hook_lowers_without_the_rule():
    """The trainer runs `after_update` only where a model has one: the
    window stack's step names no `router_bias_update`, the latent stack's
    does, inside `phase_dense_apply`."""
    from deeprec_tpu.utils import scopes
    from tests.test_window_stack import MIX as WMIX
    from tests.test_window_stack import builder as wbuilder
    from tests.test_window_stack import CONFIG as WCONFIG

    def text(p, mix):
        state = p.fresh_state(0)
        batch = p.put(tokens.make_batch(mix, 1, 0))
        return p.trainer._train_step.lower(
            state, batch, jnp.float32(0.05)).as_text(debug_info=True)

    ours = text(program(), MIX)
    assert f"{scopes.PHASE_DENSE_APPLY}/{scopes.ROUTER_BIAS_UPDATE}" in ours
    for name in (scopes.ATTN_LATENT, scopes.MOE_SHARED, scopes.BLOCK_MLP):
        assert name in ours, name
    theirs = text(wbuilder.Program(WCONFIG, WMIX), WMIX)
    for name in (scopes.ROUTER_BIAS_UPDATE, scopes.ATTN_LATENT,
                 scopes.MOE_SHARED, scopes.BLOCK_MLP):
        assert name not in theirs, name


def test_an_overflowing_step_is_a_failed_step_for_the_benchmark():
    p = builder.Program(CONFIG, dict(MIX, pair_budget=8))
    state = p.fresh_state(5)
    state, _ = p.step(state, p.put(tokens.make_batch(MIX, 5, 0)))
    counters = np.asarray(p.counters(state))
    assert "moe_overflow" in p.FAIL_COUNTERS
    assert counters[p.COUNTERS.index("moe_overflow")] > 0
