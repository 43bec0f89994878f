"""The hybrid token stack (models/hybrid_stack.py) and what it stands on:
the chunked gated delta rule against the recurrence, the mixers and the
expert block against the benchmark's plain reference
(benchmark/reference/qwen3next.py, which imports nothing of deeprec_tpu),
the expert layer's share and budget, three train steps through `Trainer`
with the model's own loss, and the flash kernels at grouped queries and
head dim 256. Small sizes, CPU, seeded random weights with NON-ZERO norm
weights, `A_log` and `dt_bias`."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, harness
from benchmark.builders import qwen3next as builder
from benchmark.generators import tokens
from benchmark.reference import qwen3next as reference
from deeprec_tpu.models import HybridStackLM
from deeprec_tpu.ops import moe
from deeprec_tpu.ops.flash_attention import (attention_reference,
                                             flash_attention)
from deeprec_tpu.ops.gated_delta import (gated_delta_recurrence,
                                         gated_delta_rule)

CONFIG = {
    "name": "tiny-hybrid", "builder": "qwen3next", "reference": "qwen3next",
    "work": "qwen3next", "full_attention_interval": 4, "head_dim": 16,
    "hidden_size": 32, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 8, "moe_intermediate_size": 16,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 16, "vocab_size": 48,
    "deployment": {"router_outputs": 16, "first_expert_held": 4},
    "emb_dim": 32, "capacity": 128, "delta_rule_chunk": 8,
    "embedding_init": {"kind": "stateless_normal", "mean": 0.0,
                       "stddev": 0.02},
    "sparse_optimizer": {"name": "adagrad", "lr": 0.05,
                         "initial_accumulator_value": 0.1},
    "dense_optimizer": {"name": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.95,
                        "eps": 1e-8},
}
MIX = {"name": "tiny-seq", "generator": "tokens", "batch": 2, "seq_len": 32,
       "vocab": 48, "zipf_a": 1.1, "unique_budget": 40, "pair_budget": 256}


def model(**kw) -> HybridStackLM:
    program = builder.Program(CONFIG, MIX)
    for k, v in {"compute_dtype": jnp.float32, **kw}.items():
        setattr(program.model, k, v)
    return program.model


def params(seed: int = 0):
    """The reference's own weights from a seed, with every leaf that
    starts at 0 or 1 (norms) or in a narrow range moved off it."""
    p = reference.init_dense(CONFIG, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(100 + seed), 64))
    for layer in p["layers"]:
        for name in ("in_norm", "post_norm"):
            layer[name] = 0.3 * jax.random.normal(next(keys),
                                                  layer[name].shape)
        mixer = layer["mixer"]
        for name in ("q_norm", "k_norm", "norm"):
            if name in mixer:
                mixer[name] = mixer[name] + 0.3 * jax.random.normal(
                    next(keys), mixer[name].shape)
        # wider weights than the initializer's, so that gates, decays and
        # the router are far from flat
        for name in ("qkvz", "ba", "wq", "wk", "wv", "wo"):
            if name in mixer:
                mixer[name] = 10.0 * mixer[name]
        layer["moe"] = jax.tree.map(lambda w: 10.0 * w, layer["moe"])
    p["final_norm"] = 0.3 * jax.random.normal(next(keys),
                                              p["final_norm"].shape)
    return p


def close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b))) + 1e-30
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale, (
        float(jnp.max(jnp.abs(a - b))), scale)


# ------------------------------------------------------------ the delta rule


def rule_inputs(T, seed=0, B=2, H=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], (B, T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def test_the_chunked_rule_holds_where_a_chunks_keys_align():
    """One key all through a chunk of 64, beta near 1, next to no decay:
    the strictly lower `A` is then nearly all ones, its powers reach 1e17,
    and an inverse made from them (the product form) returns noise of 1e9
    where forward substitution is exact. The benchmark's cell met it thirty
    steps into training, as a loss of NaN."""
    B, T, H, dk, dv = 1, 128, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    k1 = jax.random.normal(ks[0], (B, 1, H, dk))
    k = jnp.broadcast_to(k1 / jnp.linalg.norm(k1, axis=-1, keepdims=True),
                         (B, T, H, dk))
    q = jax.random.normal(ks[1], (B, T, H, dk)) / np.sqrt(dk)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = jnp.full((B, T, H), -1e-4)
    beta = jnp.full((B, T, H), 0.999)
    out = jax.jit(lambda *a: gated_delta_rule(*a, 64, 128, jnp.float32))(
        q, k, v, g, beta)
    want = jax.jit(gated_delta_recurrence)(q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(out)))
    close(out, want, 1e-4)


@pytest.mark.parametrize("T", [32, 45, 64, 100])
def test_the_chunked_rule_is_the_recurrence(T):
    """Forward and the gradients of q, k, v, g and beta, at lengths that
    are and are not a multiple of the chunk (16) and of the segment (32)."""
    args = rule_inputs(T)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def both(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *b: jnp.sum(fn(*b) * w), argnums=(0, 1, 2, 3, 4))(*a)))

    out, grads = both(lambda *a: gated_delta_rule(
        *a, 16, 32, jnp.float32))(*args)
    want, want_grads = both(gated_delta_recurrence)(*args)
    close(out, want, 1e-5)
    for a, b in zip(grads, want_grads):
        close(a, b, 1e-5)


def test_the_rule_in_bfloat16_stays_near_the_recurrence():
    args = rule_inputs(64)
    lowp = jax.jit(lambda *a: gated_delta_rule(
        *(x.astype(jnp.bfloat16) for x in a[:3]), *a[3:], 16, 32,
        jnp.bfloat16))(*args)
    close(lowp, jax.jit(gated_delta_recurrence)(*args), 3e-2)


# ------------------------------------------------- mixers and expert block


def layer_inputs(seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (2, MIX["seq_len"], CONFIG["hidden_size"]))


@pytest.mark.parametrize("kind", ["gdn", "attention", "experts"])
def test_a_block_equals_the_reference(kind):
    m, p, x = model(), params(), layer_inputs()
    i = {"gdn": 0, "attention": 3, "experts": 1}[kind]
    if kind == "experts":
        lp = p["layers"][i]["moe"]
        ours = lambda lp, x: m.expert_block(lp, x)[0]  # noqa: E731
        ref = lambda lp, x: jnp.stack([reference.expert_block(  # noqa: E731
            lp, x[b], CONFIG, "highest") for b in range(x.shape[0])])
    else:
        lp = p["layers"][i]["mixer"]
        ours = m.gated_delta_net if kind == "gdn" else m.gated_attention
        fn = reference.gated_delta_net if kind == "gdn" \
            else reference.gated_attention
        ref = lambda lp, x: jnp.stack([  # noqa: E731
            fn(lp, x[b], CONFIG, "highest") for b in range(x.shape[0])])
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def both(fn):
        return jax.jit(lambda lp, x: (fn(lp, x), jax.grad(
            lambda lp, x: jnp.sum(fn(lp, x) * w), argnums=(0, 1))(lp, x)))

    (out, g_ours), (want, g_ref) = both(ours)(lp, x), both(ref)(lp, x)
    close(out, want, 2e-5)
    flat_o, flat_r = (reference.leaf_names(g[0]) for g in (g_ours, g_ref))
    assert flat_o.keys() == flat_r.keys()
    for name in flat_r:
        assert float(jnp.max(jnp.abs(flat_r[name]))) > 0, name
        close(flat_o[name], flat_r[name], 1e-4)
    close(g_ours[1], g_ref[1], 1e-4)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts, top 4, cut in 4 shares: the four shares' routed parts
    plus the shared expert counted once equal the uncut layer."""
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    T, d, f, E, K = 64, 32, 16, 16, 4
    x = jax.random.normal(keys[0], (T, d))
    router = jax.random.normal(keys[1], (d, E))
    whole = {"wg": jax.random.normal(keys[2], (E, d, f)),
             "wu": jax.random.normal(keys[3], (E, d, f)),
             "wd": jax.random.normal(keys[4], (E, f, d))}
    w, e = moe.route_topk(x, router, K)

    def routed(first, count):
        p = {k: v[first:first + count] for k, v in whole.items()}
        y, c = jax.jit(lambda p: moe.held_experts_apply(
            p, x, w, e, held=(first, count), pair_budget=T * K, block=8,
            compute_dtype=jnp.float32))(p)
        assert int(c["overflow"]) == 0
        return y, int(c["pairs"])

    uncut, pairs = routed(0, E)
    assert pairs == T * K
    parts = [routed(first, 4) for first in (0, 4, 8, 12)]
    assert sum(n for _, n in parts) == T * K
    close(sum(y for y, _ in parts), uncut, 1e-5)
    # and through the layer: shares of the whole block, the shared expert
    # (which every chip computes alike) counted once
    m = model()
    lp = jax.tree.map(lambda a: 10.0 * a,
                      reference.init_dense(CONFIG, 3)["layers"][0]["moe"])
    xb = x[None]
    shared_only = dict(lp, experts=jax.tree.map(jnp.zeros_like,
                                                lp["experts"]))
    block = lambda lp: jax.jit(lambda lp: m.expert_block(lp, xb)[0])(lp)  # noqa: E731
    shared = block(shared_only)
    full = dict(lp, experts={k: jnp.concatenate(
        [10.0 * jax.random.normal(jax.random.fold_in(keys[0], j), v.shape)
         for j in range(4)]) for k, v in lp["experts"].items()})
    m.held_experts, m.pair_budget = (0, 16), T * K
    total = block(full)
    acc = shared
    for j in range(4):
        m.held_experts = (4 * j, 4)
        part = dict(lp, experts={k: v[4 * j:4 * j + 4]
                                 for k, v in full["experts"].items()})
        acc = acc + block(part) - shared
    close(acc, total, 1e-5)


@pytest.mark.parametrize("interpret", [False, True])
def test_within_the_budget_no_pair_is_dropped_and_beyond_it_counted(
        interpret):
    """Every token to ONE held expert: inside the budget all of them are
    computed; past it the excess is left out and counted."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    T, d, f = 40, 16, 8
    x = jax.random.normal(keys[0], (T, d))
    p = {"wg": jax.random.normal(keys[1], (4, d, f)),
         "wu": jax.random.normal(keys[2], (4, d, f)),
         "wd": jax.random.normal(keys[3], (4, f, d))}
    experts = jnp.stack([jnp.full((T,), 6), jnp.full((T,), 1)], axis=1)
    weights = jnp.stack([jnp.full((T,), 0.75), jnp.full((T,), 0.25)], axis=1)
    want = 0.75 * (jax.nn.silu(x @ p["wg"][2]) * (x @ p["wu"][2])) @ p["wd"][2]

    def run(budget):
        return jax.jit(lambda p, x: moe.held_experts_apply(
            p, x, weights, experts.astype(jnp.int32), held=(4, 4),
            pair_budget=budget, block=8, compute_dtype=jnp.float32,
            interpret=interpret))(p, x)

    y, c = run(T)
    assert (int(c["pairs"]), int(c["overflow"]), int(c["max_load"])) == (
        T, 0, T)
    close(y, want, 1e-5)
    y, c = run(T - 8)
    assert (int(c["pairs"]), int(c["overflow"])) == (T, 8)
    close(y[:T - 8], want[:T - 8], 1e-5)
    assert float(jnp.max(jnp.abs(y[T - 8:]))) == 0.0


def test_the_expert_kernels_equal_the_gathered_products():
    """The Pallas grouped products (interpreted) against the gathered
    `einsum`, forward and gradients."""
    keys = jax.random.split(jax.random.PRNGKey(13), 6)
    T, d, f, E, K = 64, 16, 8, 16, 4
    x = jax.random.normal(keys[0], (T, d))
    w, e = moe.route_topk(x, jax.random.normal(keys[1], (d, E)), K)
    p = {"wg": jax.random.normal(keys[2], (4, d, f)),
         "wu": jax.random.normal(keys[3], (4, d, f)),
         "wd": jax.random.normal(keys[4], (4, f, d))}

    def f_(p, x, interpret):
        return jnp.sum(moe.held_experts_apply(
            p, x, w, e, held=(8, 4), pair_budget=96, block=8,
            compute_dtype=jnp.float32, interpret=interpret)[0] ** 2)

    grad = jax.jit(jax.value_and_grad(f_, argnums=(0, 1)), static_argnums=2)
    a, b = grad(p, x, True), grad(p, x, False)
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        close(u, v, 1e-5)


# ---------------------------------------------------------- through Trainer


def test_three_train_steps_equal_the_reference():
    """The tiny stack on `Trainer` (the model's own loss, Adagrad rows, the
    dense Adam) against the reference's `run`, as the benchmark compares a
    cell: the losses, every leaf's first gradient and its change."""
    program = builder.Program(CONFIG, MIX)
    program.model.compute_dtype = jnp.float32
    state = program.fresh_state(5)
    k = iter(range(harness.CHECK_STEPS))

    def next_batch():
        host = tokens.make_batch(MIX, 5, next(k))
        return host, program.put(host)

    state, got, batches = harness.check_steps(
        program, state, next_batch, CONFIG, reference)
    want = reference.run(CONFIG, batches, 5)
    assert got["grad"].keys() == want["grad"].keys()
    numbers = correct.compare(got, want)
    for name in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert numbers[name]["value"] < 1e-5, numbers
    assert numbers["grad_gap"]["value"] < 1e-4, numbers
    assert numbers["change_gap"]["value"] < 1e-3, numbers
    counters = np.asarray(program.counters(state))
    names = program.COUNTERS
    assert counters[names.index("moe_pairs")] > 0
    assert counters[names.index("moe_overflow")] == 0
    assert counters[names.index("dedup_overflow")] == 0
    # the planted fault and a state left unchanged read as gaps
    fault = correct.compare(
        reference.run(CONFIG, batches, 5, half_positions=True), want)
    assert fault["grad_median_gap"]["value"] > 0.05, fault
    still = copy.deepcopy(want)
    still["change"] = {k: 0.0 for k in want["change"]}
    assert correct.compare(still, want)["change_gap"]["value"] == 1.0


def test_an_overflowing_step_is_a_failed_step_for_the_benchmark():
    program = builder.Program(CONFIG, dict(MIX, pair_budget=8))
    state = program.fresh_state(5)
    host = tokens.make_batch(MIX, 5, 0)
    state, _ = program.step(state, program.put(host))
    counters = np.asarray(program.counters(state))
    assert "moe_overflow" in program.FAIL_COUNTERS
    assert counters[program.COUNTERS.index("moe_overflow")] > 0


# ------------------------------------------------------------- flash kernels


def test_flash_kernels_at_two_kv_heads_and_head_dim_256():
    """16 query heads over 2 key/value heads at head dim 256, causal,
    interpreted: forward and the gradients of q, k and v."""
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    B, H, Hkv, L, D = 1, 16, 2, 256, 256
    q = jax.random.normal(keys[0], (B, H, L, D))
    k = jax.random.normal(keys[1], (B, Hkv, L, D))
    v = jax.random.normal(keys[2], (B, Hkv, L, D))
    w = jax.random.normal(keys[3], (B, H, L, D))
    mask = jnp.ones((B, L), bool)
    scale = D ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, True, scale, 128, 128, True)

    def plain(q, k, v):
        return attention_reference(q, k, v, causal=True, sm_scale=scale)

    def both(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *b: jnp.sum(fn(*b) * w), argnums=(0, 1, 2))(*a)))

    (out, ours), (ref, want) = both(flash)(q, k, v), both(plain)(q, k, v)
    close(out, ref, 2e-5)
    for a, b in zip(ours, want):
        assert a.shape == b.shape
        close(a, b, 5e-5)
