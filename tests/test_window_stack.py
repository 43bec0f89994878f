"""The window-and-global token stack (models/window_stack.py) and what it
stands on: the mixer with and without a window and with and without rotary,
the expert block with its early router, the shares of a layer's chips and
three train steps through `Trainer`, each against the benchmark's plain
reference (benchmark/reference/smallthinker.py, which imports nothing of
deeprec_tpu); the flash kernels with a window at seven query heads a
key/value head and head dim 128, and which blocks their walks fetch. Small
sizes, CPU, seeded random weights with norm weights moved off 1."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, harness
from benchmark.builders import smallthinker as builder
from benchmark.generators import tokens
from benchmark.reference import smallthinker as reference
from deeprec_tpu.models import WindowStackLM
from deeprec_tpu.ops.flash_attention import (_visible_keys, _visible_queries,
                                             _walk, attention_reference,
                                             flash_attention)
from deeprec_tpu.training.trainer import ModelInputs
from deeprec_tpu.utils import scopes

CONFIG = {
    "name": "tiny-window", "builder": "smallthinker",
    "reference": "smallthinker", "work": "smallthinker", "head_dim": 16,
    "hidden_size": 32, "max_position_embeddings": 64,
    "moe_ffn_hidden_size": 16, "moe_num_active_primary_experts": 4,
    "moe_num_primary_experts": 4, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_hidden_layers": 4, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1], "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "vocab_size": 48,
    "deployment": {"router_outputs": 16, "first_expert_held": 4},
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
LIMITS = {"grad_median_gap": 0.005, "change_gap": 0.5}


def model(**kw) -> WindowStackLM:
    program = builder.Program(CONFIG, MIX)
    for k, v in {"compute_dtype": jnp.float32, **kw}.items():
        setattr(program.model, k, v)
    return program.model


def params(seed: int = 0):
    """The reference's own weights from a seed, the norms moved off 1 and
    the matrices widened, so that softmaxes and the router are far from
    flat."""
    p = reference.init_dense(CONFIG, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(100 + seed), 16))
    for layer in p["layers"]:
        for name in ("in_norm", "post_norm"):
            layer[name] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                        layer[name].shape)
        layer["mixer"] = jax.tree.map(lambda w: 10.0 * w, layer["mixer"])
        layer["moe"] = jax.tree.map(lambda w: 10.0 * w, layer["moe"])
    return p


def close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b))) + 1e-30
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale, (
        float(jnp.max(jnp.abs(a - b))), scale)


def both(fn, w, argnums):
    return jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *b: jnp.sum(fn(*b) * w), argnums=argnums)(*a)))


def same_tree(ours, want, tol):
    ours, want = reference.leaf_names(ours), reference.leaf_names(want)
    assert ours.keys() == want.keys()
    for name in want:
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
        close(ours[name], want[name], tol)


# ------------------------------------------------------------------ the mixer

# layer i of this layout: 0 global without rotary (the model's first layer),
# 1 window without, 2 global with, 3 window with (the model's other three)
KINDS = {"global_nope": 0, "window_nope": 1, "global_rope": 2,
         "window_rope": 3}


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("T,path", [(12, "plain"), (40, "plain"),
                                    (40, "blockwise"), (40, "kernels")])
def test_the_mixer_equals_the_reference(kind, T, path):
    """Window 16 at T = 12 (under it) and T = 40 (over it, no multiple of
    it), by the plain path, the blockwise fallback and the interpreted
    kernels: forward and the gradients of the weights and the input."""
    i, W = KINDS[kind], 16
    m = model(sliding_window=W, sliding_window_layout=(0, 1, 0, 1),
              rope_layout=(0, 0, 1, 1),
              flash_block=512 if path == "plain" else 8,
              interpret=path == "kernels")
    window, rope = m.is_window(i), m.has_rope(i)
    assert (window, rope) == ("window" in kind, "_rope" in kind)
    lp = params()["layers"][i]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    config = dict(CONFIG, sliding_window_size=W)
    ref = lambda lp, x: jnp.stack([reference.attention(  # noqa: E731
        lp, x[b], config, "highest", window, rope) for b in range(2)])
    with jax.default_matmul_precision("highest"):
        (out, g), (want, g_ref) = (
            both(lambda lp, x: m.attention(i, lp, x), w, (0, 1))(lp, x),
            both(ref, w, (0, 1))(lp, x))
    close(out, want, 2e-5)
    same_tree(g[0], g_ref[0], 1e-4)
    close(g[1], g_ref[1], 1e-4)
    if window and T > W:   # the window is seen: without it the result moves
        other = jnp.stack([reference.attention(
            lp, x[b], config, "highest", False, rope) for b in range(2)])
        assert float(jnp.max(jnp.abs(other - want))) > 1e-2


# ----------------------------------------------------------- the expert block


def test_the_expert_block_with_its_early_router_equals_the_reference():
    """Routed on `n` (the mixer's normed input), applied to `m` (the
    post-attention norm): forward and the gradients of the router, the
    experts, and both inputs."""
    m_, lp = model(), params()["layers"][1]["moe"]
    n = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32))
    m = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), m.shape)

    def ours(lp, n, m):
        return m_.expert_block(lp, m, *m_.routed(lp, n))[0]

    def ref(lp, n, m):
        return jnp.stack([reference.expert_block(
            lp, m[b], *reference.route(lp, n[b], CONFIG, "highest"),
            CONFIG, "highest") for b in range(2)])

    (out, g), (want, g_ref) = (both(ours, w, (0, 1, 2))(lp, n, m),
                               both(ref, w, (0, 1, 2))(lp, n, m))
    close(out, want, 2e-5)
    same_tree(g[0], g_ref[0], 1e-4)
    for a, b in zip(g[1:], g_ref[1:]):
        assert float(jnp.max(jnp.abs(b))) > 0
        close(a, b, 1e-4)
    # ReLU and not SiLU: a hidden unit under 0 gives nothing
    _, counters = jax.jit(lambda lp, n, m: m_.expert_block(
        lp, m, *m_.routed(lp, n)))(lp, n, m)
    live, pairs = int(counters["hidden_live"]), int(counters["pairs"])
    assert 0 < live < pairs * 16 and int(counters["overflow"]) == 0
    # and counted for the ReLU model alone: under SiLU nobody reads it
    x = m.reshape(-1, 32)
    assert "hidden_live" not in m_.held(lp["experts"], x,
                                        *m_.route(lp["router"], x))[1]


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """16 experts, top 4, cut in 4 shares of 4: the four chips' layers,
    with what every chip computes alike (the residual stream after
    attention) counted once, equal the layer of a chip that holds all 16;
    and the reference's uncut layer says the same."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    lp = params(3)["layers"][3]
    whole = {k: 0.2 * jax.random.normal(keys[j], (16,) + v.shape[1:])
             for j, (k, v) in enumerate(lp["moe"]["experts"].items())}
    x = jax.random.normal(keys[3], (1, 32, 32))
    m = model(pair_budget=32 * 4)

    def layer(first, count):
        m.held_experts = (first, count)
        p = dict(lp, moe=dict(lp["moe"], experts={
            k: v[first:first + count] for k, v in whole.items()}))
        return jax.jit(lambda p, x: m._layer(3, p, x))(p, x)

    uncut, counters = layer(0, 16)
    assert int(counters["pairs"]) == 32 * 4
    parts = [layer(first, 4) for first in (0, 4, 8, 12)]
    assert sum(int(c["pairs"]) for _, c in parts) == 32 * 4
    assert all(int(c["overflow"]) == 0 for _, c in parts)
    h = x + jax.jit(lambda p, x: m.attention(3, p["mixer"], m._norm(
        x, p["in_norm"])))(lp, x)
    close(sum(y for y, _ in parts) - 3 * h, uncut, 1e-5)
    config = dict(CONFIG, moe_num_primary_experts=16,
                  deployment={"router_outputs": 16, "first_expert_held": 0})
    want = reference.layer(dict(lp, moe=dict(lp["moe"], experts=whole)),
                           x[0], config, "highest", True, True)
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
    """The remat keeps the flash forward's output and log-sum-exp by name,
    so the gradient's program holds ONE forward kernel a layer, where the
    parent's (a remat that keeps nothing of attention) holds two; loss and
    gradients are the parent's. Interpreted kernels."""
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
    program = builder.Program(CONFIG, MIX)
    program.model.compute_dtype = jnp.float32
    state = program.fresh_state(5)
    k = iter(range(harness.CHECK_STEPS))

    def next_batch():
        host = tokens.make_batch(MIX, 5, next(k))
        return host, program.put(host)

    state, got, batches = harness.check_steps(
        program, state, next_batch, CONFIG, reference)
    counters = dict(zip(program.COUNTERS,
                        np.asarray(program.counters(state)).tolist()))
    return got, batches, reference.run(CONFIG, batches, 5), counters


def test_three_train_steps_equal_the_reference(three_steps):
    """The tiny stack on `Trainer` (the model's own loss, Adagrad rows, the
    dense Adam) against the reference's `run`, as the benchmark compares a
    cell: the losses, every leaf's first gradient and its change."""
    got, _, want, counters = three_steps
    assert got["grad"].keys() == want["grad"].keys()
    numbers = correct.compare(got, want)
    for name in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert numbers[name]["value"] < 1e-5, numbers
    assert numbers["grad_gap"]["value"] < 1e-4, numbers
    assert numbers["change_gap"]["value"] < 1e-3, numbers
    assert correct.verdict(numbers, LIMITS)[0]
    assert counters["moe_pairs"] > 0 and counters["moe_hidden_live"] > 0
    assert counters["moe_overflow"] == counters["dedup_overflow"] == 0
    still = copy.deepcopy(want)
    still["change"] = {k: 0.0 for k in want["change"]}
    assert correct.compare(still, want)["change_gap"]["value"] == 1.0


@pytest.mark.parametrize("fault", ["fault_no_window", "fault_half_positions",
                                   "control_fp8"])
def test_a_fault_of_the_reference_fails_the_limits(three_steps, fault):
    """The window left out of the window layers (sequences of 32 against a
    window of 8), half the positions left out of the loss, and float8
    operands each read as not correct against limits the program passes."""
    _, batches, want, _ = three_steps
    assert correct.verdict(correct.compare(want, want), LIMITS)[0]
    read = correct.compare(
        reference.run(CONFIG, batches, 5, **reference.CONTROLS[fault]), want)
    assert not correct.verdict(read, LIMITS)[0], read


def test_an_overflowing_step_is_a_failed_step_for_the_benchmark():
    program = builder.Program(CONFIG, dict(MIX, pair_budget=8))
    state = program.fresh_state(5)
    state, _ = program.step(state, program.put(tokens.make_batch(MIX, 5, 0)))
    counters = np.asarray(program.counters(state))
    assert "moe_overflow" in program.FAIL_COUNTERS
    assert counters[program.COUNTERS.index("moe_overflow")] > 0


# ------------------------------------------------------------- flash kernels


@pytest.mark.parametrize("window", [128, 100, None])
def test_flash_kernels_with_a_window_at_seven_query_heads_a_group(window):
    """7 query heads over 1 key/value head at head dim 128, causal, a
    window that is and is not a multiple of the block of 64, interpreted:
    forward and the gradients of q, k and v."""
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    B, H, Hkv, L, D = 1, 7, 1, 256, 128
    q = jax.random.normal(keys[0], (B, H, L, D))
    k = jax.random.normal(keys[1], (B, Hkv, L, D))
    v = jax.random.normal(keys[2], (B, Hkv, L, D))
    w = jax.random.normal(keys[3], (B, H, L, D))
    mask = jnp.ones((B, L), bool)
    scale = D ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, True, scale, 64, 64, True,
                               window)

    def plain(q, k, v):
        return attention_reference(q, k, v, causal=True, sm_scale=scale,
                                   window=window)

    with jax.default_matmul_precision("highest"):
        (out, ours), (ref, want) = (both(flash, w, (0, 1, 2))(q, k, v),
                                    both(plain, w, (0, 1, 2))(q, k, v))
    close(out, ref, 2e-5)
    for a, b in zip(ours, want):
        assert a.shape == b.shape
        close(a, b, 5e-5)


def test_a_window_needs_the_causal_mask():
    q = jnp.zeros((1, 1, 8, 8))
    with pytest.raises(ValueError, match="causal"):
        attention_reference(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, jnp.ones((1, 8), bool), False, None, 8, 8,
                        True, 4)


@pytest.mark.parametrize("T,block_q,block_k,window", [
    (16384, 512, 512, 4096), (16384, 512, 512, None), (2048, 256, 128, 300),
    (2048, 128, 256, 1000), (1024, 128, 128, 1)])
def test_a_block_no_row_sees_is_never_fetched(T, block_q, block_k, window):
    """The blocks the index maps name over a walk's steps are exactly the
    blocks that hold a (query, key) pair the mask lets through: none
    outside the window or above the diagonal, for the walks over key
    blocks (forward, dQ) and over query blocks (dK/dV) alike; and the grid
    is as long as the longest walk and no longer."""
    num_qb, num_kb = T // block_q, T // block_k
    t, s = np.arange(T)[:, None], np.arange(T)[None, :]
    seen = (s <= t) if window is None else (s <= t) & (t - s < window)
    blocks = seen.reshape(num_qb, block_q, num_kb, block_k).any(axis=(1, 3))
    steps, key_block = _walk(_visible_keys, num_qb, block_q, block_k, num_kb,
                             True, window)
    i, j = np.meshgrid(np.arange(num_qb), np.arange(steps), indexing="ij")
    fetched = np.zeros_like(blocks)
    fetched[i, np.asarray(key_block(i, j))] = True
    assert (fetched == blocks).all()
    assert steps == blocks.sum(axis=1).max()
    q_steps, query_block = _walk(_visible_queries, num_kb, block_q, block_k,
                                 num_qb, True, window)
    kb, j = np.meshgrid(np.arange(num_kb), np.arange(q_steps), indexing="ij")
    fetched = np.zeros_like(blocks)
    fetched[np.asarray(query_block(kb, j)), kb] = True
    assert (fetched == blocks).all()
    assert q_steps == blocks.sum(axis=0).max()
    if (T, window) == (16384, 4096):   # the benchmark's cell
        assert steps == q_steps == 9 and blocks.sum() == 32 * 9 - 36
        k_first, k_last = _visible_keys(np.arange(num_qb), block_q, block_k,
                                        num_kb, True, window, xp=np)
        assert (k_last - k_first + 1).sum() == blocks.sum()
