"""The Mamba token stack (models/mamba_stack.py) and what it stands on: the
chunked Mamba-2 scan against its recurrence, the causal convolution's bias,
experts without a gate, the three blocks (Mamba-2 mixer, attention without
position, latent experts), the shares of a layer's chips, the selection
bias' rule lifted into models/token_stack.py, and train steps through
`Trainer`, each against the benchmark's plain reference
(benchmark/reference/nemotron.py, which imports nothing of deeprec_tpu);
and the cell's step compiled for a described v5e. Small sizes, CPU, seeded
random weights with norm weights moved off 1."""
import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, harness
from benchmark.builders import nemotron as builder
from benchmark.generators import tokens
from benchmark.reference import nemotron as reference
from deeprec_tpu import nn
from deeprec_tpu.models import MambaStackLM
from deeprec_tpu.ops import moe
from deeprec_tpu.ops.ssd import ssd_recurrence, ssd_scan
from deeprec_tpu.utils import backend, scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron3-super-120b-a12b.json")) as f:
    FULL = json.load(f)
GAMMA = 0.001
CONFIG = dict(
    FULL, name="tiny-mamba", hybrid_override_pattern="MEM*E",
    num_hidden_layers=5, hidden_size=32, mamba_num_heads=4,
    mamba_head_dim=8, n_groups=2, ssm_state_size=8, chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    n_routed_experts=2, num_experts=2, num_experts_per_tok=4,
    moe_intermediate_size=16, moe_ffn_hidden_size=16, moe_latent_size=12,
    moe_shared_expert_intermediate_size=24, vocab_size=48, emb_dim=32,
    capacity=128, bias_update_rate=GAMMA,
    deployment=dict(FULL["deployment"], router_outputs=16,
                    first_expert_held=4),
    dense_optimizer=dict(FULL["dense_optimizer"], lr=1e-3))
MIX = {"name": "tiny-seq", "generator": "tokens", "batch": 2, "seq_len": 32,
       "vocab": 48, "zipf_a": 1.1, "unique_budget": 40, "pair_budget": 256}
# set from this file's own readings: the program reads under 1e-6 on every
# number, each fault at least 6x over the limit it fails
LIMITS = {"loss1_gap": 1e-5, "grad_gap": 2e-5, "grad_median_gap": 1e-6,
          "change_gap": 0.5}
BIAS = ("layers.1.moe.bias", "layers.4.moe.bias")


def program(**mix):
    p = builder.Program(CONFIG, dict(MIX, **mix))
    p.model.compute_dtype = jnp.float32
    return p


def model(**kw) -> MambaStackLM:
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
    for layer in p["layers"]:
        layer["norm"] = 1.0 + 0.3 * jax.random.normal(next(keys), (32,))
        (kind, block), = ((k, v) for k, v in layer.items() if k != "norm")
        for name in ("w_in", "w_out", "wq", "wk", "wv", "wo", "router",
                     "w_down", "w_up"):
            if name in block:
                block[name] = 10.0 * block[name]
        if kind == "mamba":
            block["norm"] = 1.0 + 0.3 * jax.random.normal(
                next(keys), block["norm"].shape)
            block["D"] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                       block["D"].shape)
        if kind == "moe":
            block["experts"] = jax.tree.map(lambda w: 10.0 * w,
                                            block["experts"])
            block["shared"] = jax.tree.map(lambda w: 10.0 * w,
                                           block["shared"])
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


# ------------------------------------------------------------------ the scan


@pytest.mark.parametrize("T,chunk,H,G", [(45, 8, 4, 2), (64, 16, 4, 1),
                                         (200, 128, 2, 2)])
def test_the_chunked_scan_equals_the_recurrence(T, chunk, H, G):
    """Forward and the gradients of all five inputs, at lengths that are
    and are not a multiple of the chunk (200 of 128: a padded last chunk),
    heads sharing a group's B and C or each its own."""
    k = jax.random.split(jax.random.PRNGKey(T), 6)
    x = jax.random.normal(k[0], (2, T, H, 8))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, T, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B, C = (jax.random.normal(k[i], (2, T, G, 16)) for i in (3, 4))
    w = jax.random.normal(k[5], x.shape)
    args = (0, 1, 2, 3, 4)
    out, g = both(lambda *a: ssd_scan(*a, chunk=chunk,
                                      compute_dtype=jnp.float32),
                  w, args)(x, dt, A, B, C)
    want, g_ref = both(ssd_recurrence, w, args)(x, dt, A, B, C)
    close(out, want, 1e-5)
    for a, b in zip(g, g_ref):
        assert a.shape == b.shape
        close(a, b, 1e-5)
    # in bf16 operands the states between chunks stay f32: the result is
    # the recurrence's to bf16's rounding, not to a drifting state's
    close(ssd_scan(x, dt, A, B, C, chunk), want, 2e-2)


def test_the_conv_takes_an_optional_bias():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    b = jax.random.normal(jax.random.PRNGKey(2), (6,))
    plain = nn.causal_conv1d(x, w)
    xp = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    np.testing.assert_array_equal(
        plain, sum(xp[:, j:j + 9] * w[j] for j in range(4)))
    np.testing.assert_array_equal(nn.causal_conv1d(x, w, b), plain + b)


def test_held_experts_without_a_gate():
    """`p` without `wg`: `w activation(x Wu) Wd` a pair, against a plain
    loop over the held experts; with `wg` the gated form is what it was."""
    T, d, f, E, K, held = 24, 8, 12, 8, 3, (2, 4)
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (T, d))
    p = {"wg": jax.random.normal(ks[1], (4, d, f)),
         "wu": jax.random.normal(ks[2], (4, d, f)),
         "wd": jax.random.normal(ks[3], (4, f, d))}
    w, e = moe.route_topk(x, jax.random.normal(ks[4], (d, E)), K)
    run = lambda p, act: moe.held_experts_apply(  # noqa: E731
        p, x, w, e, held=held, pair_budget=T * K, block=8,
        compute_dtype=jnp.float32, activation=act)[0]
    relu2 = lambda v: jnp.square(jax.nn.relu(v))  # noqa: E731
    want_plain, want_gated = jnp.zeros((T, d)), jnp.zeros((T, d))
    for j in range(4):
        share = jnp.sum(jnp.where(e == held[0] + j, w, 0.0), axis=-1)[:, None]
        up = jnp.dot(x, p["wu"][j], precision="highest")
        gate = jnp.dot(x, p["wg"][j], precision="highest")
        want_plain += share * jnp.dot(relu2(up), p["wd"][j],
                                      precision="highest")
        want_gated += share * jnp.dot(jax.nn.silu(gate) * up, p["wd"][j],
                                      precision="highest")
    with jax.default_matmul_precision("highest"):
        close(run({"wu": p["wu"], "wd": p["wd"]}, relu2), want_plain, 1e-5)
        close(run(p, jax.nn.silu), want_gated, 1e-5)


# ---------------------------------------------------------------- the blocks


@pytest.mark.parametrize("T", [32, 28])
def test_the_mamba_mixer_equals_the_reference(T):
    """The mixer (input projection, the convolution with its bias, the
    chunked scan, the D skip, the gated group norm) against the
    reference's per-token recurrence: forward and the gradients of every
    weight and of the input, at a length that is no multiple of the
    chunk too."""
    m, lp = model(), params()["layers"][0]["mamba"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), (2, T, 32))
    ref = lambda lp, x: per_sequence(  # noqa: E731
        lambda s: reference.mamba(lp, s, CONFIG, "highest"), x)
    with jax.default_matmul_precision("highest"):
        (out, g), (want, g_ref) = (both(m.mamba, w, (0, 1))(lp, x),
                                   both(ref, w, (0, 1))(lp, x))
    close(out, want, 1e-5)
    same_tree(g[0], g_ref[0], 1e-4)
    close(g[1], g_ref[1], 1e-4)
    # the D skip is seen
    no_skip = per_sequence(lambda s: reference.mamba(
        lp, s, CONFIG, "highest", no_skip=True), x)
    assert float(jnp.max(jnp.abs(no_skip - want))) > 1e-2


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_the_attention_equals_the_reference(path):
    """Four query heads over two key/value heads, causal, no position
    encoding: by `attention_reference` and by the interpreted kernels,
    forward and gradients; and positions do not matter but through the
    causal mask (a sequence turned round gives another result)."""
    m = model(flash_block=512 if path == "plain" else 8,
              interpret=path == "kernels")
    lp = params()["layers"][3]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    ref = lambda lp, x: per_sequence(  # noqa: E731
        lambda s: reference.attention(lp, s, CONFIG, "highest"), x)
    with jax.default_matmul_precision("highest"):
        (out, g), (want, g_ref) = (both(m.attention, w, (0, 1))(lp, x),
                                   both(ref, w, (0, 1))(lp, x))
    close(out, want, 2e-5)
    same_tree(g[0], g_ref[0], 1e-4)
    close(g[1], g_ref[1], 1e-4)
    # the last position sees every key with no position: a permutation of
    # the keys before it leaves its output alone
    perm = jnp.concatenate([jnp.arange(30, -1, -1), jnp.array([31])])
    moved = reference.attention(lp, x[0, perm], CONFIG, "highest")
    close(moved[-1], want[0, -1], 1e-5)


def test_the_latent_expert_block_equals_the_reference():
    """Routed by score + bias over all 16 outputs, the held experts run
    relu^2 in the 12-wide latent between the two projections, the shared
    expert on the whole token: forward, the loads, and the gradients of
    every leaf but the bias (none) and of the input."""
    m_, lp = model(), params()["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    ours = lambda lp, x: m_.expert_block(lp, x)[0]  # noqa: E731
    ref = lambda lp, x: per_sequence(  # noqa: E731
        lambda s: reference.expert_block(lp, s, CONFIG, "highest")[0], x)
    (out, g), (want, g_ref) = (both(ours, w, (0, 1))(lp, x),
                               both(ref, w, (0, 1))(lp, x))
    close(out, want, 2e-5)
    same_tree(g[0], g_ref[0], 1e-4, but=("bias",))
    assert float(jnp.max(jnp.abs(g[0]["bias"]))) == 0.0
    close(g[1], g_ref[1], 1e-4)
    _, counters = jax.jit(m_.expert_block)(lp, x)
    want_load = sum(reference.expert_block(lp, x[b], CONFIG, "highest")[1]
                    for b in range(2))
    assert (np.asarray(counters["load"]) == np.asarray(want_load)).all()
    assert int(counters["pairs"]) == int(counters["load"][4:6].sum()) > 0
    # relu^2 leaves a hidden unit live where its product is above 0: some
    # of the pairs' units, never more than the pairs hold
    live = int(counters["hidden_live"])
    assert 0 < live < int(counters["pairs"]) * CONFIG["moe_intermediate_size"]


# ------------------------------------------------------------------ the share


def _uncut(kind: str):
    """A tiny uncut layer of each kind and its configuration: 16 Mamba-2
    heads in 8 groups, 16 query heads over 2 key/value heads, 64 experts."""
    cfg = dict(CONFIG, hybrid_override_pattern=kind, num_hidden_layers=1,
               mamba_num_heads=16, n_groups=8, num_attention_heads=16,
               num_key_value_heads=2, n_routed_experts=64, num_experts=64,
               deployment=dict(CONFIG["deployment"], router_outputs=64,
                               first_expert_held=0))
    return cfg, params(7, cfg)["layers"][0]


def _mamba_share(lp, g, H, G, P, N):
    """Group g's heads and channels of an uncut Mamba-2 layer's leaves."""
    r, inner = H // G, H * P
    heads = np.arange(g * r, (g + 1) * r)
    ch = (heads[:, None] * P + np.arange(P)).reshape(-1)
    grp = g * N + np.arange(N)
    xbc = np.concatenate([ch, inner + grp, inner + G * N + grp])
    cols = np.concatenate([ch, inner + xbc, 2 * inner + 2 * G * N + heads])
    return {"w_in": lp["w_in"][:, cols], "conv": lp["conv"][:, xbc],
            "conv_bias": lp["conv_bias"][xbc], "dt_bias": lp["dt_bias"][heads],
            "A_log": lp["A_log"][heads], "D": lp["D"][heads],
            "norm": lp["norm"][ch], "w_out": lp["w_out"][ch]}


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """The 8 head shares of a Mamba-2 layer (a group and its heads each)
    and of an attention layer (2 query heads and the key/value head they
    read each), and the 64 expert shares of a latent expert layer (the
    router, the latent projections and the shared expert, which every chip
    computes alike, counted once), add up to the uncut layer, which is the
    reference's."""
    cfg, lp = _uncut(kind)
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 32, 32))
    m = builder.Program(cfg, MIX).model
    m.compute_dtype = jnp.float32
    want, _ = reference.layer(lp, x[0], kind, cfg, "highest")
    n = m._norm(x, lp["norm"])
    with jax.default_matmul_precision("highest"):
        if kind == "M":
            m.mamba_heads, m.mamba_groups = 2, 1
            parts = [jax.jit(m.mamba)(_mamba_share(lp["mamba"], g, 16, 8, 8,
                                                   8), n) for g in range(8)]
        elif kind == "*":
            m.attn_heads, m.attn_kv_heads = 2, 1
            a, D = lp["attn"], 8

            def share(s):
                q = np.arange(2 * s * D, 2 * (s + 1) * D)
                kv = np.arange((s // 4) * D, (s // 4 + 1) * D)
                return {"wq": a["wq"][:, q], "wk": a["wk"][:, kv],
                        "wv": a["wv"][:, kv], "wo": a["wo"][q]}
            parts = [jax.jit(m.attention)(share(s), n) for s in range(8)]
        else:
            m.pair_budget = 32 * 4
            e = lp["moe"]
            alike = m._mm(jax.nn.relu(m._mm(n[0], e["shared"]["wu"])) ** 2,
                          e["shared"]["wd"])
            parts = []
            for first in range(64):
                m.held_experts = (first, 1)
                s = dict(e, experts=jax.tree.map(
                    lambda v: v[first:first + 1], e["experts"]))
                y, c = jax.jit(m.expert_block)(s, n)
                assert int(c["overflow"]) == 0
                parts.append(y - alike)
            parts.append(alike[None])
    close(x[0] + sum(parts)[0], want, 1e-5)


# ---------------------------------------------------------------- the stack


def test_the_model_equals_the_reference():
    """The whole stack (M E M * E) under its layer remat: the loss and the
    gradient of every leaf and of the rows, against the reference's
    `loss_fn`; the counters come from the two expert layers alone."""
    from deeprec_tpu.training.trainer import ModelInputs

    m, p = model(), params(2)
    rows = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    labels = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 48)

    def ours(p, rows):
        inputs = ModelInputs(pooled={}, seq={"tok": (rows, None)}, dense={})
        loss, mets = m.loss(p, inputs, {"label": labels})
        return loss, mets

    def ref(p, rows):
        flat = rows.reshape(64, 32)
        idx = jnp.arange(64).reshape(2, 32)
        return reference.loss_fn(p, flat, idx, labels, CONFIG, "highest",
                                 False, False, False)

    (loss, mets), g = jax.jit(jax.value_and_grad(ours, (0, 1),
                                                 has_aux=True))(p, rows)
    (want, loads), g_ref = jax.jit(jax.value_and_grad(ref, (0, 1),
                                                      has_aux=True))(p, rows)
    close(loss, want, 1e-6)
    same_tree(g[0], g_ref[0], 1e-4, but=BIAS)
    close(g[1], g_ref[1], 1e-4)
    assert (np.asarray(mets["moe_load"]) == np.asarray(loads)).all()
    assert mets["moe_load"].shape == (2, 16)


def test_the_stack_names_its_blocks_and_parts():
    """Every Mamba-2 mixer stands under `block_mamba`, its scan under
    `ssd_scan` and its convolution under `mamba_conv`; the latent
    projections under `moe_latent` and the shared expert under
    `moe_shared` inside `block_moe`; the rule under `router_bias_update`
    inside `phase_dense_apply`."""
    p = program()
    state = p.fresh_state(0)
    text = p.trainer._train_step.lower(
        state, p.put(tokens.make_batch(MIX, 1, 0)),
        jnp.float32(0.05)).as_text(debug_info=True)
    for name in (scopes.BLOCK_MAMBA, scopes.SSD_SCAN, scopes.MAMBA_CONV,
                 scopes.MOE_LATENT, scopes.MOE_SHARED, scopes.BLOCK_ATTN,
                 scopes.BLOCK_MOE):
        assert name in text, name
    assert f"{scopes.BLOCK_MAMBA}/{scopes.SSD_SCAN}" in text
    assert f"{scopes.BLOCK_MAMBA}/{scopes.MAMBA_CONV}" in text
    assert f"{scopes.BLOCK_MOE}/{scopes.MOE_LATENT}" in text
    assert f"{scopes.PHASE_DENSE_APPLY}/{scopes.ROUTER_BIAS_UPDATE}" in text


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
    bias = {k: np.asarray(v) for k, v in p.dense_params(state).items()
            if k in BIAS}
    return got, batches, reference.run(CONFIG, batches, 5), counters, bias


def test_three_train_steps_equal_the_reference(three_steps):
    """The tiny stack on `Trainer` (the model's own loss, Adagrad rows, the
    dense Adam, the rule's leaves) against the reference's `run`, as the
    benchmark compares a cell: the losses, every leaf's first gradient and
    its change; the bias leaves after three steps are the reference's to
    the bit, moved by whole steps of gamma (the rule, lifted into
    models/token_stack.py, moves the expert layers 1 and 4 of M E M * E)."""
    got, _, want, counters, bias = three_steps
    assert got["grad"].keys() == want["grad"].keys()
    numbers = correct.compare(got, want)
    for name in ("loss1_gap", "loss2_gap", "loss3_gap"):
        assert numbers[name]["value"] < 1e-5, numbers
    assert numbers["grad_gap"]["value"] < 1e-5, numbers
    assert numbers["change_gap"]["value"] < 1e-3, numbers
    assert correct.verdict(numbers, LIMITS)[0]
    assert counters["moe_pairs"] > 0
    assert 0 < counters["moe_hidden_live"] < counters["moe_pairs"] * 16
    assert counters["moe_overflow"] == counters["dedup_overflow"] == 0
    for name in BIAS:
        assert got["grad"][name] == want["grad"][name] == 0.0
        np.testing.assert_array_equal(bias[name], np.asarray(
            want["bias"][name], np.float32))
        moves = np.round(bias[name] / GAMMA).astype(int)
        assert np.abs(moves).max() >= 1
        np.testing.assert_allclose(bias[name], moves * np.float32(GAMMA),
                                   rtol=1e-6)


@pytest.mark.parametrize("fault", ["control_fp8", "fault_half_positions",
                                   "fault_bf16_state", "fault_no_skip"])
def test_a_fault_of_the_reference_fails_the_limits(three_steps, fault):
    """float8 operands, half the positions left out of the loss, the scan's
    state rounded to bf16 after every token, and the D skip left out each
    read as not correct against limits the program passes."""
    _, batches, want, _, _ = three_steps
    read = correct.compare(
        reference.run(CONFIG, batches, 5, **reference.CONTROLS[fault]), want)
    assert not correct.verdict(read, LIMITS)[0], read


# ------------------------------------------------------- the cell's program


def test_the_cells_step_compiles_for_a_described_v5e(monkeypatch):
    """The cell's whole train step (`nemotron3-super.seq8k`: eleven layers
    at the published widths, 16 Mamba-2 heads, 4 query heads, 8 of 512
    experts at the mix's pair budget, one sequence of 8,192) lowered and
    compiled for a v5e that is described and not attached: the flash and
    grouped-product kernels are in it, and set-up's peak (arguments +
    temporaries + the check's f32 copy of the dense leaves) stays under the
    15.0e9 B that are safe (PERF.md section 4)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    dev = SingleDeviceSharding(topo.devices[0])
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cell = harness.load_cell("nemotron3-super.seq8k")
    tr = cell.builder.Program(cell.config, cell.mix).trainer
    sd = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=dev)
    shapes = jax.eval_shape(tr.init, np.int32(1))
    dense = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes.dense))
    assert dense == 633_756_656
    batch = jax.eval_shape(tr.stage_batch, cell.generator.make_batch(
        cell.mix, 1, 0))
    compiled = tr._train_step.lower(
        jax.tree.map(sd, shapes), jax.tree.map(sd, batch),
        jax.ShapeDtypeStruct((), np.float32)).compile()
    hlo = compiled.as_text()
    for name in (scopes.KERNEL_FLASH_FWD, scopes.KERNEL_GROUPED_MATMUL):
        assert name in hlo, name
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + 4 * dense)
    assert peak < 15.0e9, peak
