"""The vocabulary of trace names (utils/scopes.py): what reaches the compiled
step's `op_name`s, what reaches the host plane of a trace, and that it is
the one the benchmark's reader holds (benchmark/phases.json merged with
every benchmark/phases/*.json)."""
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark import phase_reduce
from deeprec_tpu.data import SyntheticCriteo
from deeprec_tpu.models import DLRMDCN
from deeprec_tpu.optim import Adagrad
from deeprec_tpu.training import Trainer
from deeprec_tpu.utils import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = scopes.vocabulary()
# what executes nothing: a trace never times it
FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
# the shard_map call's own name, which the partitioner gives to what it makes
# at the call's boundary; the program's operations stand under the body
BOUNDARY = re.compile(r"^jit\([^)]*\)/shard_map(/[\w\-]+\.\d+)?$")


def model():
    return DLRMDCN(emb_dim=8, capacity=1 << 10, bottom=(16, 8), top=(16, 1),
                   num_cat=26, num_dense=13, cross_depth=1)


def batches(n, batch_size=64):
    gen = SyntheticCriteo(batch_size=batch_size, num_cat=26, num_dense=13,
                          vocab=300, seed=3)
    return [{k: jnp.asarray(v) for k, v in gen.batch().items()}
            for _ in range(n)]


@pytest.fixture(scope="module")
def mesh():
    from deeprec_tpu.parallel import make_mesh

    return make_mesh(8)


def op_names(compiled):
    """[(opcode, op_name)] of a compiled program's instructions that carry
    a name of jax's own (`jit(...)/...`; the compiler's and the
    parameters' names are not the program's)."""
    out = []
    for line in compiled.as_text().splitlines():
        m = re.search(r'op_name="((?:[^"\\]|\\.)*)"', line)
        op = re.search(r"= .*? ([\w\-]+)\(", line)
        if m and op and m.group(1).startswith("jit(") \
                and not BOUNDARY.match(m.group(1)):
            out.append((op.group(1), m.group(1)))
    return out


def found(names):
    got = [phase_reduce.scope_of(n, VOCAB) for _, n in names]
    return ({s.phase for s in got}, {s.stage for s in got},
            {s.rows for s in got})


def assert_every_instruction_has_a_phase(names):
    bare = [(op, n) for op, n in names if op not in FREE
            and not phase_reduce.scope_of(n, VOCAB).phase]
    assert not bare, bare[:10]


# ----------------------------------------------------------- the vocabulary


def test_names_are_plain_and_distinct():
    names = [n for key, group in VOCAB.items() if key != "groups"
             for n in ([group] if isinstance(group, str) else group)]
    names += [n for group in VOCAB["groups"].values() for n in group["names"]]
    names += [scopes.exchange_chunk(3), scopes.hier_intra_chunk(0),
              scopes.hier_inter_chunk(12)]
    assert len(set(names)) == len(names)
    for name in names:
        assert re.fullmatch(r"[A-Za-z_][A-Za-z0-9_.]*", name), name
    assert all(n.startswith("phase_") for n in scopes.PHASES)
    assert all(n.startswith("engine_") for n in scopes.STAGES)
    assert all(n.startswith("rows_") for n in scopes.ROWS)
    assert all(n.startswith("block_") for n in scopes.BLOCKS)
    assert all(n.startswith("deeprec.") for n in scopes.HOST_SPANS)


def test_the_benchmark_holds_the_same_vocabulary():
    """The union of benchmark/phases.json and benchmark/phases/*.json (a
    later scope is a new file there; phases.json itself stays as it is) is
    what the program writes."""
    merged = phase_reduce.load_vocabulary()
    assert merged == VOCAB
    # the first file alone is the engine's and the trainers' part of it
    with open(os.path.join(ROOT, "benchmark", "phases.json")) as f:
        first = json.load(f)
    for key in ("phases", "stages", "rows", "exchange"):
        assert first[key] == VOCAB[key], key
    assert first["host_spans"] == list(scopes.HOST_SPANS)
    assert first["kernels"] == list(scopes.KERNELS)
    # what each per-layer metric reads by scope is a name of the vocabulary,
    # and a metric of the manifest (BENCHMARK.json) with a reader of its own
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    groups = {g.name: g.names for g in phase_reduce.groups_of(VOCAB)}
    every = set().union(*groups.values())
    for metric, what in phase_reduce.metric_reads().items():
        (kind, name), = what.items()
        assert name in {"stage": scopes.STAGES,
                        "loop": scopes.STAGES + scopes.PROBE_PARTS,
                        "phase": scopes.PHASES + (phase_reduce.UNPHASED,),
                        "rows": ("wrapper", "kernel"), "scope": every,
                        "kernel": groups["kernel"],
                        "span": (scopes.TRAIN_STEP,)}[kind], metric
        assert metric in listed
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", metric + ".py"))


def test_no_scope_or_span_is_written_outside_the_module():
    hits = []
    for path in glob.glob(os.path.join(ROOT, "deeprec_tpu", "**", "*.py"),
                          recursive=True):
        if path.endswith(os.path.join("utils", "scopes.py")):
            continue
        with open(path) as f:
            for i, line in enumerate(f, 1):
                if re.search(r"named_scope\(|TraceAnnotation\(", line):
                    hits.append(f"{path}:{i}")
    assert not hits, hits


# ------------------------------------------------- what the compiled step holds


def test_train_step_names_every_phase_stage_and_row_funnel():
    tr = Trainer(model(), Adagrad(lr=0.1), optax.adam(1e-3),
                 unique_budget=48)
    names = op_names(tr._train_step.lower(
        tr.init(0), batches(1)[0], jnp.float32(0.1)).compile())
    phases, stages, rows = found(names)
    assert phases == {scopes.PHASE_LOOKUP, scopes.PHASE_DENSE_FWD_BWD,
                      scopes.PHASE_SPARSE_APPLY, scopes.PHASE_DENSE_APPLY}
    assert stages == set(scopes.STAGES) | {""}
    assert rows == set(scopes.ROWS) | {""}
    assert_every_instruction_has_a_phase(names)
    text = "\n".join(n for _, n in names)
    # the 26-table vmap wraps the outermost scope under it; the probe,
    # nested in the insert's, is the innermost stage of its operations
    for stage in (scopes.ENGINE_ROUTE, scopes.ENGINE_INSERT,
                  scopes.ENGINE_GATHER):
        assert f"/{scopes.PHASE_LOOKUP}/vmap({stage})/" in text
    probe = [n for _, n in names if scopes.ENGINE_PROBE in n]
    assert any("/while/body/" in n for n in probe)
    assert all(phase_reduce.scope_of(n, VOCAB).stage == scopes.ENGINE_PROBE
               for n in probe)
    # the probe's two loops, each `while` under its own name inside the
    # stage's: the find loop first, the claim loop after it
    loops = [phase_reduce.scope_of(n, VOCAB).probe_part
             for op, n in names if op == "while" and scopes.ENGINE_PROBE in n]
    assert loops == list(scopes.PROBE_PARTS)
    # the optimizer's slot rows take the same two funnels, under the apply
    assert f"/{scopes.PHASE_SPARSE_APPLY}/vmap({scopes.ROWS_SCATTER})/" in text
    assert f"/{scopes.PHASE_SPARSE_APPLY}/vmap({scopes.ROWS_GATHER})/" in text
    # the backward pass: jax wraps the scope's name, or what is under it
    back = [n for _, n in names if "transpose(" in n]
    assert back and all(
        phase_reduce.scope_of(n, VOCAB).phase == scopes.PHASE_DENSE_FWD_BWD
        for n in back)
    assert any(f"transpose({scopes.PHASE_DENSE_FWD_BWD})" in n for n in back)


def test_the_token_stack_names_its_blocks_and_parts():
    """Every operation of the hybrid stack's dense forward and backward
    stands under one of the `block_*` scopes (what is left is the residual
    adds and the embedding's cast), the delta rule and the expert layer's
    parts under theirs, through remat and the backward's wrappers."""
    from deeprec_tpu.models import HybridStackLM

    m = HybridStackLM(
        vocab=48, seq_len=32, capacity=128, pair_budget=256, hidden=32,
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8,
        attn_heads=4, attn_kv_heads=2, head_dim=16, num_experts=16,
        experts_per_token=4, expert_width=16, shared_expert_width=16,
        held_experts=(4, 4), chunk=8, segment=16, flash_block=16,
        moe_block=8, loss_block=16)
    tr = Trainer(m, Adagrad(lr=0.05), optax.adam(1e-3), unique_budget=40)
    state = tr.init(0)
    tok = jnp.arange(2 * 33, dtype=jnp.int32).reshape(2, 33) % 48
    batch = {"tok": tok[:, :-1], "label": tok[:, 1:]}
    names = op_names(tr._train_step.lower(state, batch, None).compile())
    assert_every_instruction_has_a_phase(names)
    got = [phase_reduce.scope_of(n, VOCAB) for _, n in names]
    # no dense layer, no Mamba-2 mixer, no short convolution
    blocks = set(scopes.BLOCKS) - {scopes.BLOCK_MLP, scopes.BLOCK_MAMBA,
                                   scopes.BLOCK_SHORTCONV}
    assert {s.block for s in got} - {""} == blocks
    assert {s.block_part for s in got} - {""} == set(scopes.BLOCK_PARTS) - {
        scopes.MOE_SHARED, scopes.SSD_SCAN, scopes.MAMBA_CONV,
        scopes.MOE_LATENT, scopes.SHORTCONV_GATE}
    # a part stands inside its block
    inside = {"gdn_rule": "block_gdn", "moe_dispatch": "block_moe",
              "moe_experts": "block_moe"}
    assert all(s.block == inside[s.block_part] for s in got if s.block_part)
    dense = [(op, s) for (op, _), s in zip(names, got)
             if s.phase == scopes.PHASE_DENSE_FWD_BWD and op not in FREE]
    bare = [op for op, s in dense if not s.block]
    assert len(bare) < 0.1 * len(dense), (len(bare), len(dense))
    # the backward of every block is named too
    for block in blocks:
        assert any(s.block == block and "transpose(" in n
                   for (_, n), s in zip(names, got)), block


def test_the_window_stack_names_its_attention_parts():
    """The window-and-global stack: every layer's flash call stands under
    `attn_window` or `attn_global` inside `block_attn`, forward and
    backward; the router, which runs BEFORE attention, stands under
    `block_moe` and `moe_dispatch` all the same; no delta net is named."""
    from deeprec_tpu.models import WindowStackLM

    m = WindowStackLM(
        vocab=48, seq_len=32, capacity=128, pair_budget=256, hidden=32,
        layers=4, sliding_window_layout=(0, 1, 1, 1),
        rope_layout=(0, 1, 1, 1), sliding_window=8, attn_heads=4,
        attn_kv_heads=2, head_dim=16, rope_theta=1.5e6, num_experts=16, experts_per_token=4, expert_width=16,
        held_experts=(4, 4), flash_block=16, moe_block=8, loss_block=16)
    tr = Trainer(m, Adagrad(lr=0.05), optax.adam(1e-3), unique_budget=40)
    tok = jnp.arange(2 * 33, dtype=jnp.int32).reshape(2, 33) % 48
    batch = {"tok": tok[:, :-1], "label": tok[:, 1:]}
    names = op_names(tr._train_step.lower(tr.init(0), batch, None).compile())
    assert_every_instruction_has_a_phase(names)
    got = [phase_reduce.scope_of(n, VOCAB) for _, n in names]
    assert {s.block for s in got} - {""} == set(scopes.BLOCKS) - {
        scopes.BLOCK_GDN, scopes.BLOCK_MLP, scopes.BLOCK_MAMBA,
        scopes.BLOCK_SHORTCONV}
    assert {s.block_part for s in got} - {""} == {scopes.MOE_DISPATCH,
                                                  scopes.MOE_EXPERTS}
    parts = (scopes.ATTN_WINDOW, scopes.ATTN_GLOBAL)
    assert {s.attn_part for s in got} - {""} == set(parts)
    assert all(s.block == scopes.BLOCK_ATTN for s in got if s.attn_part)
    assert all(s.block == scopes.BLOCK_MOE for s in got if s.block_part)
    for part in parts:
        assert any(s.attn_part == part and "transpose(" in n
                   for (_, n), s in zip(names, got)), part
    # the router's top-k is the expert block's, though attention follows it
    routed = [s for (op, n), s in zip(names, got) if "top_k" in n]
    assert routed and all(s.block_part == scopes.MOE_DISPATCH
                          for s in routed)
    dense = [(op, s) for (op, _), s in zip(names, got)
             if s.phase == scopes.PHASE_DENSE_FWD_BWD and op not in FREE]
    # what is left is the two norms of a layer (one feeds the router AND the
    # mixer), the residual adds and the embedding's cast, as in the hybrid
    # stack; at this size they are a larger share of fewer operations
    bare = [op for op, s in dense if not s.block]
    assert len(bare) < 0.15 * len(dense), (len(bare), len(dense))


def test_the_latent_stack_names_its_parts_and_its_rule():
    """The latent stack: every layer's flash call stands under
    `attn_latent` inside `block_attn`, the leading layer's feed-forward
    under `block_mlp`, the shared experts under `moe_shared` inside
    `block_moe`, forward and backward; the rule that moves the selection
    bias under `router_bias_update` inside `phase_dense_apply`, and nothing
    else there is named by it; and the names are the ones the benchmark's
    phase file holds."""
    from deeprec_tpu.models import LatentStackLM

    m = LatentStackLM(
        vocab=48, seq_len=32, capacity=128, pair_budget=256, hidden=32,
        layers=3, dense_layers=1, dense_width=48, attn_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        kv_lora_rank=16, rope_theta=5e4, num_experts=16, experts_per_token=4,
        expert_width=16, shared_expert_width=32, routed_scaling_factor=2.446,
        bias_update_rate=1e-3, held_experts=(4, 2), flash_block=16,
        moe_block=8, loss_block=16)
    tr = Trainer(m, Adagrad(lr=0.05), optax.adam(1e-3), unique_budget=40)
    tok = jnp.arange(2 * 33, dtype=jnp.int32).reshape(2, 33) % 48
    batch = {"tok": tok[:, :-1], "label": tok[:, 1:]}
    names = op_names(tr._train_step.lower(tr.init(0), batch, None).compile())
    assert_every_instruction_has_a_phase(names)
    got = [phase_reduce.scope_of(n, VOCAB) for _, n in names]
    assert {s.block for s in got} - {""} == set(scopes.BLOCKS) - {
        scopes.BLOCK_GDN, scopes.BLOCK_MAMBA, scopes.BLOCK_SHORTCONV}
    assert {s.block_part for s in got} - {""} == {
        scopes.MOE_DISPATCH, scopes.MOE_EXPERTS, scopes.MOE_SHARED}
    assert {s.attn_part for s in got} - {""} == {scopes.ATTN_LATENT}
    assert all(s.block == scopes.BLOCK_ATTN for s in got if s.attn_part)
    assert all(s.block == scopes.BLOCK_MOE for s in got if s.block_part)
    for field, name in (("attn_part", scopes.ATTN_LATENT),
                        ("block", scopes.BLOCK_MLP),
                        ("block_part", scopes.MOE_SHARED)):
        assert any(getattr(s, field) == name and "transpose(" in n
                   for (_, n), s in zip(names, got)), name
    ruled = [s for s in got if s.dense_rule]
    assert ruled and all(s.phase == scopes.PHASE_DENSE_APPLY
                         and s.dense_rule == scopes.ROUTER_BIAS_UPDATE
                         for s in ruled)
    assert any(s.phase == scopes.PHASE_DENSE_APPLY and not s.dense_rule
               for s in got)          # Adam's own update is not the rule's
    with open(os.path.join(ROOT, "benchmark", "phases",
                           "57-latent-stack.json")) as f:
        ours = json.load(f)["groups"]
    assert {g: spec["names"] for g, spec in ours.items()} == {
        "block": [scopes.BLOCK_MLP], "block_part": [scopes.MOE_SHARED],
        "attn_part": [scopes.ATTN_LATENT],
        "dense_rule": list(scopes.DENSE_RULES)}
    dense = [(op, s) for (op, _), s in zip(names, got)
             if s.phase == scopes.PHASE_DENSE_FWD_BWD and op not in FREE]
    bare = [op for op, s in dense if not s.block]
    assert len(bare) < 0.15 * len(dense), (len(bare), len(dense))


def test_the_conv_stack_names_its_mixers_and_gates():
    """The short-convolution stack: every conv mixer stands under
    `block_shortconv` and its gates and taps under `shortconv_gate` inside
    it, forward and backward; attention under `block_attn` with no
    attention part of its own, the leading layer's feed-forward under
    `block_mlp`; and the names are the ones the benchmark's phase file
    holds."""
    from deeprec_tpu.models import ConvStackLM

    m = ConvStackLM(
        vocab=48, seq_len=32, capacity=128, pair_budget=128, hidden=32,
        layers=3, layer_types=("conv", "full_attention", "conv"),
        dense_layers=1, dense_width=48, conv_kernel=3, attn_heads=4,
        attn_kv_heads=2, head_dim=8, rope_theta=1e6, num_experts=8,
        experts_per_token=4, expert_width=16, routed_scaling_factor=1.0,
        renorm_eps=1e-6, bias_update_rate=1e-3, held_experts=(2, 2),
        flash_block=16, moe_block=8, loss_block=16)
    tr = Trainer(m, Adagrad(lr=0.05), optax.adam(1e-3), unique_budget=40)
    tok = jnp.arange(2 * 33, dtype=jnp.int32).reshape(2, 33) % 48
    batch = {"tok": tok[:, :-1], "label": tok[:, 1:]}
    names = op_names(tr._train_step.lower(tr.init(0), batch, None).compile())
    assert_every_instruction_has_a_phase(names)
    got = [phase_reduce.scope_of(n, VOCAB) for _, n in names]
    assert {s.block for s in got} - {""} == set(scopes.BLOCKS) - {
        scopes.BLOCK_GDN, scopes.BLOCK_MAMBA}
    assert {s.block_part for s in got} - {""} == {
        scopes.MOE_DISPATCH, scopes.MOE_EXPERTS, scopes.SHORTCONV_GATE}
    assert {s.attn_part for s in got} - {""} == set()
    assert all(s.block == scopes.BLOCK_SHORTCONV for s in got
               if s.block_part == scopes.SHORTCONV_GATE)
    for field, name in (("block", scopes.BLOCK_SHORTCONV),
                        ("block_part", scopes.SHORTCONV_GATE)):
        assert any(getattr(s, field) == name and "transpose(" in n
                   for (_, n), s in zip(names, got)), name
    with open(os.path.join(ROOT, "benchmark", "phases",
                           "59-conv-stack.json")) as f:
        ours = json.load(f)["groups"]
    assert {g: spec["names"] for g, spec in ours.items()} == {
        "block": [scopes.BLOCK_SHORTCONV],
        "block_part": [scopes.SHORTCONV_GATE]}
    dense = [(op, s) for (op, _), s in zip(names, got)
             if s.phase == scopes.PHASE_DENSE_FWD_BWD and op not in FREE]
    # what is left is the two norms of a layer and the residual adds, as in
    # the latent stack; a short convolution is fewer operations than a
    # latent attention, so they are a larger share (15.2 % at this size)
    bare = [op for op, s in dense if not s.block]
    assert len(bare) < 0.2 * len(dense), (len(bare), len(dense))


def test_a_read_only_lookup_shows_no_insert():
    """Eval and serving resolve with `train=False`: nothing is created or
    stamped, and a trace of it must not show time under `engine_insert`."""
    tr = Trainer(model(), Adagrad(lr=0.1), optax.adam(1e-3),
                 unique_budget=48)
    names = op_names(tr._eval_step.lower(
        tr.init(0), batches(1)[0]).compile())
    _, stages, rows = found(names)
    assert scopes.ENGINE_INSERT not in stages
    assert {scopes.ENGINE_PROBE, scopes.ENGINE_GATHER} <= stages
    assert scopes.ROWS_SCATTER not in rows


def test_sentinel_and_pipelined_steps_name_their_phases():
    from deeprec_tpu.guard import SentinelConfig
    from deeprec_tpu.training import stack_batches

    tr = Trainer(model(), Adagrad(lr=0.1), optax.adam(1e-3),
                 unique_budget=48, pipeline_mode="lookahead",
                 sentinel=SentinelConfig(row_norm_max=1e3))
    names = op_names(tr._train_steps.lower(
        tr.init(0), stack_batches(batches(3)), jnp.float32(0.1),
        tr._guard_or_init(None)).compile())
    phases, stages, _ = found(names)
    assert {scopes.PHASE_LOOKUP, scopes.PHASE_ROUTE_NEXT,
            scopes.PHASE_FINISH_EXCHANGE, scopes.PHASE_SENTINEL,
            scopes.PHASE_DENSE_FWD_BWD, scopes.PHASE_SPARSE_APPLY,
            scopes.PHASE_DENSE_APPLY} <= phases
    assert set(scopes.STAGES) <= stages
    # the lookahead's route and probe stand under the phase that hoists them
    hoisted = {phase_reduce.scope_of(n, VOCAB).stage for _, n in names
               if phase_reduce.scope_of(n, VOCAB).phase
               == scopes.PHASE_ROUTE_NEXT}
    assert {scopes.ENGINE_ROUTE, scopes.ENGINE_PROBE,
            scopes.ENGINE_INSERT} <= hoisted
    assert scopes.ENGINE_GATHER not in hoisted


@pytest.mark.parametrize("program", ["train_step", "train_steps"])
@pytest.mark.parametrize("comm, chunks", [("allgather", 1), ("a2a", 3)])
def test_sharded_train_step_names_the_same_vocabulary(mesh, comm, chunks,
                                                      program):
    """The mesh runs the base trainer's step bodies, so the one step and
    the pipelined K-step scan write the base's phases, with the exchange
    phase where the base writes the lookup."""
    from deeprec_tpu.parallel import ShardedTrainer, shard_batch
    from deeprec_tpu.training import stack_batches

    kw = dict(pipeline_mode="chunked", pipeline_chunks=chunks) \
        if chunks > 1 else \
        dict(pipeline_mode="lookahead") if program == "train_steps" else {}
    tr = ShardedTrainer(model(), Adagrad(lr=0.1), optax.adam(1e-3),
                        mesh=mesh, comm=comm, unique_budget=48, **kw)
    want = {scopes.PHASE_LOOKUP_EXCHANGE, scopes.PHASE_DENSE_FWD_BWD,
            scopes.PHASE_SPARSE_APPLY, scopes.PHASE_DENSE_APPLY}
    if program == "train_step":
        lowered = tr._train_step.lower(
            tr.init(0), shard_batch(mesh, batches(1)[0]), jnp.float32(0.1))
    else:
        want |= {scopes.PHASE_ROUTE_NEXT, scopes.PHASE_FINISH_EXCHANGE}
        lowered = tr._train_steps.lower(
            tr.init(0),
            shard_batch(mesh, stack_batches(batches(3)), stacked=True),
            jnp.float32(0.1))
    names = op_names(lowered.compile())
    phases, stages, rows = found(names)
    assert set(scopes.STAGES) <= stages and set(scopes.ROWS) <= rows
    if program == "train_step":
        assert phases == want, phases
        assert_every_instruction_has_a_phase(names)
    else:  # the scan's own slicing and stacking stand under no phase
        assert phases - {""} == want, phases
    text = "\n".join(n for _, n in names)
    for i in range(chunks if chunks > 1 else 0):
        assert scopes.exchange_chunk(i) in text


# ------------------------------------------------------- the host's spans


def host_events(trace_dir):
    """{name: [stats dict of each event]} of the program's spans on the
    host planes of the trace under `trace_dir`."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    wanted = set(scopes.HOST_SPANS + scopes.SETUP_SPANS) | {scopes.TRAIN_STEP}
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in wanted:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, dict(e.stats)))
    return {k: [s for _, s in sorted(v, key=lambda x: x[0])]
            for k, v in out.items()}


def traced(tmp_path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return host_events(str(tmp_path))


def test_a_three_step_trace_holds_three_numbered_step_spans(tmp_path):
    tr = Trainer(model(), Adagrad(lr=0.1), optax.adam(1e-3),
                 unique_budget=48)
    state = tr.init(0)
    host = [{k: jax.device_get(v) for k, v in b.items()}
            for b in batches(3)]
    state, mets = tr.train_step(state, tr.stage_batch(host[0]))  # compiles
    jax.block_until_ready(mets["loss"])
    tr._dispatches = 0

    def body():
        nonlocal state
        for b in host:
            state, mets = tr.train_step(state, tr.stage_batch(b))
        jax.block_until_ready(mets["loss"])

    events = traced(tmp_path, body)
    assert [s["step_num"] for s in events[scopes.TRAIN_STEP]] == [0, 1, 2]
    assert len(events[scopes.STAGE_BATCH]) == 3
    assert int(state.step) == 4  # the spans count dispatches, not the state


def test_step_spans_count_dispatches_of_every_entry_point(tmp_path):
    tr = Trainer(model(), Adagrad(lr=0.1), optax.adam(1e-3),
                 unique_budget=48)
    state = tr.init(0)
    bs = batches(4, batch_size=32)

    def body():
        nonlocal state
        state, _ = tr.train_step(state, bs[0])
        state, _ = tr.train_steps(state, bs[1:3])
        big = {k: jnp.concatenate([a[k], b[k]]) for a, b in [bs[:2]]
               for k in a}
        state, mets = tr.train_step_accum(state, big, 2)
        jax.block_until_ready(mets["loss"])

    events = traced(tmp_path, body)
    assert [s["step_num"] for s in events[scopes.TRAIN_STEP]] == [0, 1, 2]
    assert int(state.step) == 4  # 1 + 2 + 1 steps in three dispatches


def test_maintenance_and_checkpoint_calls_leave_their_spans(tmp_path):
    from deeprec_tpu.training.checkpoint import CheckpointManager

    tr = Trainer(model(), Adagrad(lr=0.1), optax.adam(1e-3),
                 unique_budget=48)
    state, _ = tr.train_step(tr.init(0), batches(1)[0])
    ckpt = CheckpointManager(str(tmp_path / "ckpt"), tr)

    def body():
        nonlocal state
        state, _ = tr.maintain(state)     # update_budgets inside it
        state = tr.evict_tables(state)
        state, _ = ckpt.save(state)
        state, _ = ckpt.save_incremental_async(state)
        ckpt.wait()
        state = ckpt.restore()

    events = traced(tmp_path / "trace", body)
    count = {name: len(events.get(name, ())) for name in scopes.HOST_SPANS}
    assert count == {scopes.STAGE_BATCH: 0, scopes.UPDATE_BUDGETS: 1,
                     scopes.MAINTAIN: 1, scopes.EVICT_TABLES: 1,
                     scopes.CKPT_SAVE: 2, scopes.CKPT_RESTORE: 1}


def test_building_a_trainer_and_its_state_leaves_the_setup_spans(tmp_path):
    """Set-up's three spans: one `Trainer(...)`, one `.init()`, and a
    `deeprec.kernel_trace` for every Pallas call jax binds while it traces
    (off a TPU the trainer's row reads are XLA's and bind none; the row
    kernel asked for by hand, interpreted, binds one)."""
    from deeprec_tpu.ops import fused_lookup

    values = jnp.ones((32, 128), jnp.float32)

    def body():
        tr = Trainer(model(), Adagrad(lr=0.1), optax.adam(1e-3),
                     unique_budget=48)
        jax.block_until_ready(tr.init(0))
        fused_lookup.gather_rows(
            values, jnp.asarray([1, 5, 2, 9, 30, 7, 0, 3], jnp.int32),
            block=8, interpret=True).block_until_ready()

    events = traced(tmp_path, body)
    assert {name: len(events.get(name, ())) for name in scopes.SETUP_SPANS} \
        == {scopes.TRAINER_BUILD: 1, scopes.INIT_STATE: 1,
            scopes.KERNEL_TRACE: 1}


def test_host_spans_allocate_nothing_when_no_profiler_runs():
    """With no profiler running a span is a flag test: the objects entered
    and left are freed at once, so a thousand spans leave nothing behind
    (the same pin as obs/trace.py's disabled path)."""
    import tracemalloc

    with scopes.host_span(scopes.MAINTAIN):   # touch every lazy path once
        pass
    with scopes.step_span(0):
        pass
    N = 2000
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(N):
            with scopes.host_span(scopes.STAGE_BATCH):
                pass
            with scopes.step_span(i):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = [st for st in after.compare_to(before, "filename")
             if st.traceback[0].filename == scopes.__file__]
    assert sum(st.count_diff for st in grown) < N / 100, grown
    assert sum(st.size_diff for st in grown) < 4096, grown


def test_the_entry_points_cache_setting_keeps_the_scopes(monkeypatch, tmp_path):
    """Every entry point calls `enable_compile_cache()`. The setting it used
    to make for stable Pallas cache keys
    (`jax_include_full_tracebacks_in_locations=False`) cut every `op_name`
    down to its primitive, and a trace of the real program lost its scopes;
    the one-frame limit it makes now keeps them."""
    from deeprec_tpu.utils import backend

    names = ("jax_traceback_in_locations_limit",
             "jax_include_full_tracebacks_in_locations")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        backend.enable_compile_cache()

        def step(x):
            with scopes.scope(scopes.PHASE_LOOKUP):
                return jax.vmap(scopes.scoped(scopes.ENGINE_PROBE)(jnp.sin))(x)

        text = jax.jit(step).lower(jnp.ones((2, 4))).compile().as_text()
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
    assert f"{scopes.PHASE_LOOKUP}/vmap({scopes.ENGINE_PROBE})/sin" in text


def test_a_kernel_serialises_the_same_from_two_callers(monkeypatch, tmp_path):
    """What the setting is for (PR 21): a Pallas kernel is serialised into
    its program with the locations of its trace, and so into the compile
    cache's key. With one frame a location the kernel's own line is all
    that is left, and the body is the same bytes whoever calls it; with
    jax's default ten frames the callers are in it and it is not. (Shown on
    `fused_gather_combine`: the two row kernels' calls are jitted since
    PR 32, so one trace serves every caller whatever the setting.)"""
    from deeprec_tpu.ops import fused_lookup
    from deeprec_tpu.utils import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    name = "jax_traceback_in_locations_limit"
    before = getattr(jax.config, name)
    args = (jax.ShapeDtypeStruct((4096, 128), jnp.float32),
            jax.ShapeDtypeStruct((64, 8), jnp.int32),
            jax.ShapeDtypeStruct((64, 8), jnp.float32))

    def bodies():  # functions of their own: jax keeps a lowering by them
        def one(values, ix, w):
            return fused_lookup.fused_gather_combine(values, ix, w)

        def other(values, ix, w):
            def deeper(values, ix, w):
                return fused_lookup.fused_gather_combine(values, ix, w) + 0

            return deeper(values, ix, w)

        found = []
        for fn in (one, other):
            text = jax.jit(fn).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text()
            body, = re.findall(r'\\22body\\22: \\22([^\\]*)\\22', text)
            found.append(body)
        return found

    try:
        jax.config.update(name, 10)
        one, other = bodies()
        assert one != other
        backend.enable_compile_cache()
        assert getattr(jax.config, name) == 1
        one, other = bodies()
        assert one == other
    finally:
        jax.config.update(name, before)
