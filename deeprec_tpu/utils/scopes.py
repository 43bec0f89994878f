"""The one vocabulary of trace names: scopes inside the compiled step and
spans on the host, written where the work happens.

Device scopes are `jax.named_scope`s. A scope is metadata of the compiled
program (the `op_name` of every HLO instruction traced under it, which a
device trace carries beside the instruction's time) and costs nothing at
run time. Three kinds nest in every step, as written:

  * `phase_*`  — what the trainers' step is made of; every instruction of
    a train step stands under exactly one outermost phase;
  * `engine_*` — the stages of one table lookup inside the embedding
    engine (route, probe, insert, gather);
  * `rows_*`   — tight round a row read or a row write, so that under it
    everything that is not the row kernel itself is wrapper.

Inside `engine_probe` the probe's two loops stand under `probe_find` and
`probe_claim` (a group of their own, so that the stage's time and passes
hold both loops'). A pass of `probe_find` reads every pending id one
aligned window of 128 consecutive keys (a row read under `rows_gather`, so
a rows reader books it too), two passes at the default `max_probes`.

A token model's stack (models/hybrid_stack.py) names two more inside
`phase_dense_fwd_bwd`: `block_*`, the parts of a layer (the two mixers, the
expert block, the head with its loss), and inside a block the part a
roofline is read for (`gdn_rule`, tight round the delta rule as `rows_*`
stands round a row kernel; `moe_dispatch`, `moe_experts`). They are two
groups so that a block's time holds its parts' (a reader picks the
innermost name of EACH group). A stack that mixes window and global
attention layers (models/window_stack.py) names, tight round the flash
call inside `block_attn`, which of the two a layer is (`attn_window`,
`attn_global`: a third group); a latent stack (models/latent_stack.py)
names its flash call `attn_latent` there, its shared experts `moe_shared`
inside `block_moe`, a leading layer's dense feed-forward `block_mlp`, and,
inside `phase_dense_apply`, the rule that moves its routers' selection bias
`router_bias_update` (a group of its own). A Mamba stack
(models/mamba_stack.py) names its Mamba-2 mixers `block_mamba`, and inside
them the scan `ssd_scan` and the convolution `mamba_conv`; inside
`block_moe` the projections into and out of the routed experts' latent
space `moe_latent` (all three of the group `block_part`). A
short-convolution stack (models/conv_stack.py) names its gated
short-convolution mixers `block_shortconv` (the group `block`), and inside
them the two gates and the taps between them `shortconv_gate` (the group
`block_part`).

jax wraps a scope's name in the transforms it is traced under
(`vmap(engine_probe)`, `transpose(jvp(phase_dense_fwd_bwd))`); a reader
unwraps them (benchmark/phase_reduce.py; docs/profiling.md). The Pallas
kernels' `name=` are part of the vocabulary for the same reason: they land
in `op_name` the same way.

Host spans are `jax.profiler.TraceAnnotation`s: they land in the same
`.xplane.pb` as the device lines, on the profiler's clock, and with no
profiler running entering one is a flag test. `deeprec.train_step` is a
`StepTraceAnnotation`, whose `step_num` the profiler's step views key on.

Three spans are set-up's (`SETUP_SPANS`), and besides being annotations they
tell the one recorder of set-up (obs/compile_log.py) what they took:
`deeprec.trainer_build` (`Trainer.__init__`), `deeprec.init_state`
(`Trainer.init`: tables and dense weights made; under a caller's `jit` it is
the trace of them) and `deeprec.kernel_trace`, the bind of a Pallas call,
which is its body's trace to a jaxpr. Every `pl.pallas_call` of the package
stands under `kernel_trace(<the kernel's name>)`: it runs when jax traces
the call and never when a compiled program runs.

Names hold no `/`, `(`, `)` or space. Nothing here is switched by an
environment variable or an option. The serving path's wall-clock JSONL
spans (obs/trace.py) are another mechanism for another job (one request
across processes) and are not routed through here.
"""
from __future__ import annotations

import functools
import time

import jax

from deeprec_tpu.obs import compile_log

# ------------------------------------------------------------ device scopes

PHASE_LOOKUP = "phase_lookup"
PHASE_DENSE_FWD_BWD = "phase_dense_fwd_bwd"
PHASE_SPARSE_APPLY = "phase_sparse_apply"
PHASE_DENSE_APPLY = "phase_dense_apply"
PHASE_SENTINEL = "phase_sentinel"
PHASE_ROUTE_NEXT = "phase_route_next"
PHASE_FINISH_EXCHANGE = "phase_finish_exchange"
PHASE_LOOKUP_EXCHANGE = "phase_lookup_exchange"
PHASES = (
    PHASE_LOOKUP, PHASE_DENSE_FWD_BWD, PHASE_SPARSE_APPLY, PHASE_DENSE_APPLY,
    PHASE_SENTINEL, PHASE_ROUTE_NEXT, PHASE_FINISH_EXCHANGE,
    PHASE_LOOKUP_EXCHANGE,
)

ENGINE_ROUTE = "engine_route"
ENGINE_PROBE = "engine_probe"
ENGINE_INSERT = "engine_insert"
ENGINE_GATHER = "engine_gather"
STAGES = (ENGINE_ROUTE, ENGINE_PROBE, ENGINE_INSERT, ENGINE_GATHER)

# The two loops of `engine_probe` (EmbeddingTable._probe): the read-only find
# (a lane-wide window of an id's chain a pass, to the id's key or its chain's
# first empty slot), and the race for the empty slots, which runs no pass
# when no row is to be created.
PROBE_FIND = "probe_find"
PROBE_CLAIM = "probe_claim"
PROBE_PARTS = (PROBE_FIND, PROBE_CLAIM)

ROWS_GATHER = "rows_gather"
ROWS_SCATTER = "rows_scatter"
ROWS = (ROWS_GATHER, ROWS_SCATTER)

# The token model's layer parts, and what a roofline is read for inside them.
BLOCK_GDN = "block_gdn"
BLOCK_ATTN = "block_attn"
BLOCK_MOE = "block_moe"
BLOCK_HEAD_LOSS = "block_head_loss"
BLOCK_MLP = "block_mlp"          # a leading layer's dense feed-forward
BLOCK_MAMBA = "block_mamba"      # a Mamba-2 mixer (models/mamba_stack.py)
BLOCK_SHORTCONV = "block_shortconv"  # a gated short convolution, its two
#                                      projections included
#                                      (models/conv_stack.py)
BLOCKS = (BLOCK_GDN, BLOCK_ATTN, BLOCK_MOE, BLOCK_HEAD_LOSS, BLOCK_MLP,
          BLOCK_MAMBA, BLOCK_SHORTCONV)
GDN_RULE = "gdn_rule"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_SHARED = "moe_shared"        # the shared experts, computed in full
SSD_SCAN = "ssd_scan"            # the Mamba-2 scan (ops/ssd.py), tight
MAMBA_CONV = "mamba_conv"        # the Mamba-2 mixer's causal convolution
MOE_LATENT = "moe_latent"        # the projections into and out of the
#                                  routed experts' latent space
SHORTCONV_GATE = "shortconv_gate"  # the two gates and the causal taps
#                                    between a short convolution's products
BLOCK_PARTS = (GDN_RULE, MOE_DISPATCH, MOE_EXPERTS, MOE_SHARED, SSD_SCAN,
               MAMBA_CONV, MOE_LATENT, SHORTCONV_GATE)
# Tight round the flash call: which of a window stack's two kinds a layer
# is, or a latent stack's call (keys wider than values).
ATTN_WINDOW = "attn_window"
ATTN_GLOBAL = "attn_global"
ATTN_LATENT = "attn_latent"
ATTN_PARTS = (ATTN_WINDOW, ATTN_GLOBAL, ATTN_LATENT)
# Inside `phase_dense_apply`: a model's own rule for the leaves of its dense
# tree that no gradient moves (the router's selection bias).
ROUTER_BIAS_UPDATE = "router_bias_update"
DENSE_RULES = (ROUTER_BIAS_UPDATE,)

# What a layer's remat keeps instead of making again (`checkpoint_name`s; no
# trace shows them): the experts each token chose and the rows they were
# dispatched to (a few int32 arrays a layer), the delta rule's states at its
# segments' starts (8 MB a layer at 32 heads of 128 x 128 and four segments),
# and the flash forward's output and log-sum-exp, so that the backward never
# runs the attention forward again (`[B, H, T, Dv]` in the operands' dtype +
# `[B, H, T]` f32 a layer: 117.4 + 1.8 MB at 28 heads of 128 and T 16,384,
# 33.6 + 0.5 MB at 16 of 128 and T 8,192, 67.1 + 0.5 MB at 16 of 256; q, k
# and v are NOT kept: their projections are made again, docs/attention.md).
KEPT_MOE_ROUTE = "kept_moe_route"
KEPT_GDN_STATES = "kept_gdn_states"
KEPT_ATTN_OUT = "kept_attn_out"
REMAT_KEPT = (KEPT_MOE_ROUTE, KEPT_GDN_STATES, KEPT_ATTN_OUT)

# The sharded exchange's scopes, nested under the phase that issues them.
# They keep the names they had (`phase_` + what parallel/sharded.py called
# them); no metric reads them until a multi-chip cell exists.
HIER_INTRA_IDS = "phase_hier_intra_ids"
HIER_INTER_IDS = "phase_hier_inter_ids"
_EXCHANGE_CHUNK = "phase_exchange_chunk"
_HIER_INTRA_CHUNK = "phase_hier_intra_chunk"
_HIER_INTER_CHUNK = "phase_hier_inter_chunk"
EXCHANGE = (HIER_INTRA_IDS, HIER_INTER_IDS, _EXCHANGE_CHUNK,
            _HIER_INTRA_CHUNK, _HIER_INTER_CHUNK)


def exchange_chunk(i: int) -> str:
    """Column chunk `i` of a flat exchange (a2a / allgather)."""
    return f"{_EXCHANGE_CHUNK}{i}"


def hier_intra_chunk(i: int) -> str:
    return f"{_HIER_INTRA_CHUNK}{i}"


def hier_inter_chunk(i: int) -> str:
    return f"{_HIER_INTER_CHUNK}{i}"


# The `name=` of the Pallas row kernels (ops/fused_lookup.py).
KERNEL_GATHER_ROWS = "gather_rows"
KERNEL_GATHER_ROWS_PAIR = "gather_rows_pair"
KERNEL_APPLY_ROWS_SR = "apply_rows_sr"
KERNEL_APPLY_ROWS_SR_PAIR = "apply_rows_sr_pair"
KERNEL_FUSED_GATHER_COMBINE = "fused_gather_combine"
KERNEL_FUSED_SPARSE_FORWARD = "fused_sparse_forward"
KERNEL_FUSED_SPARSE_BACKWARD = "fused_sparse_backward"
KERNELS = (
    KERNEL_GATHER_ROWS, KERNEL_GATHER_ROWS_PAIR, KERNEL_APPLY_ROWS_SR,
    KERNEL_APPLY_ROWS_SR_PAIR, KERNEL_FUSED_GATHER_COMBINE,
    KERNEL_FUSED_SPARSE_FORWARD, KERNEL_FUSED_SPARSE_BACKWARD,
)
# The `name=` of the dense side's Pallas kernels (ops/flash_attention.py,
# ops/moe.py).
KERNEL_FLASH_FWD = "flash_attention_fwd"
KERNEL_FLASH_BWD_DKDV = "flash_attention_bwd_dkdv"
KERNEL_FLASH_BWD_DQ = "flash_attention_bwd_dq"
KERNEL_GROUPED_MATMUL = "grouped_matmul"
KERNEL_GROUPED_MATMUL_DX = "grouped_matmul_dx"
KERNEL_GROUPED_MATMUL_DW = "grouped_matmul_dw"
DENSE_KERNELS = (
    KERNEL_FLASH_FWD, KERNEL_FLASH_BWD_DKDV, KERNEL_FLASH_BWD_DQ,
    KERNEL_GROUPED_MATMUL, KERNEL_GROUPED_MATMUL_DX, KERNEL_GROUPED_MATMUL_DW,
)

# -------------------------------------------------------------- host spans

TRAIN_STEP = "deeprec.train_step"
STAGE_BATCH = "deeprec.stage_batch"
UPDATE_BUDGETS = "deeprec.update_budgets"
MAINTAIN = "deeprec.maintain"
EVICT_TABLES = "deeprec.evict_tables"
CKPT_SAVE = "deeprec.ckpt_save"
CKPT_RESTORE = "deeprec.ckpt_restore"
HOST_SPANS = (STAGE_BATCH, UPDATE_BUDGETS, MAINTAIN, EVICT_TABLES, CKPT_SAVE,
              CKPT_RESTORE)
# Set-up's spans: each also books its seconds with obs/compile_log.py.
TRAINER_BUILD = "deeprec.trainer_build"
INIT_STATE = "deeprec.init_state"
KERNEL_TRACE = "deeprec.kernel_trace"
SETUP_SPANS = (TRAINER_BUILD, INIT_STATE, KERNEL_TRACE)


def scope(name: str):
    """A device scope: every operation traced inside carries `name` in its
    `op_name`."""
    return jax.named_scope(name)


def host_span(name: str):
    """A host span on the profiler's clock."""
    return jax.profiler.TraceAnnotation(name)


def _around(enter, name: str):
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with enter(name):  # a fresh context a call: threads may share fn
                return fn(*args, **kwargs)
        return call
    return wrap


def scoped(name: str):
    """Decorator form of `scope`: the whole function is one device scope."""
    return _around(scope, name)


def host_spanned(name: str):
    """Decorator form of `host_span`: the whole call is one host span. One
    of `SETUP_SPANS` (`deeprec.<stage>`) also books
    `deeprec_setup_seconds_total{stage}`."""
    if name in SETUP_SPANS:
        stage = name.split(".", 1)[1]
        return _around(
            lambda span: _Booked(span, compile_log.SETUP, stage), name)
    return _around(host_span, name)


class _Booked:
    """A host span that also tells the recorder of set-up what it took:
    where it lies on `time.time()` (the clock jax's own spans nest on) and
    its seconds on `perf_counter`."""

    __slots__ = ("_span", "_stage", "_name", "_wall", "_t0")

    def __init__(self, span: str, stage: str, name: str):
        self._span = host_span(span)
        self._stage, self._name = stage, name

    def __enter__(self):
        self._span.__enter__()
        if self._stage == compile_log.KERNEL_TRACE:
            compile_log.kernel_bind_begins()
        self._wall, self._t0 = time.time(), time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        compile_log.record(self._stage, self._name, self._wall, time.time(),
                           seconds)
        return self._span.__exit__(*exc)


def kernel_trace(kernel: str):
    """Round a `pl.pallas_call(...)(...)`: the bind of the call is the trace
    of the kernel's body, which jax makes again for every call of every
    program on every run, whatever the compile cache holds. `kernel` is the
    call's `name=`. The traces jax makes inside (the call's own `wrapped`,
    the body's jitted `jnp` functions) are booked here and not as traces."""
    return _Booked(KERNEL_TRACE, compile_log.KERNEL_TRACE, kernel)


def step_span(n: int):
    """The host span of one train dispatch; `n` is a host count of
    dispatches, never a read of a device value."""
    return jax.profiler.StepTraceAnnotation(TRAIN_STEP, step_num=n)


def vocabulary() -> dict:
    """The names as data; benchmark/phases.json and benchmark/phases/*.json
    hold the same (benchmark/phase_reduce.py::load_vocabulary merges them)."""
    return {
        "phases": list(PHASES), "stages": list(STAGES), "rows": list(ROWS),
        "exchange": list(EXCHANGE),
        "kernels": list(KERNELS + DENSE_KERNELS),
        "step_span": TRAIN_STEP,
        "host_spans": list(HOST_SPANS + SETUP_SPANS),
        "groups": {
            "block": {"pick": "innermost", "names": list(BLOCKS)},
            "block_part": {"pick": "innermost", "names": list(BLOCK_PARTS)},
            "attn_part": {"pick": "innermost", "names": list(ATTN_PARTS)},
            "dense_rule": {"pick": "innermost", "names": list(DENSE_RULES)},
            "probe_part": {"pick": "innermost", "names": list(PROBE_PARTS)},
        },
    }
