"""The one vocabulary of trace names: scopes inside the compiled step and
spans on the host, written where the work happens.

Device scopes are `jax.named_scope`s. A scope is metadata of the compiled
program (the `op_name` of every HLO instruction traced under it, which a
device trace carries beside the instruction's time) and costs nothing at
run time. Three kinds nest, as written:

  * `phase_*`  — what the trainers' step is made of; every instruction of
    a train step stands under exactly one outermost phase;
  * `engine_*` — the stages of one table lookup inside the embedding
    engine (route, probe, insert, gather);
  * `rows_*`   — tight round a row read or a row write, so that under it
    everything that is not the row kernel itself is wrapper.

jax wraps a scope's name in the transforms it is traced under
(`vmap(engine_probe)`, `transpose(jvp(phase_dense_fwd_bwd))`); a reader
unwraps them (benchmark/phase_reduce.py; docs/profiling.md). The Pallas
kernels' `name=` are part of the vocabulary for the same reason: they land
in `op_name` the same way.

Host spans are `jax.profiler.TraceAnnotation`s: they land in the same
`.xplane.pb` as the device lines, on the profiler's clock, and with no
profiler running entering one is a flag test. `deeprec.train_step` is a
`StepTraceAnnotation`, whose `step_num` the profiler's step views key on.

Names hold no `/`, `(`, `)` or space. Nothing here is switched by an
environment variable or an option. The serving path's wall-clock JSONL
spans (obs/trace.py) are another mechanism for another job (one request
across processes) and are not routed through here.
"""
from __future__ import annotations

import functools

import jax

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

ROWS_GATHER = "rows_gather"
ROWS_SCATTER = "rows_scatter"
ROWS = (ROWS_GATHER, ROWS_SCATTER)

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
    """Decorator form of `host_span`: the whole call is one host span."""
    return _around(host_span, name)


def step_span(n: int):
    """The host span of one train dispatch; `n` is a host count of
    dispatches, never a read of a device value."""
    return jax.profiler.StepTraceAnnotation(TRAIN_STEP, step_num=n)


def vocabulary() -> dict:
    """The names as data; benchmark/phases.json holds the same."""
    return {
        "phases": list(PHASES), "stages": list(STAGES), "rows": list(ROWS),
        "exchange": list(EXCHANGE), "kernels": list(KERNELS),
        "step_span": TRAIN_STEP, "host_spans": list(HOST_SPANS),
    }
