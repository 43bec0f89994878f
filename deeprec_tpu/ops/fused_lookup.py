"""Fused TPU lookup kernels: DMA-pipelined gather, gather+combine, and a
stochastic-rounded scatter-apply.

Why these exist: the reference spends 5.5k LoC of CUDA on fused embedding
lookups (core/ops/fused_embedding_ops.cc:65, core/kernels/group_embedding/
group_embedding_lookup_sparse_forward_base_ops.cu.h) because op-composed
sparse gathers leave bandwidth on the table. The TPU analog is a Pallas
kernel that moves random table rows by DMA, many in flight at once:

  * ``gather_rows``          — values[ix] for [U] unique slots (the hot
    [U, D] gather inside every lookup).
  * ``fused_gather_combine`` — bag-pooling straight out of the table:
    out[b] = sum_l w[b,l] * values[ix[b,l]] without materializing the
    [B, L, D] intermediate (serving/eval path; the train path needs the
    unique-space embeddings for autodiff and uses gather_rows).
  * ``apply_rows_sr``        — scatter updated rows back with stochastic
    rounding when the table is bf16 (plain round-to-nearest silently drops
    small gradient updates once |update| < ulp(value)/2).

Eligibility: the single-row DMA kernels require **f32 tables with
dim == 128** (Mosaic's HBM tiling constraint, ``_dma_ok``). **bf16
tables with dim % 128 == 0** ride the PAIR-granule variants
(``gather_rows_pair`` / ``apply_rows_sr_pair`` / the pair branch of
``fused_gather_combine``): 2-row even-aligned DMAs with the half-select
or read-modify-write done in VMEM, including IN-KERNEL stochastic
rounding — gated behind kernel="pallas" / AUTO_TRUSTS_BF16_PAIR until a
hardware bench crowns them. Everything else falls back to the
identical-semantics XLA path. Off-TPU all calls are XLA, so every caller
is oracle-testable on CPU (the kernels themselves via interpret mode,
where the in-kernel SR branches are also covered).

Batching. Stacked bundles vmap the lookup and the apply over their tables,
and ``gather_rows`` / ``apply_rows_sr`` take their row indices as a
scalar-prefetch operand. jax's batching rule for ``pallas_call`` adds no
grid axis for a batched scalar prefetch: it loops over the tables,
``dynamic_slice``s each whole [C, D] table out of the stack, runs the
kernel on the slice and ``dynamic_update_slice``s the result into a fresh
[T, ...] buffer (jax 0.9.0, ``_batch_with_explicit_loop``) — table-sized
copies round a row read or write. So these two kernels carry a table axis
themselves (grid (tables, row blocks), ``values`` whole in HBM as
[T, C, D], a row's DMA at ``values_ref.at[t, idx]``, the scatter aliased
onto the stacked array) and sit behind a ``custom_vmap`` hook that folds
any vmap — and a second one on top, shards x tables — into that axis by a
reshape (``_fold``). The unbatched call is T = 1. docs/kernels.md has the
SMEM budget and the mixed-batching cases.

Schedule. The two row kernels only copy: a row goes HBM to HBM, from the
table to its row of the result or from its row of the updates to the
table, with no block in VMEM and no vector load or store; a grid step
carries all of a table's rows and keeps _GROUP * _AHEAD row DMAs in
flight (``_row_window``); a skipped slot starts nothing and is waited for
never. docs/kernels.md, "How the row kernels keep DMAs in flight", has the
sweep on the chip that set the two constants and what a row costs.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from deeprec_tpu.utils import backend, scopes

_BLOCK = 8  # rows per grid step of the pair kernels; one f32 sublane tile
_LANES = 128  # Mosaic HBM tiling: DMA row slices must be lane-aligned
# The row kernels' schedule (docs/kernels.md, "How the row kernels keep DMAs
# in flight"): rows start in groups of _GROUP, and _AHEAD groups are in
# flight before the first is waited for. Both come from a sweep on the chip,
# not from a caller: there is no option that sets them.
_GROUP = 16
_AHEAD = 4
# Row indices ride SMEM as a scalar prefetch, all tables of a call in one
# operand; past this many bytes of them a stacked call splits into calls
# over table ranges (_table_ranges). Two limits stand behind the number.
# Mosaic's: a v5e core has 1 MiB of SMEM and what does not fit is refused
# at compile time (26 x 10000 indices pass, 26 x 12000 do not:
# tests/test_aot_kernels.py). The chip's, found the hard way (PERF.md,
# PR 27): with all 26 x 8200 indices of the benchmark's uniform cell in one
# call (853 KB of SMEM; the gather's [26, 8200, 128] result is 109 MB, which
# XLA keeps in VMEM) the cell's first train step never returns, where calls
# of 7 tables (230 KB, 29 MB) run, as do 26 x 2304 rows in one call (240 KB,
# 31 MB). 256 KiB keeps every call inside what has been seen to run.
_SMEM_INDEX_BYTES = 256 * 1024


def _dma_ok(dim: int, dtype) -> bool:
    """Single-row DMA eligibility: f32 tables with dim == 128 —
    Mosaic requires HBM slices aligned to the tiling (measured on v5e:
    misaligned widths are a compile error, not a slowdown — dim 64 fails
    "must be aligned to tiling (128)"; bf16 tiles pack 2 sublanes per
    32-bit word so a dynamic single-row slice fails "index in dimension 0
    is a multiple of 2"). A row WIDER than one lane tile fails too (dim
    256 and 2048, compiled for a described v5e: "Slice shape along
    dimension 1 must be aligned to tiling (8), but is 1"): under the
    (8, 128) HBM tiling one row of a [C, 128] table is one contiguous run
    of a tile, one row of a [C, 2048] table is sixteen strided runs, and
    Mosaic takes no such slice whatever VMEM buffer it lands in. Wide rows
    take the XLA gather and scatter (8 KiB rows are what those are good
    at); a Pallas path for them needs the table STORED as [C, D/128, 128]
    (docs/kernels.md). bf16 tables with dim % 128 == 0 have their own
    PAIR-granule kernels (gather_rows / apply_rows_sr /
    fused_gather_combine route them via _dma_pair_ok); narrower tables
    take the XLA path (a D<128 row
    underfills even one DMA granule — beating XLA there needs a packed
    storage layout, not a better kernel; see docs/perf.md)."""
    return dim == _LANES and jnp.dtype(dtype).itemsize == 4


def _dma_pair_ok(shape, dtype) -> bool:
    """bf16 pair-granule eligibility: rows ride 2-row granules (the bf16
    packing unit), so the table needs dim % 128 == 0 AND an even row
    count — checked here, not assumed, since the ops are public (an odd
    C would let a clamped index DMA one row past the array)."""
    C, dim = shape
    return (
        dim % _LANES == 0
        and C % 2 == 0
        and jnp.dtype(dtype) == jnp.bfloat16
    )


# Which (kernel, shape-class) combos "auto" trusts. The policy is that
# auto only resolves to Pallas where a live-hardware bench crowned it; the
# bf16 pair kernels are implemented + oracle-tested but NOT yet measured on
# hardware, so auto keeps XLA for them until a measurement flips these
# flags. Both flags are consulted by EmbeddingTable.use_pallas /
# .pair_kernels. What stands behind the first (one v5e, PR 32;
# docs/kernels.md, PERF.md 5-6): the kernels alone at [26, 262144, 128]
# read 11.2 ns a gathered row and 12.4 a scattered one where XLA's gather
# and scatter read 11.4 and 74.9, and the benchmark's `.zipf` step 97.0k
# examples/s where the XLA arm reads 86.9k (the XLA arm halts the chip in
# `.uniform`, ROADMAP D0). Before PR 32 the XLA arm was ahead in `.zipf`
# (71.3k against 55.4k, PR 27); the "+37 % gather, +54 % scatter" this
# line used to cite came from a window no artifact records.
AUTO_TRUSTS_F32_ROW = True
AUTO_TRUSTS_BF16_PAIR = False  # pending hardware window
AUTO_TRUSTS_FUSED_STEP = False  # single-pass step kernels: pending hardware


# ------------------------------------------------ fallback observability
#
# Every dispatch predicate above can silently reject a kernel="pallas"
# request and take the XLA path — correct, but invisible: a table that
# was supposed to ride the DMA kernels can spend its life on the
# fallback because of one misaligned dim. Mirror dedup.log_full_fallback:
# note each distinct rejection exactly once per (kernel, reason, shape,
# dtype) on the obs registry, where /metrics renders it as
# deeprec_pallas_fallback_total{kernel,reason}.

_fallback_noted: set = set()


def _note_fallback(kernel: str, reason: str, shape, dtype) -> None:
    """Count a Pallas→XLA dispatch rejection. Runs at TRACE time (shapes
    and dtypes are static), so the counter costs nothing inside the
    compiled step and dedup keeps a steady-state loop from re-counting
    the same miss on every retrace."""
    key = (kernel, reason, tuple(shape), str(jnp.dtype(dtype)))
    if key in _fallback_noted:
        return
    _fallback_noted.add(key)
    from deeprec_tpu.obs.metrics import default_registry

    default_registry().counter(
        "deeprec_pallas_fallback",
        help="Pallas kernel dispatches that fell back to XLA, by cause",
        labels={"kernel": kernel, "reason": reason},
    ).inc()


def _row_reason(dim: int, dtype) -> str:
    """Why backend.on_tpu() + _dma_ok rejected a single-row-DMA dispatch."""
    if not backend.on_tpu():
        return "not_tpu"
    if dim % _LANES != 0:
        return "dim_unaligned"
    if dim != _LANES and jnp.dtype(dtype).itemsize == 4:
        return "dim_wide"
    return "dtype"


def _pair_reason(shape, dtype) -> str:
    """Why backend.on_tpu() + _dma_pair_ok rejected a pair-granule dispatch."""
    if not backend.on_tpu():
        return "not_tpu"
    _, dim = shape
    if dim % _LANES != 0:
        return "dim_unaligned"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "dtype"
    return "odd_capacity"


def _pad_rows(ix: jnp.ndarray, block: int, fill: int = 0) -> jnp.ndarray:
    """Pad the index axis (the last; tables may lead) to a block multiple."""
    pad = (-ix.shape[-1]) % block
    if pad:
        ix = jnp.pad(ix, [(0, 0)] * (ix.ndim - 1) + [(0, pad)],
                     constant_values=fill)
    return ix


def _pad_updates(slot_ix, new_rows, block):
    """Shared scatter preamble: pad slot indices [..., U] (-1 = skip) and
    update rows [..., U, D] to a block multiple."""
    ixp = _pad_rows(
        jnp.where(slot_ix >= 0, slot_ix, -1).astype(jnp.int32), block,
        fill=-1,
    )
    pad = ixp.shape[-1] - new_rows.shape[-2]
    if pad:
        new_rows = jnp.pad(
            new_rows, [(0, 0)] * (new_rows.ndim - 2) + [(0, pad), (0, 0)]
        )
    return ixp, new_rows


def _fold(op, axis_size, in_batched, *args):
    """A vmap over `op`, whose operands and result all lead with a table
    axis [T, ...], as ONE call of `op` on axis_size * T tables: unmapped
    operands are broadcast, the two axes merge by a reshape (free: both
    lead) and the result splits back. `op` is itself the hooked function,
    so a second vmap on top folds into the same axis the same way."""
    folded = []
    for a, batched in zip(args, in_batched):
        if not batched:
            a = jnp.broadcast_to(a, (axis_size, *a.shape))
        folded.append(a.reshape(-1, *a.shape[2:]))
    out = op(*folded)
    return out.reshape(axis_size, -1, *out.shape[1:])


def _table_ranges(tables: int, rows: int):
    """(first table, tables) of each Pallas call of a stacked row kernel:
    one call for all tables while their `rows` padded indices apiece fit
    the SMEM budget, else a few calls over table ranges. Every call is
    handed the SAME whole stacked array and its range's first table as a
    scalar, so a range costs one more call and never a slice of a table."""
    per = max(1, _SMEM_INDEX_BYTES // (4 * rows))
    return [(t0, min(per, tables - t0)) for t0 in range(0, tables, per)]


def _note_schedule(kernel: str, shape, rows: int, block: int) -> None:
    """What a (kernel, shape) rides: the rows a grid step carries and the
    window its call was built with, at TRACE time like _note_fallback, as
    deeprec_pallas_row_schedule{kernel,shape,rows,block,window} 1 (a gauge:
    noting it again on a retrace changes nothing)."""
    from deeprec_tpu.obs.metrics import default_registry

    default_registry().gauge(  # noqa: DRT007 — bounded: static shapes, one series a compiled (kernel, shape), each a compile of its own
        "deeprec_pallas_row_schedule",
        help="Row-kernel calls by the rows a grid step carries and the row "
             "DMAs it keeps in flight",
        labels={"kernel": kernel, "shape": "x".join(map(str, shape)),
                "rows": str(rows), "block": str(block),
                "window": str(_GROUP * _AHEAD)},
    ).set(1)


def _wait_rows(table_ref, k: int, sem) -> None:
    """Wait on `sem` for k row DMAs of table_ref's row size: ONE wait whose
    descriptor spans k rows of the table (it is never started: a wait only
    counts the bytes), or k waits of a row where the table has fewer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    span = k if k <= table_ref.shape[0] else 1
    done = table_ref.at[pl.ds(0, span)]
    for _ in range(k // span):
        pltpu.make_async_copy(done, done, sem).wait()


def _row_window(rows: int, start, wait, live=None, moved=None) -> None:
    """One grid step's `rows` row DMAs, a window of them in flight: rows
    start in groups of _GROUP (unrolled: one loop branch a group), a group
    is waited for as the group _AHEAD after it starts, and what is left in
    flight is waited for at the end: every DMA the body starts is waited
    for before the body ends.

    `start(i)` starts row i's DMA and `wait(k)` waits for any k (static)
    rows: all rows are one size and signal ONE semaphore, so a wait is for
    that many of the rows in flight, whichever, and the last wait is for
    all of them. `live(i)`, where given, says whether row i moves at all (a
    skipped slot, a row past the call's last): a row that does not starts
    nothing and is waited for never, because a group counts the rows it
    started and is waited for by that count. `moved(g)`, where given beside
    it, is that count of group g, made before the call: a group that moves
    whole then starts its rows without a test a row, one that moves none
    reads one number, and only a mixed group asks `live` row by row."""
    from jax.experimental import pallas as pl

    groups, rest = divmod(rows, _GROUP)

    def start_group(g, size):
        """Start rows [g * _GROUP, g * _GROUP + size); how many moved."""
        first = g * _GROUP
        if live is None:
            for u in range(size):
                start(first + u)
            return jnp.int32(size)
        if moved is None:
            ok = [live(first + u) for u in range(size)]
            count = sum((o.astype(jnp.int32) for o in ok), jnp.int32(0))

            @pl.when(count > 0)  # one branch for a group that starts nothing
            def _():
                for u in range(size):
                    pl.when(ok[u])(functools.partial(start, first + u))

            return count
        if size == 0:
            return jnp.int32(0)
        count = moved(g)

        @pl.when(count == size)
        def _():
            for u in range(size):
                start(first + u)

        @pl.when((count > 0) & (count < size))
        def _():
            for u in range(size):
                pl.when(live(first + u))(functools.partial(start, first + u))

        return count

    def wait_rows(count):
        """A whole group in ONE wait, else a row at a time."""
        whole = count == _GROUP
        pl.when(whole)(lambda: wait(_GROUP))
        jax.lax.fori_loop(0, jnp.where(whole, 0, count),
                          lambda j, _: wait(1) or 0, 0)

    def step(g, in_flight):
        # the rows the last _AHEAD groups started, oldest first: none yet
        # for the first of them, so the first waits are for nothing
        wait_rows(in_flight[0])
        return in_flight[1:] + (start_group(g, _GROUP),)

    in_flight = (jnp.int32(0),) * _AHEAD
    if groups:
        in_flight = jax.lax.fori_loop(0, groups, step, in_flight)
    wait_rows(sum(in_flight) + start_group(groups, rest))


def _sr_bits(seed, shape):
    """The one seed-derivation for stochastic-rounding bits: every SR
    path (XLA fallback, row kernel, pair kernel) must use this so their
    numerics stay interchangeable."""
    key = jax.random.fold_in(jax.random.PRNGKey(0x5EED), seed)
    return jax.random.bits(key, shape, jnp.uint32)


def _sr_round_in_kernel(row_f32, bits_u32):
    """In-kernel stochastic rounding f32 -> bf16-representable f32
    (same bit-twiddle as stochastic_round): add uniform noise below the
    mantissa cut, truncate. Shared by the pair scatter and the fused
    backward (the row scatter's rows are rounded before the kernel)."""
    from jax.experimental.pallas import tpu as pltpu

    u = pltpu.bitcast(row_f32, jnp.uint32)
    u = u + (bits_u32 & jnp.uint32(0xFFFF))
    u = u & jnp.uint32(0xFFFF0000)
    return pltpu.bitcast(u, jnp.float32)


# ------------------------------------------------- bf16 pair-granule ops


def gather_rows_pair(values: jnp.ndarray, ix: jnp.ndarray, *,
                     block: int = _BLOCK,
                     interpret: bool = False) -> jnp.ndarray:
    """bf16 gather via 2-row granules: values [C, D] bf16 (D % 128 == 0,
    C even), ix [n] int32 -> [n, D]. A dynamic single-row HBM slice is
    not expressible for bf16 (rows pack 2 sublanes per 32-bit word), so
    each lookup DMAs the even-aligned PAIR containing the row and emits
    the wanted half — 2x the HBM read volume of an f32 row gather, but
    the pair shares the granule the hardware reads anyway."""
    n = ix.shape[0]
    C, D = values.shape
    if not interpret and not (
        backend.on_tpu() and _dma_pair_ok(values.shape, values.dtype)
    ):
        _note_fallback("gather_rows_pair",
                       _pair_reason(values.shape, values.dtype),
                       values.shape, values.dtype)
        return values.at[ix].get(mode="clip")

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ixp = _pad_rows(ix.astype(jnp.int32), block)
    np_ = ixp.shape[0]

    def kernel(ix_ref, values_ref, out_ref, scratch, sems):
        base = pl.program_id(0) * block

        def pair_dma(slot, i):
            idx = jnp.clip(ix_ref[base + i], 0, C - 1)
            g = (idx // 2) * 2  # even-aligned granule base
            return pltpu.make_async_copy(
                values_ref.at[pl.ds(g, 2), :],
                scratch.at[slot],
                sems.at[slot],
            )

        pair_dma(0, 0).start()

        def body(i, _):
            cur = i % 2

            @pl.when(i + 1 < block)
            def _():
                pair_dma((i + 1) % 2, i + 1).start()

            pair_dma(cur, i).wait()
            idx = jnp.clip(ix_ref[base + i], 0, C - 1)
            out_ref[i, :] = scratch[cur, idx % 2, :]
            return 0

        jax.lax.fori_loop(0, block, body, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(np_ // block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (block, D), lambda i, ix_ref: (i, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((2, 2, D), values.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    with scopes.kernel_trace(scopes.KERNEL_GATHER_ROWS_PAIR):
        out = pl.pallas_call(
            kernel,
            name=scopes.KERNEL_GATHER_ROWS_PAIR,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((np_, D), values.dtype),
            interpret=interpret,
        )(ixp, values)
    return out[:n]


def apply_rows_sr_pair(values: jnp.ndarray, slot_ix: jnp.ndarray,
                       new_rows: jnp.ndarray, seed: jnp.ndarray, *,
                       interpret: bool = False) -> jnp.ndarray:
    """bf16 scatter with IN-KERNEL stochastic rounding via 2-row
    granules: read-modify-write the even-aligned pair containing each
    target row. Fully serialized (one granule in flight): consecutive
    updates may share a granule, and the read of update i+1 must observe
    the write of update i. new_rows [U, D] f32; values [C, D] bf16."""
    U, D = new_rows.shape
    C = values.shape[0]
    if not interpret and not (
        backend.on_tpu() and _dma_pair_ok(values.shape, values.dtype)
    ):
        _note_fallback("apply_rows_sr_pair",
                       _pair_reason(values.shape, values.dtype),
                       values.shape, values.dtype)
        return apply_rows_sr(values, slot_ix, new_rows, seed,
                             use_pallas=False, interpret=False)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ixp, new_rows = _pad_updates(slot_ix, new_rows, _BLOCK)
    Up = ixp.shape[0]
    bits = _sr_bits(seed, (Up, D))

    def kernel(ix_ref, rows_ref, bits_ref, vin_ref, vout_ref, scratch, sem):
        del vin_ref  # aliased with vout_ref
        g0 = pl.program_id(0) * _BLOCK

        def body(i, _):
            idx = ix_ref[g0 + i]

            @pl.when(idx >= 0)
            def _():
                g = (idx // 2) * 2
                rd = pltpu.make_async_copy(
                    vout_ref.at[pl.ds(g, 2), :], scratch, sem.at[0]
                )
                rd.start()
                rd.wait()
                row = _sr_round_in_kernel(
                    rows_ref[pl.ds(i, 1), :].astype(jnp.float32),
                    bits_ref[pl.ds(i, 1), :],
                )
                scratch[pl.ds(idx % 2, 1), :] = row.astype(scratch.dtype)
                wr = pltpu.make_async_copy(
                    scratch, vout_ref.at[pl.ds(g, 2), :], sem.at[0]
                )
                wr.start()
                wr.wait()

            return 0

        jax.lax.fori_loop(0, _BLOCK, body, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Up // _BLOCK,),
        in_specs=[
            pl.BlockSpec(
                (_BLOCK, D), lambda i, ix_ref: (i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (_BLOCK, D), lambda i, ix_ref: (i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, D), values.dtype),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    with scopes.kernel_trace(scopes.KERNEL_APPLY_ROWS_SR_PAIR):
        return pl.pallas_call(
            kernel,
            name=scopes.KERNEL_APPLY_ROWS_SR_PAIR,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(values.shape, values.dtype),
            input_output_aliases={3: 0},
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
            interpret=interpret,
        )(ixp, new_rows, bits, values)


# ------------------------------------------------------------- gather_rows


def gather_rows(values: jnp.ndarray, ix: jnp.ndarray, *,
                block: int | None = None, interpret: bool = False,
                pair_kernels: bool = False,
                skip_negative: bool = False) -> jnp.ndarray:
    """values [C, D], ix [n] int32 -> [n, D]; out-of-range ix clamp (the
    'clip' semantics of the jnp fallback). Rows go by DMA, HBM to HBM, a
    window of them in flight (_row_window); `block=` splits a table's rows
    into grid steps of that many (default: one step carries them all).
    pair_kernels=True additionally routes eligible bf16 tables through the
    pair-granule kernel (explicit kernel="pallas" or a measured-winners
    flag — see AUTO_TRUSTS_BF16_PAIR). skip_negative=True is for a caller
    that masks those rows out itself: the row kernel then starts no DMA for
    a negative index, as the scatter starts none for a skipped slot, and
    that row of the result holds whatever its memory held (on the other
    paths it holds some row of the table)."""
    if pair_kernels and _dma_pair_ok(values.shape, values.dtype) and (
        interpret or backend.on_tpu()
    ):
        return gather_rows_pair(values, ix, block=block or _BLOCK,
                                interpret=interpret)
    if not interpret and not (
        backend.on_tpu() and _dma_ok(values.shape[1], values.dtype)
    ):
        _note_fallback("gather_rows",
                       _row_reason(values.shape[1], values.dtype),
                       values.shape, values.dtype)
        return values.at[ix].get(mode="clip")

    return _gather_rows_op(block, interpret, skip_negative)(
        values[None], ix[None])[0]


def _gather_rows_stacked(values, ix, *, block, interpret, skip):
    """The gather kernel, with its table axis: values [T, C, D], ix [T, n]
    -> [T, n, D]. One call for a range of tables (_table_ranges; all of
    them while their indices fit the budget), and where ONE table's
    indices pass it, calls over row ranges. A grid step carries all of a
    table's rows unless `block=` splits them; indices are padded only
    then, and a padded row starts no DMA."""
    n = ix.shape[1]
    # ONE table's indices over the SMEM budget (a whole table's slots
    # probed at once; a vmap over indices folded into one unmapped table):
    # calls over row ranges, as _table_ranges makes calls over tables
    most = _SMEM_INDEX_BYTES // 4
    most -= most // _GROUP if skip else 0  # the groups' counts ride too
    if n > most:
        return jnp.concatenate([
            _gather_rows_stacked(values, ix[:, r0:r0 + most], block=block,
                                 interpret=interpret, skip=skip)
            for r0 in range(0, n, most)], axis=1)
    block = block or max(n, 1)
    _note_schedule(scopes.KERNEL_GATHER_ROWS, values.shape, n, block)
    ixp = _pad_rows(ix.astype(jnp.int32), block)
    # under `skip`, how many rows of each group move: [T, groups] beside the
    # indices in SMEM, so that the kernel tests a group and not its rows
    moved = _group_counts(ixp, n, block) if skip else None
    outs = [
        _gather_call(jnp.full((1,), t0, jnp.int32), ixp[t0:t0 + tables],
                     values, moved[t0:t0 + tables] if skip else None,
                     n=n, block=block, interpret=interpret)
        for t0, tables in _table_ranges(
            values.shape[0], ixp.shape[1] + (moved.shape[1] if skip else 0))
    ]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def _group_counts(ixp, n, block):
    """The rows that move (index >= 0, inside the call's n) of each group
    of _GROUP rows as _row_window walks them: [T, blocks * groups a block],
    a block's last group the rest of it."""
    tables, np_ = ixp.shape
    live = (ixp >= 0) & (jnp.arange(np_) < n)
    per = -(-block // _GROUP)
    live = jnp.pad(live.reshape(tables, np_ // block, block),
                   ((0, 0), (0, 0), (0, per * _GROUP - block)))
    return live.reshape(tables, -1, _GROUP).sum(-1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def _gather_call(t0, ixp, values, moved=None, *, n, block, interpret):
    """One Pallas call of the gather: the tables [t0, t0 + len(ixp)) of
    values [T, C, D] at the (padded) indices ixp -> [len(ixp), n, D]. The
    grid is (table, row block), the indices are one flat scalar prefetch,
    and a row's DMA goes from values_ref[t0 + t, idx] in the whole stacked
    array straight to its row of the result, HBM to HBM with no block in
    VMEM, a window of them in flight (_row_window). With `moved`
    (skip_negative: _group_counts of ixp, a third scalar prefetch) a
    negative index is a row that does not move. Jitted, so that a step's
    calls of one shape are traced and lowered once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, C, D = values.shape
    tables, np_ = ixp.shape
    skip = moved is not None

    def kernel(t0_ref, ix_ref, *refs):
        moved_ref = refs[0] if skip else None
        values_ref, out_ref, sem = refs[skip:]
        t = pl.program_id(0)
        row0 = pl.program_id(1) * block
        base = t * np_ + row0
        if skip:  # this grid step's first group among `moved`'s
            group0 = (t * (np_ // block) + pl.program_id(1)) * (
                moved.shape[1] // (np_ // block))

        def start(i):
            idx = jnp.clip(ix_ref[base + i], 0, C - 1)
            pltpu.make_async_copy(
                values_ref.at[t0_ref[0] + t, idx], out_ref.at[t, row0 + i],
                sem,
            ).start()

        def wait(k):
            _wait_rows(values_ref.at[t0_ref[0] + t], k, sem)

        def live(i):
            inside = row0 + i < n
            return inside & (ix_ref[base + i] >= 0) if skip else inside

        _row_window(block, start, wait,
                    live if skip or np_ != n else None,
                    (lambda g: moved_ref[group0 + g]) if skip else None)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + skip,
        grid=(tables, np_ // block),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    with scopes.kernel_trace(scopes.KERNEL_GATHER_ROWS):
        return pl.pallas_call(
            kernel,
            name=scopes.KERNEL_GATHER_ROWS,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((tables, n, D), values.dtype),
            interpret=interpret,
        )(t0, ixp.reshape(-1), *([moved.reshape(-1)] if skip else []), values)


@functools.lru_cache(maxsize=None)
def _gather_rows_op(block, interpret, skip=False):
    """_gather_rows_stacked under the hook that folds a vmap into its
    table axis (module docstring, "Batching")."""
    op = jax.custom_batching.custom_vmap(functools.partial(
        _gather_rows_stacked, block=block, interpret=interpret, skip=skip
    ))

    @op.def_vmap
    def _(axis_size, in_batched, values, ix):
        if not in_batched[0]:
            # indices mapped over unmapped tables: S * n rows of each table
            T, n = ix.shape[1:]
            out = op(values, jnp.swapaxes(ix, 0, 1).reshape(T, -1))
            out = out.reshape(T, axis_size, n, -1)
            return jnp.swapaxes(out, 0, 1), True
        return _fold(op, axis_size, in_batched, values, ix), True

    return op


# ----------------------------------------------------- fused gather+combine


def fused_gather_combine(values: jnp.ndarray, row_ix: jnp.ndarray,
                         weights: jnp.ndarray, *, block_b: int = 8,
                         interpret: bool = False,
                         pair_kernels: bool = False) -> jnp.ndarray:
    """Pooled bags straight from the table.

    values [C, D]; row_ix [B, L] int32 slot per position (< 0 = skip);
    weights [B, L] f32 per-position weight (carry the combiner here: 1 for
    sum, 1/n_b for mean, 1/sqrt(n_b) for sqrtn, 0 for pad/blocked).
    Returns [B, D] f32: out[b] = sum_l weights[b, l] * values[row_ix[b, l]].
    pair_kernels routes eligible bf16 tables through 2-row granule DMAs
    (same rationale as gather_rows_pair).
    """
    B, L = row_ix.shape
    C, D = values.shape
    pair = pair_kernels and _dma_pair_ok(values.shape, values.dtype) and (
        interpret or backend.on_tpu()
    )
    if not pair and not interpret and not (
        backend.on_tpu() and _dma_ok(D, values.dtype)
    ):
        _note_fallback("fused_gather_combine", _row_reason(D, values.dtype),
                       values.shape, values.dtype)
        e = values.at[jnp.clip(row_ix, 0, C - 1)].get(mode="clip")
        w = jnp.where(row_ix >= 0, weights, 0.0)
        return jnp.sum(e.astype(jnp.float32) * w[..., None], axis=1)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    padB = (-B) % block_b
    if padB:
        row_ix = jnp.concatenate(
            [row_ix, jnp.full((padB, L), -1, row_ix.dtype)]
        )
        weights = jnp.concatenate([weights, jnp.zeros((padB, L), weights.dtype)])
    Bp = row_ix.shape[0]
    flat_ix = row_ix.reshape(-1).astype(jnp.int32)
    # Weights ride SMEM as a second scalar-prefetch operand: a dynamic
    # per-position scalar read from a VMEM block is not expressible on TPU
    # ("index in dimension 1 must be a multiple of 128"); SMEM scalar loads
    # at computed offsets are.
    flat_w = weights.reshape(-1).astype(jnp.float32)
    rows_per_blk = block_b * L

    def kernel(ix_ref, w_ref, values_ref, out_ref, scratch, sems):
        base = pl.program_id(0) * rows_per_blk

        def row_dma(slot, j):
            idx = jnp.clip(ix_ref[base + j], 0, C - 1)
            if pair:
                g = (idx // 2) * 2  # even-aligned bf16 granule
                return pltpu.make_async_copy(
                    values_ref.at[pl.ds(g, 2), :], scratch.at[slot],
                    sems.at[slot],
                )
            return pltpu.make_async_copy(
                values_ref.at[idx], scratch.at[slot], sems.at[slot]
            )

        row_dma(0, 0).start()
        out_ref[:] = jnp.zeros_like(out_ref)

        def body(j, _):
            cur = j % 2

            @pl.when(j + 1 < rows_per_blk)
            def _():
                row_dma((j + 1) % 2, j + 1).start()

            row_dma(cur, j).wait()
            b = j // L
            w = jnp.where(ix_ref[base + j] >= 0, w_ref[base + j], 0.0)
            if pair:
                idx = jnp.clip(ix_ref[base + j], 0, C - 1)
                row = scratch[cur, idx % 2, :]
            else:
                row = scratch[cur]
            out_ref[b, :] = out_ref[b, :] + w * row.astype(jnp.float32)
            return 0

        jax.lax.fori_loop(0, rows_per_blk, body, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (block_b, D), lambda i, ix_ref, w_ref: (i, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM(((2, 2, D) if pair else (2, D)), values.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    with scopes.kernel_trace(scopes.KERNEL_FUSED_GATHER_COMBINE):
        out = pl.pallas_call(
            kernel,
            name=scopes.KERNEL_FUSED_GATHER_COMBINE,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((Bp, D), jnp.float32),
            interpret=interpret,
        )(flat_ix, flat_w, values)
    return out[:B]


# --------------------------------------------------- stochastic-rounded apply


def stochastic_round(x: jnp.ndarray, key: jnp.ndarray,
                     dtype=jnp.bfloat16) -> jnp.ndarray:
    """XLA stochastic rounding f32 -> bf16: add uniform noise below the
    mantissa cut, then truncate. E[round(x)] == x, so tiny optimizer updates
    survive bf16 tables in expectation instead of vanishing at ulp/2."""
    assert dtype == jnp.bfloat16, "only bf16 targets supported"
    bits = jax.random.bits(key, x.shape, jnp.uint32)
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = u + (bits & jnp.uint32(0xFFFF))  # carry into the kept mantissa
    u = u & jnp.uint32(0xFFFF0000)  # truncate to bf16-representable
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(jnp.bfloat16)


def apply_rows_sr(values: jnp.ndarray, slot_ix: jnp.ndarray,
                  new_rows: jnp.ndarray, seed: jnp.ndarray, *,
                  block: int | None = None, interpret: bool = False,
                  use_pallas: bool = True,
                  pair_kernels: bool = False) -> jnp.ndarray:
    """Scatter new_rows [U, D] f32 into values [C, D] at slot_ix [U]
    (< 0 = skip). bf16 tables round stochastically; f32 tables store exact.
    Returns the updated values array (aliased in-place under jit on TPU).
    use_pallas=False keeps the XLA scatter (still stochastic-rounding bf16);
    pair_kernels=True routes eligible bf16 tables through the pair-granule
    read-modify-write kernel with IN-KERNEL stochastic rounding."""
    U, D = new_rows.shape
    C = values.shape[0]
    if use_pallas and pair_kernels and _dma_pair_ok(values.shape, values.dtype) and (
        interpret or backend.on_tpu()
    ):
        return apply_rows_sr_pair(values, slot_ix, new_rows, seed,
                                  interpret=interpret)
    if not interpret and not (
        use_pallas and backend.on_tpu() and _dma_ok(D, values.dtype)
    ):
        if use_pallas:
            # only a *rejected* Pallas request is a fallback worth noting;
            # use_pallas=False callers asked for the XLA scatter.
            _note_fallback("apply_rows_sr", _row_reason(D, values.dtype),
                           values.shape, values.dtype)
        if values.dtype == jnp.bfloat16:
            key = jax.random.fold_in(jax.random.PRNGKey(0x5EED), seed)
            rows = stochastic_round(new_rows, key)
        else:
            rows = new_rows.astype(values.dtype)
        ix = jnp.where(slot_ix >= 0, slot_ix, C)
        return values.at[ix].set(rows, mode="drop")

    return _apply_rows_op(block, interpret)(
        values[None], slot_ix[None], new_rows[None],
        jnp.asarray(seed, jnp.int32).reshape(1),
    )[0]


def _apply_rows_stacked(values, slot_ix, new_rows, seed, *, block,
                        interpret):
    """The scatter kernel, with its table axis: values [T, C, D],
    slot_ix [T, U], new_rows [T, U, D], seed [T] -> the updated [T, C, D],
    aliased onto `values`: in place on a donated stacked table state. The
    kernel only copies: a bf16 table's rows are rounded before it, a table
    at a time from its own seed, as its unbatched call and the XLA scatter
    round them."""
    U = slot_ix.shape[1]
    block = block or max(U, 1)
    _note_schedule(scopes.KERNEL_APPLY_ROWS_SR, values.shape, U, block)
    # Pad with -1 (skip): a padded slot starts no DMA.
    ixp = _pad_rows(
        jnp.where(slot_ix >= 0, slot_ix, -1).astype(jnp.int32), block,
        fill=-1,
    )
    if values.dtype == jnp.bfloat16:
        new_rows = jax.vmap(
            lambda r, s: _sr_round_bits(r, _sr_bits(s, r.shape))
        )(new_rows, seed)
    new_rows = new_rows.astype(values.dtype)
    for t0, tables in _table_ranges(values.shape[0], ixp.shape[1]):
        values = _apply_call(jnp.full((1,), t0, jnp.int32),
                             ixp[t0:t0 + tables], new_rows, values,
                             block=block, interpret=interpret)
    return values


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _apply_call(t0, ixp, new_rows, values, *, block, interpret):
    """One Pallas call of the scatter: rows of new_rows [T, U, D] into the
    tables [t0, t0 + len(ixp)) of values [T, C, D] at the (padded) slots
    ixp, -1 = skip. A row's DMA goes from new_rows[t0 + t, i] straight to
    vout_ref[t0 + t, idx], HBM to HBM, a window of them in flight
    (_row_window: valid slots of a call are unique, so the writes do not
    meet). Jitted for the reason _gather_call is."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tables, Up = ixp.shape

    def kernel(t0_ref, ix_ref, rows_ref, vin_ref, vout_ref, sem):
        del vin_ref  # aliased with vout_ref
        t = t0_ref[0] + pl.program_id(0)
        row0 = pl.program_id(1) * block
        base = pl.program_id(0) * Up + row0

        def start(i):
            pltpu.make_async_copy(
                rows_ref.at[t, row0 + i], vout_ref.at[t, ix_ref[base + i]],
                sem,
            ).start()

        def wait(k):
            _wait_rows(vout_ref.at[t], k, sem)

        _row_window(block, start, wait, lambda i: ix_ref[base + i] >= 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tables, Up // block),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    with scopes.kernel_trace(scopes.KERNEL_APPLY_ROWS_SR):
        return pl.pallas_call(
            kernel,
            name=scopes.KERNEL_APPLY_ROWS_SR,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(values.shape, values.dtype),
            input_output_aliases={3: 0},
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
            interpret=interpret,
        )(t0, ixp.reshape(-1), new_rows, values)


@functools.lru_cache(maxsize=None)
def _apply_rows_op(block, interpret):
    """_apply_rows_stacked under the hook that folds a vmap into its
    table axis (module docstring, "Batching")."""
    op = jax.custom_batching.custom_vmap(functools.partial(
        _apply_rows_stacked, block=block, interpret=interpret
    ))

    @op.def_vmap
    def _(axis_size, in_batched, values, slot_ix, new_rows, seed):
        if not in_batched[0]:
            # Updates mapped over ONE table: each wants a table of its own
            # (folding them into one breaks the unique-slot contract), so
            # the table is copied axis_size times. Worth a reader's notice.
            _note_fallback("apply_rows_sr", "values_unmapped",
                           values.shape, values.dtype)
        return _fold(op, axis_size, in_batched, values, slot_ix, new_rows,
                     seed), True

    return op


# ------------------------------------------------------- fused sparse step
#
# The single-pass per-table step kernels (docs/kernels.md). Forward: one
# Pallas pass runs a hash-probe dedup inline (its scratch table lives in
# VMEM and the ids are walked one by one, so a claim is a plain in-kernel
# slot write), DMAs each unique row from HBM exactly once, and
# segment-combines straight into the [B, D] output: the [U, D] unique-rows
# buffer never round-trips through HBM. Backward: one pass segment-sums the
# per-example output gradient into unique-row space in VMEM, stages the
# touched value/slot rows in, applies the optimizer row-function, and
# DMA-scatters the results back — the [U, D] gradient buffer never exists
# outside the kernel either. Both are oracle-tested on CPU via
# interpret=True against the XLA composition below (bit-identical fp32,
# same-bits SR equality bf16; tests/test_fused_step.py).


class FusedBags(NamedTuple):
    """Everything fused_sparse_forward produced / the backward consumes.

    out      [B, D] f32 pooled bags (always f32: rows are cast up before
             the combine on BOTH paths, so bf16 tables pool exactly).
    uids     [U] int32 unique row indices; uids[0] == -1 (reserved
             sentinel, the dedup_at_budget contract). NOTE the ORDER of
             uids is path-dependent (kernel claims in first-occurrence
             order, the XLA fallback packs in hash order); `out` and the
             uids↔inverse correspondence are order-independent.
    inverse  [B, L] int32 position -> unique slot (0 = pad/overflow).
    counts   [U] int32 occurrences per unique slot (counts[0] == 0).
    overflow [] int32 distinct ids past the budget + unresolved probes.
    """

    out: jnp.ndarray
    uids: jnp.ndarray
    inverse: jnp.ndarray
    counts: jnp.ndarray
    overflow: jnp.ndarray


def _sr_bits_rows(seed, uids: jnp.ndarray, dim: int) -> jnp.ndarray:
    """Row-KEYED stochastic-rounding bits: a pure integer hash of
    (seed, row id, column). The positional `_sr_bits` stream would hand a
    row different noise depending on the order dedup emitted it — and the
    fused kernel and the XLA fallback emit uids in different (equally
    valid) orders — so bf16 parity across paths needs bits that are a
    function of the ROW, not its position in the unique set."""
    from deeprec_tpu.utils import hashing

    s = hashing.mix32(jnp.asarray(seed).astype(jnp.uint32))
    base = hashing.mix32(hashing.fold64(uids) ^ s)  # [U]
    col = hashing.mix32(
        jnp.arange(dim, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
    )  # [D]
    return hashing.mix32(base[:, None] ^ col[None, :])  # [U, D]


def _sr_round_bits(x: jnp.ndarray, bits: jnp.ndarray) -> jnp.ndarray:
    """XLA stochastic rounding from caller-supplied bits — the same
    twiddle as stochastic_round / _sr_round_in_kernel, so the fallback
    and the kernel are bit-interchangeable when fed the same bits."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = u + (bits & jnp.uint32(0xFFFF))
    u = u & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(jnp.bfloat16)


def _bag_denominator(mask: jnp.ndarray, combiner: str) -> jnp.ndarray:
    """Per-bag combine denominator [B, 1] f32: 1 for sum, max(n,1) for
    mean, sqrt(max(n,1)) for sqrtn. Always applied OUTSIDE the
    kernel-vs-fallback branch (forward epilogue and backward grad
    pre-scaling), so the division is one shared traced computation: XLA's
    algebraic simplifier rewrites x/sqrt(n) into x*rsqrt(n) in some graph
    contexts and not others (1-ulp apart — observed on CPU), and a
    division INSIDE the branch would let the two paths drift by exactly
    that rewrite."""
    n = jnp.sum(mask.astype(jnp.float32), axis=1, keepdims=True)
    if combiner == "sum":
        return jnp.ones_like(n)
    if combiner == "mean":
        return jnp.maximum(n, 1.0)
    if combiner == "sqrtn":
        return jnp.sqrt(jnp.maximum(n, 1.0))
    raise ValueError(f"unknown combiner: {combiner}")


def _combine_epilogue(bags: "FusedBags", ids: jnp.ndarray,
                      combiner: str) -> "FusedBags":
    """mean/sqrtn scaling over the raw per-bag sums, shared by both
    forward paths (see _bag_denominator for why it must live out here)."""
    if combiner == "sum":
        return bags
    return bags._replace(
        out=bags.out / _bag_denominator(ids >= 0, combiner)
    )


def fusable_optimizer(opt, dim: int) -> bool:
    """The fused backward stages slot rows in VMEM as [U, dim] tiles: an
    optimizer qualifies iff every slot is a full-width (dim,) row — no
    per-table scalars (AdamAsync's beta powers), no (1,)-wide rows
    (AdagradDecay's decay_period). sgd/adagrad/adam/adamw/ftrl qualify;
    the rest keep the split-phase apply_gradients path."""
    from deeprec_tpu.optim.sparse import SCALAR_PREFIX

    for name, (shape, _) in opt.slot_specs(dim).items():
        if name.startswith(SCALAR_PREFIX) or tuple(shape) != (dim,):
            return False
    return True


def _scratch_size(n: int) -> int:
    """Slots of the fused forward kernel's in-VMEM probe table for `n`
    positions: the next power of two >= 4 (n + 1), so an all-distinct
    batch loads it to a quarter and its linear-probe chains stay short."""
    return 1 << (4 * (int(n) + 1) - 1).bit_length()


def fused_sparse_forward(values: jnp.ndarray, ids: jnp.ndarray, *,
                         combiner: str = "sum", unique_size: int,
                         max_probes: int = 64, interpret: bool = False,
                         use_pallas: bool = True) -> FusedBags:
    """Single-pass budgeted lookup: dedup-probe + unique-row gather +
    segment-combine, one kernel per table.

    values [C, D]; ids [B, L] int32 ROW indices into values (< 0 = pad);
    unique_size the static dedup budget U (>= 2; index 0 is the reserved
    sentinel slot — use dedup.resolve_size). Returns FusedBags.

    Off-TPU (and for any shape _dma_ok rejects) this is the identical-
    semantics XLA composition dedup_at_budget -> gather ->
    combiners.combine, which doubles as the oracle for the interpret-mode
    kernel tests. When `overflow > 0` the SET of budgeted ids is
    path-dependent (claim order vs hash order) — both satisfy the budget
    contract. `max_probes` bounds the kernel's own probe chains; the XLA
    composition has none.
    """
    B, L = ids.shape
    C, D = values.shape
    U = int(unique_size)
    N = B * L
    flat = jnp.where(ids >= 0, ids, -1).reshape(-1).astype(jnp.int32)

    if not interpret and not (
        use_pallas and backend.on_tpu() and _dma_ok(D, values.dtype)
    ):
        if use_pallas:
            _note_fallback("fused_sparse_forward",
                           _row_reason(D, values.dtype),
                           values.shape, values.dtype)
        from deeprec_tpu.embedding import combiners
        from deeprec_tpu.ops import dedup

        uids, inverse, counts, overflow = dedup.dedup_at_budget(
            flat, U, sentinel=-1
        )
        emb = values.at[jnp.clip(uids, 0, C - 1)].get(mode="clip").astype(
            jnp.float32
        )
        emb = jnp.where((uids >= 0)[:, None], emb, 0.0)
        out = combiners.combine(emb, inverse.reshape(B, L), ids >= 0,
                                "sum")
        return _combine_epilogue(
            FusedBags(out, uids, inverse.reshape(B, L), counts, overflow),
            ids, combiner,
        )

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeprec_tpu.utils import hashing

    # Probe table sizing: `_scratch_size`'s load factor, laid out
    # (S // 128, 128) so slot access is a dynamic SUBLANE slice plus an
    # iota-select over lanes (a dynamic LANE index is not expressible on
    # TPU). Floor of one full lane row.
    S = max(_scratch_size(N), _LANES)

    def kernel(ids_ref, values_ref, out_ref, uids_ref, inv_ref, cnt_ref,
               ovf_ref, ubuf, lbuf, tabk, tabu, usm, sem):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        mask_s = jnp.uint32(S - 1)

        def tab_read(ref, pos):
            row = ref[pl.ds(pos // _LANES, 1), :]
            return jnp.sum(jnp.where(lane == (pos % _LANES), row, 0))

        def tab_write(ref, pos, val):
            r = pos // _LANES
            row = ref[pl.ds(r, 1), :]
            ref[pl.ds(r, 1), :] = jnp.where(lane == (pos % _LANES), val, row)

        tabk[...] = jnp.full_like(tabk[...], -1)
        tabu[...] = jnp.zeros_like(tabu[...])
        cnt_ref[...] = jnp.zeros_like(cnt_ref[...])
        uids_ref[...] = jnp.full_like(uids_ref[...], -1)
        # Only row 0 (the sentinel every pad/overflow position points at)
        # is ever read without having been DMA'd; zero the lot anyway so
        # no uninitialized VMEM can leak through a future indexing bug.
        ubuf[...] = jnp.zeros_like(ubuf[...])

        # ---- phase 1: sequential hash-probe insert: a claim is an
        # in-kernel slot write (the insert loop is serial in here, so
        # there is no claim race to re-check).
        def insert(n, carry):
            nu, ovf = carry
            idv = ids_ref[n]
            valid = idv >= 0
            h0 = hashing.mix32(hashing.fold64(idv))

            def cond(c):
                return jnp.logical_and(~c[1], c[0] < max_probes)

            def body(c):
                p_step, done, u, nu, ovf = c
                pos = ((h0 + p_step.astype(jnp.uint32)) & mask_s).astype(
                    jnp.int32
                )
                k = tab_read(tabk, pos)
                hit = k == idv
                empty = k == -1
                u = jnp.where(hit, tab_read(tabu, pos), u)
                new_u = jnp.where(nu < jnp.int32(U), nu, 0)

                @pl.when(empty)
                def _():
                    tab_write(tabk, pos, idv)
                    tab_write(tabu, pos, new_u)

                @pl.when(empty & (nu < jnp.int32(U)))
                def _():
                    uids_ref[pl.ds(new_u, 1), :] = idv.reshape(1, 1)
                    usm[new_u] = idv

                u = jnp.where(empty, new_u, u)
                ovf = ovf + jnp.where(
                    empty & (nu >= jnp.int32(U)), 1, 0
                ).astype(jnp.int32)
                nu = nu + jnp.where(
                    empty & (nu < jnp.int32(U)), 1, 0
                ).astype(jnp.int32)
                done = done | hit | empty
                return p_step + 1, done, u, nu, ovf

            _, done, u, nu, ovf = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), ~valid, jnp.int32(0), nu, ovf),
            )
            # probe chain exhausted: the position counts as overflow.
            ovf = ovf + jnp.where(valid & ~done, 1, 0).astype(jnp.int32)
            inv_ref[pl.ds(n, 1), :] = u.reshape(1, 1)

            @pl.when(u > 0)
            def _():
                cnt_ref[pl.ds(u, 1), :] = cnt_ref[pl.ds(u, 1), :] + 1

            return nu, ovf

        _, ovf = jax.lax.fori_loop(
            0, N, insert, (jnp.int32(1), jnp.int32(0))
        )
        ovf_ref[...] = ovf.reshape(1, 1)

        # ---- phase 2: DMA each unique row HBM -> VMEM once (2-deep
        # pipeline, same idiom as gather_rows). Unclaimed tail slots
        # fetch a clamped row unconditionally so start/wait stay paired.
        def fetch(slot, u):
            idx = jnp.clip(usm[u], 0, C - 1)
            return pltpu.make_async_copy(
                values_ref.at[idx], ubuf.at[u], sem.at[slot]
            )

        if U > 1:
            def fbody(u, _):
                @pl.when(u + 1 < U)
                def _():
                    fetch((u + 1) % 2, u + 1).start()

                fetch(u % 2, u).wait()
                return 0

            fetch(1, 1).start()
            jax.lax.fori_loop(1, U, fbody, 0)

        # Re-zero rows nobody claimed (their DMA fetched a clamped row):
        # inverse never points at them, but uids/counts are public and
        # tests reconstruct embeddings from the buffer's contract.
        def clear(u, _):
            @pl.when(usm[u] < 0)
            def _():
                ubuf[pl.ds(u, 1), :] = jnp.zeros_like(
                    ubuf[pl.ds(u, 1), :]
                )

            return 0

        jax.lax.fori_loop(1, U, clear, 0)

        # ---- phase 3: segment-sum into [B, D], mirroring
        # combiners.combine(..., "sum") term by term (per-position
        # multiply, one axis-reduction per bag) so fp32 output is
        # bit-identical to the fallback; the mean/sqrtn division happens
        # in the shared _combine_epilogue outside the kernel.
        def bag(b, _):
            def pos(loc, nb):
                j = b * L + loc
                w = jnp.where(ids_ref[j] >= 0, 1.0, 0.0).astype(
                    jnp.float32
                )
                u = jnp.sum(inv_ref[pl.ds(j, 1), :])
                row = ubuf[pl.ds(u, 1), :].astype(jnp.float32)
                lbuf[pl.ds(loc, 1), :] = row * w
                return nb + w

            jax.lax.fori_loop(0, L, pos, jnp.float32(0.0))
            out_ref[pl.ds(b, 1), :] = jnp.sum(
                lbuf[...], axis=0, keepdims=True
            )
            return 0

        jax.lax.fori_loop(0, B, bag, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(
            pl.BlockSpec((B, D), lambda i, ids_ref: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((U, 1), lambda i, ids_ref: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, 1), lambda i, ids_ref: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((U, 1), lambda i, ids_ref: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i, ids_ref: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((U, D), values.dtype),          # unique rows
            pltpu.VMEM((max(L, 1), D), jnp.float32),   # one bag's terms
            pltpu.VMEM((S // _LANES, _LANES), jnp.int32),  # probe keys
            pltpu.VMEM((S // _LANES, _LANES), jnp.int32),  # probe -> uid
            pltpu.SMEM((U,), jnp.int32),  # uids mirror: scalar DMA indices
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    with scopes.kernel_trace(scopes.KERNEL_FUSED_SPARSE_FORWARD):
        out, uids, inv, cnt, ovf = pl.pallas_call(
            kernel,
            name=scopes.KERNEL_FUSED_SPARSE_FORWARD,
            grid_spec=grid_spec,
            out_shape=(
                jax.ShapeDtypeStruct((B, D), jnp.float32),
                jax.ShapeDtypeStruct((U, 1), jnp.int32),
                jax.ShapeDtypeStruct((N, 1), jnp.int32),
                jax.ShapeDtypeStruct((U, 1), jnp.int32),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            ),
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
            interpret=interpret,
        )(flat, values)
    return _combine_epilogue(
        FusedBags(out, uids[:, 0], inv[:, 0].reshape(B, L), cnt[:, 0],
                  ovf[0, 0]),
        ids, combiner,
    )


def fused_sparse_backward(values: jnp.ndarray,
                          slots: Dict[str, jnp.ndarray],
                          grad_out: jnp.ndarray, ids: jnp.ndarray,
                          res: FusedBags, opt, *, combiner: str = "sum",
                          step=0, lr=None, seed=0,
                          grad_averaging: bool = False,
                          interpret: bool = False,
                          use_pallas: bool = True,
                          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-pass backward: segment-sum per-example grads to unique rows
    and apply the optimizer update, fused into the scatter.

    values [C, D]; slots {name: [C, D] f32} (the optimizer's row slots —
    must satisfy fusable_optimizer, else the XLA composition runs even
    under interpret); grad_out [B, D] grad w.r.t. the forward's `out`;
    ids/res from the matching fused_sparse_forward call. bf16 tables
    stochastic-round with ROW-keyed bits (_sr_bits_rows), so kernel and
    fallback round identically regardless of uid order. Returns
    (new_values, new_slots).
    """
    B, L = ids.shape
    C, D = values.shape
    U = res.uids.shape[0]
    N = B * L
    step = jnp.asarray(step, jnp.int32)
    lr = jnp.asarray(opt.lr if lr is None else lr, jnp.float32)
    mask = ids >= 0
    sr = values.dtype == jnp.bfloat16
    bits = _sr_bits_rows(seed, res.uids, D) if sr else None
    # Combiner scaling happens HERE, shared by both paths (see
    # _bag_denominator for why the division can't live inside the branch).
    gs = grad_out.astype(jnp.float32) / _bag_denominator(mask, combiner)
    fusable = fusable_optimizer(opt, D)
    snames = sorted(slots)
    for name in snames:
        if slots[name].shape != (C, D):
            # A silent fallback here would gather WRONG rows (a packed
            # slot's row space is C // P) — reject loudly instead.
            raise ValueError(
                f"fused_sparse_backward: slot {name!r} has shape "
                f"{slots[name].shape}, want {(C, D)} — packed slot "
                "layouts keep the split-phase apply_gradients path"
            )

    if not fusable or (
        not interpret and not (use_pallas and backend.on_tpu()
                               and _dma_ok(D, values.dtype))
    ):
        if use_pallas and not interpret:
            _note_fallback(
                "fused_sparse_backward",
                "optimizer" if not fusable else _row_reason(D, values.dtype),
                values.shape, values.dtype,
            )
        g = gs  # [B, D], combiner-scaled above
        w = mask.astype(jnp.float32)[..., None]
        contrib = (jnp.broadcast_to(g[:, None, :], (B, L, D)) * w).reshape(
            N, D
        )
        grad_u = jnp.zeros((U, D), jnp.float32).at[
            res.inverse.reshape(-1)
        ].add(contrib)
        grad_u = grad_u.at[0].set(0.0)
        if grad_averaging:
            grad_u = grad_u / jnp.maximum(
                res.counts.astype(jnp.float32), 1.0
            )[:, None]
        ok = res.uids >= 0
        safe = jnp.where(ok, jnp.clip(res.uids, 0, C - 1), 0)
        value = values.at[safe].get(mode="clip").astype(jnp.float32)
        row_slots = {
            name: slots[name].at[safe].get(mode="clip").astype(jnp.float32)
            for name in snames
        }
        new_value, new_slots = opt.update(value, row_slots, grad_u,
                                          res.counts, step, lr)
        rows = (_sr_round_bits(new_value, bits) if sr
                else new_value.astype(values.dtype))
        drop = jnp.where(ok, safe, C)
        out_values = values.at[drop].set(rows, mode="drop")
        out_slots = {
            name: slots[name].at[drop].set(
                new_slots[name].astype(slots[name].dtype), mode="drop"
            )
            for name in snames
        }
        return out_values, out_slots

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K = len(snames)
    if sr:
        bits_in, bits_dim = bits, D
    else:
        # f32 path never reads the bits: ship a 1-wide dummy, not U*D zeros.
        bits_in = jnp.zeros((U, 1), jnp.uint32)  # noqa: DRT003 — deliberate 1-wide dummy: f32 path never reads it
        bits_dim = 1

    def kernel(*refs):
        (uids_ref, inv_ref, m_ref, step_ref, lr_ref,
         g_ref, cnt_ref, bits_ref) = refs[:8]
        # refs[8 : 9+K] are the aliased value/slot inputs — read through
        # the output refs below (aliasing makes them the same buffers).
        vout = refs[9 + K]
        souts = refs[10 + K:10 + 2 * K]
        gbuf = refs[10 + 2 * K]
        vstage = refs[11 + 2 * K]
        stgs = refs[12 + 2 * K:12 + 3 * K]
        sem = refs[12 + 3 * K]

        # ---- phase A: segment-sum grads into unique-row space, same
        # accumulation order (flat position order) as the XLA scatter-add.
        gbuf[...] = jnp.zeros_like(gbuf[...])

        def accum(n, _):
            u = inv_ref[n]
            w = jnp.where(m_ref[n] > 0, 1.0, 0.0).astype(jnp.float32)
            b = n // L
            row = g_ref[pl.ds(b, 1), :]  # combiner-scaled by the caller
            gbuf[pl.ds(u, 1), :] = gbuf[pl.ds(u, 1), :] + row * w
            return 0

        jax.lax.fori_loop(0, N, accum, 0)
        gbuf[pl.ds(0, 1), :] = jnp.zeros_like(gbuf[pl.ds(0, 1), :])
        if grad_averaging:
            gbuf[...] = gbuf[...] / jnp.maximum(
                cnt_ref[...].astype(jnp.float32), 1.0
            )

        # ---- phase B: stage the touched value + slot rows VMEM-side
        # (one DMA per row per array; unclaimed tail rows fetch a clamped
        # row that phase D never writes back).
        def stage(u, _):
            idx = jnp.clip(uids_ref[u], 0, C - 1)
            cps = [pltpu.make_async_copy(
                vout.at[idx], vstage.at[u], sem.at[0]
            )]
            for k in range(K):
                cps.append(pltpu.make_async_copy(
                    souts[k].at[idx], stgs[k].at[u], sem.at[1 + k]
                ))
            for c in cps:
                c.start()
            for c in cps:
                c.wait()
            return 0

        jax.lax.fori_loop(1, U, stage, 0)

        # ---- phase C: the optimizer row-function over the whole [U, D]
        # stage — the SAME update() the unfused apply calls, so numerics
        # agree by construction; bf16 adds row-keyed SR before downcast.
        new_value, new_slots = opt.update(
            vstage[...].astype(jnp.float32),
            {snames[k]: stgs[k][...] for k in range(K)},
            gbuf[...],
            cnt_ref[...][:, 0],
            step_ref[0],
            lr_ref[0],
        )
        if sr:
            new_value = _sr_round_in_kernel(new_value, bits_ref[...])
        vstage[...] = new_value.astype(vstage.dtype)
        for k in range(K):
            stgs[k][...] = new_slots[snames[k]].astype(stgs[k].dtype)

        # ---- phase D: DMA-scatter the updated rows back (guarded: the
        # sentinel row and unclaimed tail slots are never written).
        def unstage(u, _):
            @pl.when(uids_ref[u] >= 0)
            def _():
                idx = jnp.clip(uids_ref[u], 0, C - 1)
                cps = [pltpu.make_async_copy(
                    vstage.at[u], vout.at[idx], sem.at[0]
                )]
                for k in range(K):
                    cps.append(pltpu.make_async_copy(
                        stgs[k].at[u], souts[k].at[idx], sem.at[1 + k]
                    ))
                for c in cps:
                    c.start()
                for c in cps:
                    c.wait()

            return 0

        jax.lax.fori_loop(1, U, unstage, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((B, D), lambda i, *_: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((U, 1), lambda i, *_: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((U, bits_dim), lambda i, *_: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in range(K)],
        out_specs=tuple(
            pl.BlockSpec(memory_space=pl.ANY) for _ in range(1 + K)
        ),
        scratch_shapes=[
            pltpu.VMEM((U, D), jnp.float32),   # grad_u (never leaves VMEM)
            pltpu.VMEM((U, D), values.dtype),  # value stage
        ] + [pltpu.VMEM((U, D), jnp.float32) for _ in range(K)]
        + [pltpu.SemaphoreType.DMA((1 + K,))],
    )
    with scopes.kernel_trace(scopes.KERNEL_FUSED_SPARSE_BACKWARD):
        outs = pl.pallas_call(
            kernel,
            name=scopes.KERNEL_FUSED_SPARSE_BACKWARD,
            grid_spec=grid_spec,
            out_shape=tuple(
                [jax.ShapeDtypeStruct(values.shape, values.dtype)]
                + [jax.ShapeDtypeStruct(slots[n].shape, slots[n].dtype)
                   for n in snames]
            ),
            input_output_aliases={8 + i: i for i in range(1 + K)},
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
            interpret=interpret,
        )(
            jnp.clip(res.uids, -1, C - 1).astype(jnp.int32),
            res.inverse.reshape(-1).astype(jnp.int32),
            mask.reshape(-1).astype(jnp.int32),
            step.reshape(1),
            lr.reshape(1),
            gs,
            res.counts.reshape(U, 1),
            bits_in,
            values,
            *[slots[n] for n in snames],
        )
    return outs[0], {snames[k]: outs[1 + k] for k in range(K)}
