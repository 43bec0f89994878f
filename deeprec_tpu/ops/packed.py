"""Packed small-dim storage layout: ride the fused DMA kernels at dim < 128.

Why: every Pallas kernel in ops/fused_lookup.py needs rows that fill a
128-lane HBM granule, but the flagship DLRM/Criteo tables are dim 16 — and
worse, XLA pads a [C, 16] f32 array's minor dim to 128 lanes on TPU, so a
small-dim table wastes 8x HBM *and* 8x gather bandwidth. The reference's
CUDA group/fused lookups cover small dims as a matter of course
(/root/reference/tensorflow/core/kernels/group_embedding/
group_embedding_lookup_sparse_forward_base_ops.cu.h); the TPU answer is a
layout change, not a new kernel:

  * store P = 128 // dim logical rows per 128-lane granule — the physical
    array is [C // P, P * dim], exactly a row-major reshape, so host-side
    unpack is a free numpy view and the checkpoint format (compacted
    LOGICAL rows) is unchanged;
  * gather = granule gather (the already-measured f32 row / bf16 pair DMA
    kernels apply verbatim, the packed array IS a dim-128 table) + a cheap
    XLA sub-row select on the batch-sized result;
  * scatter = merge updates granule-wise in XLA (unique granules -> patch
    + mask), then read-modify-write whole granules through apply_rows_sr.
    bf16 merge is safe because stochastic rounding of an exactly-
    representable bf16 value is the identity (its low 16 mantissa bits are
    zero, so no carry can reach the kept bits) — untouched lanes round
    through unchanged.

Every helper here is layout-polymorphic: the pack factor is derived from
the array shape (P = capacity // arr.shape[0]), so P == 1 arrays take the
original unpacked path and callers never branch.
"""
from __future__ import annotations

import jax.numpy as jnp

from deeprec_tpu.ops import fused_lookup as _fl
from deeprec_tpu.utils import scopes

LANES = 128


def pack_factor(width: int, capacity: int) -> int:
    """Rows per 128-lane granule for a [capacity, width] per-row array;
    1 when packing does not apply (width already lane-sized, width does
    not divide 128, or capacity not a granule multiple)."""
    if width <= 0 or width >= LANES or LANES % width:
        return 1
    p = LANES // width
    if capacity % p:
        return 1
    return p


def row_factor(arr, capacity: int) -> int:
    """Recover the pack factor of a possibly-packed per-row array from its
    shape (shapes are static under jit, so this is a python int)."""
    rows = arr.shape[-2] if arr.ndim >= 2 else arr.shape[0]
    if rows and capacity % rows == 0:
        return capacity // rows
    return 1


def is_unpacked(arr, capacity: int) -> bool:
    """True when `arr` stores one logical row per physical row — the layout
    the fused-step kernels (ops/fused_lookup.fused_sparse_*) require, since
    their per-row DMAs address whole logical rows."""
    return row_factor(arr, capacity) == 1


def pack_array(arr: jnp.ndarray, p: int) -> jnp.ndarray:
    """[C, w] -> [C // p, p * w] (row-major; a relayout copy on device,
    a free view on host numpy)."""
    if p == 1:
        return arr
    c, w = arr.shape
    return arr.reshape(c // p, p * w)


def unpack_array(arr, capacity: int):
    """Inverse of pack_array: [C // p, p * w] -> [C, w]. Works on jnp and
    numpy arrays (numpy: zero-copy view). No-op for unpacked arrays."""
    return arr.reshape(capacity, -1)


def _read_rows(arr, ix, use_pallas, pair_kernels, interpret):
    """arr[ix] (clip) through the configured kernel, under `rows_gather`:
    whatever a trace shows there that is not the row kernel is wrapper (the
    indices' reshape to one scalar prefetch; before the kernels batched
    themselves over tables, the loop jax built round a vmapped kernel, its
    table-sized slices and fills)."""
    with scopes.scope(scopes.ROWS_GATHER):
        if use_pallas:
            return _fl.gather_rows(arr, ix, pair_kernels=pair_kernels,
                                   interpret=interpret)
        return arr.at[ix].get(mode="clip")


def gather_rows_any(arr: jnp.ndarray, ix: jnp.ndarray, capacity: int, *,
                    use_pallas: bool = False, pair_kernels: bool = False,
                    interpret: bool = False) -> jnp.ndarray:
    """values[ix] with clip semantics for a possibly-packed per-row array.

    Packed arrays DMA one granule per lookup (minimum possible HBM
    traffic — the hardware reads 128 lanes regardless) and select the
    sub-row in XLA on the [n, 128] result.
    """
    p = row_factor(arr, capacity)
    if p == 1:
        return _read_rows(arr, ix, use_pallas, pair_kernels, interpret)
    ix = jnp.clip(ix.astype(jnp.int32), 0, capacity - 1)
    gran = _read_rows(arr, ix // p, use_pallas, pair_kernels, interpret)
    n = ix.shape[0]
    w = arr.shape[1] // p
    sub = gran.reshape(n, p, w)
    return jnp.take_along_axis(sub, (ix % p)[:, None, None], axis=1).reshape(
        n, w
    )


def scatter_rows_any(arr: jnp.ndarray, slot_ix: jnp.ndarray,
                     rows: jnp.ndarray, capacity: int,
                     seed: jnp.ndarray | int = 0, *,
                     use_pallas: bool = False, pair_kernels: bool = False,
                     interpret: bool = False) -> jnp.ndarray:
    """Write rows [U, w] at logical slot_ix [U] (< 0 = skip) into a
    possibly-packed per-row array; bf16 targets stochastic-round.

    Caller contract (same as apply_rows_sr): slot indices are unique among
    the valid entries — two updates to one logical row would race. Packed
    arrays merge the updates granule-wise first (distinct rows of one
    granule occupy disjoint lanes, so the merge scatter cannot collide),
    then RMW whole granules; untouched lanes pass through SR unchanged
    (exactly-representable values round to themselves).

    The row write itself stands under the `rows_scatter` scope (and the
    packed layout's read of the old granules under `rows_gather`).
    """
    p = row_factor(arr, capacity)
    rows = rows.astype(jnp.float32)
    slot_ix = slot_ix.astype(jnp.int32)
    seed = jnp.asarray(seed, jnp.int32)
    if p == 1:
        with scopes.scope(scopes.ROWS_SCATTER):
            return _fl.apply_rows_sr(arr, slot_ix, rows, seed,
                                     use_pallas=use_pallas,
                                     pair_kernels=pair_kernels,
                                     interpret=interpret)
    u, w = rows.shape
    ok = slot_ix >= 0
    g = jnp.where(ok, slot_ix // p, -1)
    r = jnp.where(ok, slot_ix % p, 0)
    # Merge in unique-granule space: invalid updates share the -1 entry
    # (dropped at scatter time), valid ones land at distinct (granule,
    # sub-row) coordinates.
    ug, inv = jnp.unique(g, size=u, fill_value=-1, return_inverse=True)
    patch = jnp.zeros((u, p, w), jnp.float32).at[inv, r].set(rows)
    mask = jnp.zeros((u, p), bool).at[inv, r].set(ok)
    # Old granule contents ride the same DMA gather the lookup path uses.
    gran = _read_rows(arr, jnp.clip(ug, 0, arr.shape[0] - 1), use_pallas,
                      pair_kernels, interpret)
    merged = jnp.where(
        mask[:, :, None], patch, gran.reshape(u, p, w).astype(jnp.float32)
    ).reshape(u, p * w)
    with scopes.scope(scopes.ROWS_SCATTER):
        return _fl.apply_rows_sr(arr, jnp.where(ug >= 0, ug, -1), merged,
                                 seed, use_pallas=use_pallas,
                                 pair_kernels=pair_kernels,
                                 interpret=interpret)
