"""Hash-embedding table: the TPU-native EmbeddingVariable.

DeepRec's EmbeddingVariable (/root/reference/tensorflow/core/framework/embedding/
embedding_var.h:53) is a C++ resource wrapping a lockless hash map, a filter
policy and tiered storage; its hot loop is per-key pointer chasing
(kv_variable_lookup_ops.cc:255-306). That design cannot map onto XLA's
static-shape, functional world — so this is a redesign, not a port:

  * The table IS a pytree of dense arrays living in HBM: `keys [C]`,
    `values [C, D]`, `freq [C]`, `version [C]`, plus optimizer slot arrays.
    C is a fixed power-of-two capacity; growth is a host-orchestrated rehash
    into a larger table (recompiles once per capacity).
  * Lookup-or-create is a *vectorized* open-addressing probe: every pending id
    gathers its candidate slot, matches or claims empty slots via batched
    scatter, losers of a claim race advance to the next probe offset. The loop
    is a `lax.while_loop` of pure gathers/scatters — no per-key host loop,
    everything lands on the VPU.
  * Admission filters, frequency/version tracking and initialization are
    masked vector updates on the same arrays.
  * Eviction rebuilds the table (rare, checkpoint-time), which also heals
    probe chains — no tombstones on the hot path.

All ops are pure: they take a TableState and return a new one; XLA's buffer
donation makes the updates in-place in practice.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as _np
from flax import struct

from deeprec_tpu.config import TableConfig
from deeprec_tpu.utils import backend, hashing, scopes


def _key_dtype(cfg: TableConfig):
    return jnp.dtype(cfg.key_dtype)


def empty_key(cfg: TableConfig) -> int:
    """Reserved sentinel marking a free slot (min value of the key dtype)."""
    return int(jnp.iinfo(_key_dtype(cfg)).min)


# The ids the probe's find loop settles at a time: a pass holds one window
# of keys an id ([ids, 128], 512 bytes an id of int32 keys), so more ids
# than this walk the loop in slices of this many (`_probe`). A train
# step's unique ids (2.3k / 8.2k a table in the benchmark's cells) are one
# slice; the maintenance callers, which probe a whole table's slots at once
# (rebuild, and through it evict, grow, maintain; a restore), are many: a
# window of [2^18, 128] keys a table would be 128 MiB a table, and its 2^18
# row indices more than the row kernel's SMEM holds. 2^14 ids are 8 MiB a
# table and 64 KiB of indices. Nothing sets it.
_PROBE_SLICE = 1 << 14


def _note_probe_window(table: str, width: int, passes: int) -> None:
    """What a table's find loop was built with, at TRACE time (the key
    array's shape and `max_probes` are static), as
    deeprec_probe_window{table,width,max_passes} 1: a gauge, so noting it
    again on a retrace changes nothing (fused_lookup._note_schedule)."""
    from deeprec_tpu.obs.metrics import default_registry

    default_registry().gauge(
        "deeprec_probe_window",
        help="Slots the probe's find loop reads an id a pass, and the "
             "passes that cover max_probes",
        labels={"table": table, "width": str(width),
                "max_passes": str(passes)},
    ).set(1)


# Row indices of the packed per-slot metadata leaf (TableState.meta, [3, C]):
# freq / version / dirty live in ONE int32 array so the train hot path
# updates all three with a single fused scatter instead of three. The layout
# is [3, C] (columns minor) — a [C, 3] layout would lane-pad 3 -> 128 on TPU
# and waste ~42x HBM; with C minor the array tiles like any other big row.
META_FREQ = 0
META_VERSION = 1
META_DIRTY = 2

# Per-column fill values for a fresh/vacated slot: freq 0, version -1
# (never touched), dirty 0.
_META_FILL = (0, -1, 0)


def empty_meta(capacity: int) -> jnp.ndarray:
    """[3, C] metadata array of an empty table."""
    return jnp.tile(
        jnp.asarray(_META_FILL, jnp.int32)[:, None], (1, capacity)
    )


# int8 residency quantization range: symmetric, -127..127 (the -128 code is
# unused so negation is exact and the scale maps max|row| onto the top code).
QMAX = 127.0


def quantize_rows_int8(rows: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row symmetric int8 quantization for the serving residency:
    returns (q, scale) with rows ≈ q * scale[:, None]. `q` is
    integer-valued float32 in [-127, 127] (scatter_rows_any casts to the
    table's int8 on the way in — exact for integer values), `scale` [U]
    float32 = max|row| / 127, 0 for all-zero rows (which decode to 0)."""
    rows = jnp.asarray(rows, jnp.float32)
    amax = jnp.max(jnp.abs(rows), axis=-1)
    scale = amax / QMAX
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(rows * inv[..., None]), -QMAX, QMAX)
    return q, scale


@struct.dataclass
class TableState:
    """Device-resident state of one table (a pytree; donate it through jit).

    Scan-carry contract (Trainer.train_steps runs K steps in one
    `lax.scan`, threading every TableState through the carry): all leaves
    keep a FIXED shape and dtype across a step — lookups/applies/admission
    return arrays of the same aval, and the transient counters
    (insert_fails, a2a_overflow) accumulate as int32 scalars, never
    promote. Anything host-shaped (growth, eviction rebuilds to a new
    capacity, multi-tier sync) stays OUTSIDE the scan, at K-step
    boundaries — it changes leaf shapes, which a scan carry cannot."""

    keys: jnp.ndarray  # [C] key_dtype, empty slots hold the sentinel
    values: jnp.ndarray  # [C, D] value_dtype
    # [3, C] int32 — fused per-slot metadata, rows META_FREQ / META_VERSION /
    # META_DIRTY (lookup counter for admission + LFU tiering; global step of
    # last touch for TTL evict; touched-since-last-incremental-save flag).
    # One leaf so the train hot path reads and writes all three with a
    # single gather + a single scatter; the named `freq`/`version`/`dirty`
    # properties below keep every metadata READER (eviction, filters,
    # multi-tier, checkpoint, maintain) on the columnar view, and
    # `replace_meta` is the columnar WRITE entry point for cold paths.
    meta: jnp.ndarray
    slots: Dict[str, jnp.ndarray]  # optimizer slot arrays, [C, D] or [C, 1]
    bloom: Optional[jnp.ndarray]  # [M] int32 counting-Bloom sketch (CBF filter)
    insert_fails: jnp.ndarray  # [] int32 — ids that found no slot (grow signal)
    # [] int32 — ids past the all2all per-destination budget (the knob is
    # a2a_slack, NOT capacity — kept separate from insert_fails). Transient;
    # not checkpointed, resets on rebuild.
    a2a_overflow: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((), jnp.int32)
    )
    # Dedup-engine telemetry (ops/dedup.py), train lookups only. Same
    # transient contract as the counters above: int32 scalars accumulating
    # inside the K-step scan, not checkpointed, reset on rebuild and by
    # Trainer.update_budgets (which folds them into the auto-budget EMA).
    #   dedup_overflow — distinct ids compacted out past the unique budget
    #                    (served the blocked default that step)
    #   dedup_unique   — accumulated budgeted unique ids seen
    #   dedup_ids      — accumulated non-pad id positions those covered
    dedup_overflow: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((), jnp.int32)
    )
    dedup_unique: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((), jnp.int32)
    )
    dedup_ids: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((), jnp.int32)
    )
    # Owner-side exchange-load telemetry (sharded train lookups only —
    # ShardedTable.resolve; single-device tables never move these). Same
    # transient int32-scalar contract as the dedup counters; reset by
    # Trainer.update_budgets. Per mesh position (the leading shard axis of
    # a sharded TrainState), these expose the exchange imbalance the
    # placement plan (parallel/placement.py) flattens:
    #   owner_arrivals — exchanged rows this shard owned/served (a key
    #                    present on k source shards counts k)
    #   owner_unique   — distinct keys those arrivals deduped to
    owner_arrivals: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((), jnp.int32)
    )
    owner_unique: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((), jnp.int32)
    )
    # [C] float32 per-row dequantization scale — present ONLY on int8
    # serving-residency tables (cfg.value_dtype == "int8"): a stored row
    # decodes as values[i].astype(f32) * qscale[i]. None everywhere else
    # (None is an empty pytree node, so fp32/bf16 tables are structurally
    # unchanged). Written by the checkpoint import (quantize-on-import)
    # and read by the lookup gathers; rebuild relocates it like any other
    # per-row array.
    qscale: Optional[jnp.ndarray] = None

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def dim(self) -> int:
        """Logical embedding dim. Robust to the packed small-dim layout
        (values [C // P, P * D] — ops/packed.py): D * rows stays C * dim."""
        return self.values.shape[-1] * self.values.shape[-2] // self.keys.shape[-1]

    # Columnar views of the fused metadata leaf. Leading (table-group /
    # shard) axes pass through untouched — meta is [..., 3, C], the views
    # are [..., C], the same shapes the three separate leaves had.

    @property
    def freq(self) -> jnp.ndarray:
        return self.meta[..., META_FREQ, :]

    @property
    def version(self) -> jnp.ndarray:
        return self.meta[..., META_VERSION, :]

    @property
    def dirty(self) -> jnp.ndarray:
        return self.meta[..., META_DIRTY, :] != 0

    def replace_meta(self, freq=None, version=None, dirty=None) -> "TableState":
        """Columnar metadata write for cold paths (restore, tier sync,
        tests): rebuild the packed leaf from whole replacement columns.
        The hot path never comes here — it scatters fused [3]-rows."""
        meta = self.meta
        if freq is not None:
            meta = meta.at[..., META_FREQ, :].set(
                jnp.asarray(freq, jnp.int32))
        if version is not None:
            meta = meta.at[..., META_VERSION, :].set(
                jnp.asarray(version, jnp.int32))
        if dirty is not None:
            meta = meta.at[..., META_DIRTY, :].set(
                jnp.asarray(dirty, jnp.int32))
        return self.replace(meta=meta)


@struct.dataclass
class UniqueLookup:
    """Result of a deduplicated lookup, the unit the grad path works on."""

    uids: jnp.ndarray  # [U] unique ids (sentinel-padded)
    slot_ix: jnp.ndarray  # [U] int32 slot index, -1 when absent/blocked
    inverse: jnp.ndarray  # [N] position -> index into uids
    counts: jnp.ndarray  # [U] int32 occurrences in this batch
    valid: jnp.ndarray  # [U] bool — real id (not padding)
    admitted: jnp.ndarray  # [U] bool — passes the admission filter
    embeddings: jnp.ndarray  # [U, D] gathered values (default where blocked)
    # [U, D] forward RESIDUAL: the raw (unmasked, pre-admission) value rows
    # gathered at safe_ix during the lookup. `embeddings` is a masked view
    # of these rows; `apply_gradients` reuses them in place of its own
    # value re-gather (the rows cannot go stale between a train lookup and
    # its same-step apply — inserts only claim empty slots). Empty ([0])
    # signals "no residual carried" and the apply falls back to a gather.
    rows: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((0,), jnp.float32)
    )


class EmbeddingTable:
    """Pure-function API around TableState for one TableConfig.

    The public surface mirrors what tf.get_embedding_variable +
    tf.nn.embedding_lookup deliver in DeepRec (variable_scope.py:2146,
    embedding_ops.py:365), re-cut for functional SPMD training.
    """

    def __init__(self, cfg: TableConfig):
        self.cfg = cfg

    @property
    def quantized(self) -> bool:
        """int8 serving residency: rows store int8 + per-row fp32 scale
        (TableState.qscale) and every lookup gather dequantizes. Serving
        only — train-mode lookups raise (train fp32, serve quantized)."""
        return self.cfg.value_dtype == "int8"

    def _dequant(self, emb: jnp.ndarray, safe_ix: jnp.ndarray,
                 state: TableState) -> jnp.ndarray:
        """Decode gathered int8 rows: one [U] scale gather + a broadcast
        multiply — the whole dequantization cost of the serving path."""
        scale = state.qscale.at[safe_ix].get(mode="clip")
        return emb.astype(jnp.float32) * scale[:, None]

    @property
    def use_pallas(self) -> bool:
        """Fused Pallas kernels for the row gather/scatter hot path.
        "auto" resolves to pallas where the measured-winners flag says the
        bench crowned it: tools/bench_lookup.py on v5e measured the DMA
        kernels ahead wherever they're eligible (dim%128==0, f32 tables:
        gather 494 vs 362 GB/s, scatter 1117 vs 726 — docs/perf.md), and
        the ops self-gate back to XLA for ineligible shapes/backends, so
        "auto" is always the measured winner (AUTO_TRUSTS_F32_ROW flips
        it off if a re-bench ever disagrees)."""
        from deeprec_tpu.ops.fused_lookup import AUTO_TRUSTS_F32_ROW

        return self.cfg.kernel == "pallas" or (
            self.cfg.kernel == "auto" and AUTO_TRUSTS_F32_ROW
        )

    @property
    def pair_kernels(self) -> bool:
        """bf16 pair-granule kernels (gather + in-kernel-SR scatter): on
        for explicit kernel="pallas"; "auto" keeps XLA for bf16 until a
        hardware bench crowns the pair kernels (AUTO_TRUSTS_BF16_PAIR —
        the measured-winners policy)."""
        from deeprec_tpu.ops.fused_lookup import AUTO_TRUSTS_BF16_PAIR

        return self.cfg.kernel == "pallas" or (
            self.cfg.kernel == "auto" and AUTO_TRUSTS_BF16_PAIR
        )

    @property
    def fused_step(self) -> bool:
        """Single-pass fused step kernels (fused_sparse_forward /
        fused_sparse_backward: dedup-probe + gather + combine forward,
        segment-sum + optimizer + scatter backward): on for explicit
        kernel="pallas"; "auto" keeps the split-phase path until a
        hardware bench crowns them (AUTO_TRUSTS_FUSED_STEP — the same
        measured-winners policy as the pair kernels)."""
        from deeprec_tpu.ops.fused_lookup import AUTO_TRUSTS_FUSED_STEP

        return self.cfg.kernel == "pallas" or (
            self.cfg.kernel == "auto" and AUTO_TRUSTS_FUSED_STEP
        )

    def bag_forward(self, state: TableState, row_ix: jnp.ndarray, *,
                    combiner: str = "mean", unique_size: int,
                    interpret: bool = False):
        """Single-pass bag lookup over RESOLVED slot indices [B, L]
        (< 0 = pad): hash-probe dedup + unique-row gather + segment
        combine in one fused op (ops/fused_lookup.fused_sparse_forward),
        dispatched through the same kernel= gate as the row kernels.
        Returns a FusedBags; pair it with optim.apply.apply_bag_gradients
        for the fused backward. Packed small-dim layouts keep the
        split-phase lookup — the fused kernels address whole logical
        rows."""
        from deeprec_tpu.ops import fused_lookup as fl
        from deeprec_tpu.ops.packed import is_unpacked

        if not is_unpacked(state.values, state.capacity):
            raise NotImplementedError(
                "bag_forward: packed small-dim layouts keep the "
                "split-phase lookup (the fused step kernels address "
                "whole logical rows)"
            )
        return fl.fused_sparse_forward(
            state.values, row_ix, combiner=combiner,
            unique_size=unique_size, interpret=interpret,
            use_pallas=self.fused_step,
        )

    def pack_width(self, width: int, capacity: Optional[int] = None) -> int:
        """Pack factor for a [C, width] per-row array under this table's
        layout policy. cfg.packed="auto" packs only where the layout can
        win — TPU, where XLA pads the minor dim to 128 lanes; on CPU there
        is no padding and packing measured -36% (docs/perf.md), so auto
        resolves to unpacked. "on"/"off" force it either way."""
        mode = self.cfg.packed
        if mode == "off" or (mode == "auto" and not backend.on_tpu()):
            return 1
        from deeprec_tpu.ops.packed import pack_factor

        return pack_factor(width,
                           self.cfg.capacity if capacity is None else capacity)

    def pack(self, capacity: Optional[int] = None) -> int:
        """Pack factor for the values array at this capacity (ops/packed.py:
        P rows per 128-lane granule when dim < 128 divides 128). Packing is
        a storage-layout decision independent of the kernel choice — it
        saves P x HBM (XLA pads the minor dim to 128 lanes) and makes the
        table eligible for the fused DMA kernels at any kernel= setting.
        Gated per-backend by cfg.packed (see pack_width)."""
        return self.pack_width(self.cfg.dim, capacity)

    def _gather(self, values: jnp.ndarray, ix: jnp.ndarray,
                capacity: int) -> jnp.ndarray:
        """values[ix] with clip semantics through the configured kernel,
        packed-layout aware."""
        from deeprec_tpu.ops.packed import gather_rows_any

        return gather_rows_any(
            values, ix, capacity,
            use_pallas=self.use_pallas, pair_kernels=self.pair_kernels,
        )

    def _key_window(self, key_rows: jnp.ndarray,
                    row_ix: jnp.ndarray) -> jnp.ndarray:
        """key_rows[row_ix] for the probe's find loop: the key array as
        [C / W, W] rows, one row an id, [U, W]. A negative row index is an
        id that reads nothing (the caller masks that row out). Rows of 128
        int32 keys ride the row kernel like a value row (it folds the table
        vmap and splits a call over table ranges by itself). Whether they
        do is decided HERE, so that a key read the kernel cannot take is no
        fallback of the value gather's on /metrics: int64 keys, a table
        under one lane tile and kernel="xla" take XLA's row gather (as
        every backend but the TPU does, inside `gather_rows`)."""
        from deeprec_tpu.ops import fused_lookup

        with scopes.scope(scopes.ROWS_GATHER):
            if self.use_pallas and fused_lookup._dma_ok(
                    key_rows.shape[1], key_rows.dtype):
                return fused_lookup.gather_rows(key_rows, row_ix,
                                                skip_negative=True)
            return key_rows.at[row_ix].get(mode="clip")

    def _scatter(self, values: jnp.ndarray, slot_ix: jnp.ndarray,
                 rows: jnp.ndarray, capacity: int,
                 seed: jnp.ndarray | int = 0) -> jnp.ndarray:
        """Write rows at logical slot_ix (< 0 = skip) through the configured
        kernel, packed-layout aware; bf16 tables stochastic-round."""
        from deeprec_tpu.ops.packed import scatter_rows_any

        return scatter_rows_any(
            values, slot_ix, rows, capacity, seed,
            use_pallas=self.use_pallas, pair_kernels=self.pair_kernels,
        )

    # Hashable-by-config so EmbeddingTable can ride through jit as a static
    # argument (the jitted public methods below rely on this).
    def __hash__(self):
        return hash(self.cfg)

    def __eq__(self, other):
        return isinstance(other, EmbeddingTable) and self.cfg == other.cfg

    # ------------------------------------------------------------------ state

    def create(self) -> TableState:
        cfg = self.cfg
        C, D = cfg.capacity, cfg.dim
        kdt = _key_dtype(cfg)
        vdt = jnp.dtype(cfg.value_dtype)
        bloom = None
        if cfg.ev.cbf_filter is not None:
            bloom = jnp.zeros((cfg.ev.cbf_filter.num_cells(),), jnp.int32)
        P = self.pack()
        return TableState(
            keys=jnp.full((C,), empty_key(cfg), kdt),
            values=jnp.zeros((C // P, P * D), vdt),
            meta=empty_meta(C),
            slots={},
            bloom=bloom,
            insert_fails=jnp.zeros((), jnp.int32),
            qscale=(
                jnp.zeros((C,), jnp.float32) if self.quantized else None
            ),
        )

    # ------------------------------------------------------------- initializer

    def default_salt(self) -> int:
        return hashing.name_salt(self.cfg.name)

    def _init_rows(self, uids: jnp.ndarray, salt=None) -> jnp.ndarray:
        """Initializer values for newly created keys — a pure function of
        (key, table salt), so creation is reproducible anywhere (EV
        Initializer semantics, docs/docs_en/Embedding-Variable.md). Grouped
        tables pass a traced per-table salt through vmap."""
        cfg = self.cfg
        init = cfg.ev.init
        D = cfg.dim
        # Quantized tables serve missing-key defaults at full precision:
        # the initializer row never lives in the int8 residency, it is
        # computed fresh per lookup, so there is nothing to dequantize.
        vdt = jnp.float32 if self.quantized else jnp.dtype(cfg.value_dtype)
        if salt is None:
            salt = self.default_salt()
        if init.kind == "constant":
            return jnp.full((uids.shape[0], D), init.constant, vdt)
        if init.kind == "matrix_normal":
            # DeepRec: row (key % default_value_dim) of a fixed normal matrix.
            # The matrix itself is regenerated from the salt, not stored.
            dvd = init.default_value_dim
            rows = (uids.astype(jnp.uint32) % jnp.uint32(dvd)).astype(jnp.int32)
            u = hashing.stateless_uniform_from_ids(
                rows[:, None] * jnp.int32(D)
                + jax.lax.broadcasted_iota(jnp.int32, (1, D), 1),
                salt=jnp.asarray(salt).astype(jnp.uint32) ^ jnp.uint32(0x5EED),
            )
            return self._uniform_to_normal(u).astype(vdt)
        # stateless_normal: per-key deterministic normal from the id hash.
        u = hashing.stateless_uniform_from_ids(
            uids[:, None] * jnp.int32(max(D, 1))
            + jax.lax.broadcasted_iota(jnp.int32, (1, D), 1),
            salt=salt,
        )
        return self._uniform_to_normal(u).astype(vdt)

    def _uniform_to_normal(self, u: jnp.ndarray) -> jnp.ndarray:
        init = self.cfg.ev.init
        # inverse-CDF approximation via erfinv: N(mean, stddev)
        eps = 1e-6
        z = jnp.sqrt(2.0) * jax.scipy.special.erfinv(
            jnp.clip(2.0 * u - 1.0, -1.0 + eps, 1.0 - eps)
        )
        return init.mean + init.stddev * z

    # ------------------------------------------------------------ probe/insert

    @scopes.scoped(scopes.ENGINE_PROBE)
    def _probe(
        self,
        keys: jnp.ndarray,
        uids: jnp.ndarray,
        want_create: jnp.ndarray,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Vectorized open-addressing lookup-or-create: find, then claim.

        Args:
          keys: [C] current key array.
          uids: [U] unique ids to resolve.
          want_create: [U] bool — ids allowed to claim an empty slot.

        Returns: (new_keys, slot_ix [U] (-1 = not found/placed), created [U],
        failed [U]).

        Two loops. The FIND loop writes nothing and carries [U]-sized
        arrays only (under the table vmap its per-pass selects are [T, U],
        never [T, C]). It sees the key array as rows of W = min(128, C)
        consecutive slots and reads, a pass, ONE such row an id: the
        aligned window that holds the next slots of the id's chain (pass p
        reads row (home // W + p) mod C / W, so the wrap at the table's end
        is the row index's). A row of 128 int32 keys is 512 bytes, what the
        row kernel moves for the price of one scalar gather
        (`_key_window`), and it holds the rest of most chains. Lane j of
        that row is probe offset p * W + j - home % W; of the lanes whose
        offset lies in [0, max_probes) the FIRST that holds the id's own
        key or no key settles the id: found there, or absent with its
        chain's first empty slot there. That settles residency exactly:
        linear probing without tombstones (rebuild re-hashes) keeps a
        resident key before the first empty slot of its chain, and uids are
        unique within a call, so nothing another id claims later can be
        this id's key. The passes end when no id is pending or the offsets
        are spent: ceil((W - 1 + max_probes) / W) of them at most, two at
        the defaults, whatever the longest chain. More than _PROBE_SLICE
        ids (a whole table's slots: rebuild, a restore) walk the loop a
        slice at a time, so the window a pass holds is bounded by the
        slice and not by the table. The CLAIM loop starts
        every absent, creatable id at the empty slot the find loop left it
        at and races them (scatter, re-gather, the winner keeps the slot,
        losers move on); with nothing to create it runs no pass.
        `max_probes` bounds the slots an id sees over both loops together.
        """
        from deeprec_tpu.ops.fused_lookup import _LANES

        cfg = self.cfg
        C, U = keys.shape[0], uids.shape[0]
        W = min(_LANES, C)
        rows = C // W
        passes = -(-(W - 1 + cfg.max_probes) // W)
        _note_probe_window(cfg.name, W, passes)
        mask_c = jnp.uint32(C - 1)
        h = hashing.mix32(hashing.fold64(uids))
        sentinel = jnp.asarray(empty_key(cfg), keys.dtype)
        key_rows = keys.reshape(rows, W)
        lane = jnp.arange(W, dtype=jnp.int32)

        def position(off):
            return ((h + off.astype(jnp.uint32)) & mask_c).astype(jnp.int32)

        def find(uids, home):
            """The find loop over one slice of the ids (their home slots
            beside them): (unresolved, slot_ix, empty_at) of the slice."""
            # W is a power of two, as C is
            home_row, home_lane = home >> (W.bit_length() - 1), home & (W - 1)

            def find_cond(carry):
                step, pending, *_ = carry
                return jnp.logical_and(step < passes, jnp.any(pending))

            def find_body(carry):
                step, pending, slot_ix, empty_at = carry
                row = (home_row + step) & (rows - 1)  # [U]
                # a settled id reads nothing (and its lanes do not count)
                window = self._key_window(
                    key_rows, jnp.where(pending, row, -1))
                off = step * W + lane - home_lane[:, None]  # [U, W]
                counts = pending[:, None] & (off >= 0) & (
                    off < cfg.max_probes)
                own = window == uids[:, None]
                settles = counts & (own | (window == sentinel))
                # the first lane that settles the id and which of the two
                # its key is, in one lane reduction: 2 * lane + (0 own,
                # 1 empty)
                first = jnp.min(
                    jnp.where(settles, 2 * lane + (~own).astype(jnp.int32),
                              2 * W), axis=1)
                settled, at = first < 2 * W, first >> 1
                found = settled & ((first & 1) == 0)
                slot_ix = jnp.where(found, row * W + at, slot_ix)
                # the first empty slot of the chain: the key is
                # definitively absent, and this is where a creatable id
                # starts its claim
                empty_at = jnp.where(
                    settled & ~found, step * W + at - home_lane, empty_at)
                return step + 1, pending & ~settled, slot_ix, empty_at

            none = jnp.full(uids.shape, -1, jnp.int32)
            return jax.lax.while_loop(
                find_cond, find_body,
                (jnp.int32(0), uids != sentinel, none, none))[1:]

        def claim_cond(carry):
            pending, *_ = carry
            return jnp.any(pending)

        def claim_body(carry):
            pending, off, slot_ix, keys = carry
            pos = position(off)
            want = pending & (keys[pos] == sentinel)
            # Claim race: scatter all claimants; duplicates resolve to one
            # winner, which the re-gather below reveals. Losers keep probing.
            claim_pos = jnp.where(want, pos, C)  # C = out of bounds -> dropped
            keys = keys.at[claim_pos].set(uids, mode="drop")
            won = want & (keys[pos] == uids)
            slot_ix = jnp.where(won, pos, slot_ix)
            off = off + 1
            pending = pending & ~won & (off < cfg.max_probes)
            return pending, off, slot_ix, keys

        home = (h & mask_c).astype(jnp.int32)
        with scopes.scope(scopes.PROBE_FIND):
            if U <= _PROBE_SLICE:
                unresolved, slot_ix, empty_at = find(uids, home)
            else:  # slices of the ids, one after another, the last padded
                pad = -U % _PROBE_SLICE
                unresolved, slot_ix, empty_at = (
                    x.reshape(-1)[:U] for x in jax.lax.map(
                        lambda xs: find(*xs), (
                            jnp.pad(uids, (0, pad), constant_values=sentinel
                                    ).reshape(-1, _PROBE_SLICE),
                            jnp.pad(home, (0, pad)
                                    ).reshape(-1, _PROBE_SLICE))))
        with scopes.scope(scopes.PROBE_CLAIM):
            # absent and not creatable: given up (slot_ix -1, not failed)
            claiming = (empty_at >= 0) & want_create
            _, _, slot_ix, keys = jax.lax.while_loop(
                claim_cond, claim_body, (claiming, empty_at, slot_ix, keys)
            )
        created = claiming & (slot_ix >= 0)
        # ran out of probes, finding or claiming: table (region) is full
        failed = unresolved | (claiming & ~created)
        return keys, slot_ix, created, failed

    # ----------------------------------------------------------------- lookup

    def default_unique_size(self, n: int) -> Optional[int]:
        """Resolve cfg.unique_budget for an n-position flattened TRAIN
        lookup: the uids-array size for the budgeted dedup, or None for
        the legacy U = N sort-unique (logged once per table so the waste
        is visible — None/"auto" configs; "off" stays silent). Trainers
        override this resolution with their own (EMA-driven) budgets.
        Eval/serving lookups never budget by default: resident keys must
        read exactly, and read-only state makes overflow invisible to the
        counters (callers may still force a size explicitly)."""
        from deeprec_tpu.ops import dedup

        ub = self.cfg.unique_budget
        if isinstance(ub, int) and not isinstance(ub, bool):
            return dedup.resolve_size(ub, n)
        if ub != "off":  # None or "auto": visible fallback
            dedup.log_full_fallback(self.cfg.name, n)
        return None

    def lookup_unique(
        self,
        state: TableState,
        ids: jnp.ndarray,
        *,
        step: jnp.ndarray | int = 0,
        train: bool = True,
        pad_value: int = -1,
        unique_size: Optional[int] = None,
    ) -> Tuple[TableState, UniqueLookup]:
        if unique_size is None and train:
            unique_size = self.default_unique_size(
                int(_np.prod(ids.shape)) if ids.ndim else 1
            )
        return _lookup_unique_jit(
            self, state, ids, jnp.asarray(step, jnp.int32), train, pad_value,
            unique_size,
        )

    def _route_ids(
        self, ids: jnp.ndarray, pad_value: int,
        unique_size: Optional[int],
    ):
        """Routing half of a lookup (ops/dedup.py `route_ids`): flatten +
        pad-collapse + dedup. Pure function of the id batch — no table
        state — so pipelined trainers hoist it a full step ahead."""
        from deeprec_tpu.ops import dedup

        return dedup.route_ids(
            ids, pad_value=pad_value, sentinel=empty_key(self.cfg),
            unique_size=unique_size,
        )

    def _resolve_routed(
        self,
        state: TableState,
        route,
        *,
        step,
        train: bool,
        salt=None,
    ) -> Tuple[TableState, UniqueLookup]:
        """Key/metadata half on a prepared route: probe/insert, metadata
        stamp, init-scatter for created rows, admission, dedup telemetry —
        everything EXCEPT the value-row gather (`_finish_resolved`). The
        returned result carries placeholder (0-sized) embeddings/rows;
        `rows.size == 0` is the documented "not gathered yet" sentinel.

        Hoist contract (the basis of the exact pipelined scan): nothing
        here reads or writes the VALUE rows an apply touches — keys/meta
        are apply-invariant on the diet hot path (stamp_meta=False), and
        the init scatter only lands on slots that were empty at claim
        time, which a concurrent apply (whose rows were all resident at
        its own lookup) cannot overlap. So resolve(t+1) commutes with
        apply(t) bit-exactly.
        """
        uids, inverse, counts, valid, overflow = route
        state, res = self._resolve(
            state, uids, counts, valid, step=step, train=train, salt=salt
        )
        if train:
            # Seed the auto-budget EMA (Trainer.update_budgets) on every
            # path; the overflow counter only moves under a budget.
            with scopes.scope(scopes.ENGINE_ROUTE):  # the dedup's counters
                state = state.replace(
                    dedup_unique=state.dedup_unique
                    + jnp.sum(valid).astype(jnp.int32),
                    dedup_ids=state.dedup_ids + jnp.sum(counts),
                    dedup_overflow=(
                        state.dedup_overflow + overflow
                        if overflow is not None
                        else state.dedup_overflow
                    ),
                )
        return state, dataclasses.replace(res, inverse=inverse)

    def _lookup_unique_impl(
        self,
        state: TableState,
        ids: jnp.ndarray,
        step,
        train: bool,
        pad_value: int,
        unique_size: Optional[int],
        salt=None,
    ) -> Tuple[TableState, UniqueLookup]:
        """Deduplicate ids, resolve/insert them, gather embeddings.

        `ids` may be any shape; padding positions equal to `pad_value` are
        ignored (standard for ragged sparse features). In train mode new keys
        are inserted, frequencies incremented and versions stamped — the
        combined semantics of KvResourceGather + the freq/version bookkeeping
        DeepRec does inside EmbeddingVar::GetEmbeddings/LookupOrCreateKey.

        Dedup routing: `unique_size=None` keeps the legacy sort-based
        `jnp.unique` at U = N; a concrete `unique_size` engages the O(N)
        budgeted dedup (ops/dedup.py) at that static budget — every
        downstream op then runs at U instead of N, ids past the budget
        serve the blocked default and count into `dedup_overflow`.

        Split-phase composition: route (`_route_ids`) → resolve
        (`_resolve_routed`) → finish (`_finish_resolved`) — the pipelined
        trainers call the three phases individually so the value gather
        can land after the previous step's apply while everything else
        hoists ahead of it.
        """
        route = self._route_ids(ids, pad_value, unique_size)
        state, res = self._resolve_routed(
            state, route, step=step, train=train, salt=salt
        )
        return state, self._finish_resolved(state, res)

    def _lookup_resolved(
        self,
        state: TableState,
        uids: jnp.ndarray,
        counts: jnp.ndarray,
        valid: jnp.ndarray,
        *,
        step: jnp.ndarray | int,
        train: bool,
        salt=None,
    ) -> Tuple[TableState, UniqueLookup]:
        """Core lookup on already-unique ids (also the per-shard entry point
        for sharded tables, where dedup happened before the all-to-all):
        resolve (probe/insert/meta/init/admission) + finish (value gather)."""
        state, res = self._resolve(
            state, uids, counts, valid, step=step, train=train, salt=salt
        )
        return state, self._finish_resolved(state, res)

    def _resolve(
        self,
        state: TableState,
        uids: jnp.ndarray,
        counts: jnp.ndarray,
        valid: jnp.ndarray,
        *,
        step: jnp.ndarray | int,
        train: bool,
        salt=None,
    ) -> Tuple[TableState, UniqueLookup]:
        """Key/metadata half of `_lookup_resolved`: probe-or-insert keys,
        fused metadata stamp, initializer scatter for created rows and the
        admission decision — but NOT the value-row gather, which
        `_finish_resolved` performs (the split the pipelined trainers use
        to place the gather after the previous step's apply). Returns the
        updated state and a UniqueLookup whose embeddings/rows are 0-sized
        placeholders.

        In a trace of a train step all of it stands under `engine_insert`
        but the probe loop, which `_probe` puts under `engine_probe` inside
        it (a reader takes the innermost stage): what the step pays for the
        rows it creates and for stamping the rows it touches. A read-only
        resolve creates and stamps nothing; what is left of it (the
        admission's read of the frequencies) is the gather's."""
        cfg = self.cfg
        if train and self.quantized:
            raise ValueError(
                f"table {cfg.name}: int8 residency is serving-only — train "
                "fp32 and restore into a quantized Predictor "
                "(Predictor(quantize='int8'))"
            )
        step = jnp.asarray(step, jnp.int32)
        stage = scopes.ENGINE_INSERT if train else scopes.ENGINE_GATHER
        with scopes.scope(stage):
            bloom = state.bloom
            want_create = valid
            if not train:
                want_create = jnp.zeros_like(valid)
            elif cfg.ev.cbf_filter is not None:
                # CBF admission: bump the sketch, only keys at/above threshold
                # may occupy a table slot (bloom_filter_policy.h semantics).
                from deeprec_tpu.embedding import filters as _filters

                bloom, est = _filters.cbf_add(
                    cfg.ev.cbf_filter, bloom, uids, counts
                )
                want_create = valid & (est >= cfg.ev.cbf_filter.filter_freq)

            keys, slot_ix, created, failed = self._probe(
                state.keys, uids, want_create
            )

            present = slot_ix >= 0
            safe_ix = jnp.where(present, slot_ix, 0)

            need_filter = (
                cfg.ev.counter_filter is not None
                and cfg.ev.counter_filter.filter_freq > 0
            )
            values = state.values
            meta = state.meta
            f_cur = None  # post-update per-uid frequency (admission input)
            if train:
                # Initialize newly created rows (bf16 tables stochastic-round
                # the initializer, same as every later write).
                init_rows = self._init_rows(uids, salt)
                values = self._scatter(
                    values, jnp.where(created, slot_ix, -1), init_rows,
                    state.capacity, seed=step,
                )
                # Fused metadata update: ONE [3, U] gather + ONE [3, U]
                # scatter replace the former freq add / version set / dirty
                # set trio. The gather also feeds the admission filter, whose
                # legacy post-update freq read it subsumes (uids are unique,
                # so each present id owns its slot and set == read-add-write).
                upd_ix = jnp.where(present, slot_ix, state.capacity)
                m_rows = meta.at[:, safe_ix].get(mode="clip")  # [3, U]
                f_cur = m_rows[META_FREQ] + counts
                new_rows = jnp.stack([
                    f_cur,
                    jnp.broadcast_to(step, f_cur.shape).astype(jnp.int32),
                    jnp.ones_like(f_cur),
                ])
                meta = meta.at[:, upd_ix].set(new_rows, mode="drop")
            elif need_filter:
                f_cur = meta[META_FREQ].at[safe_ix].get(mode="clip")

            # Admission: counter filter gates on the (just updated) frequency.
            admitted = present
            if need_filter:
                admitted = present & (f_cur >= cfg.ev.counter_filter.filter_freq)

            new_state = state.replace(
                keys=keys,
                values=values,
                meta=meta,
                bloom=bloom,
                insert_fails=state.insert_fails
                + jnp.sum(failed).astype(jnp.int32),
            )
            res = UniqueLookup(
                uids=uids,
                slot_ix=slot_ix,
                inverse=jnp.zeros((0,), jnp.int32),  # filled by lookup_unique
                counts=counts,
                valid=valid,
                admitted=admitted,
                # Placeholders until _finish_resolved gathers the value rows.
                embeddings=jnp.zeros((0, 0), jnp.float32),
                rows=jnp.zeros((0, 0), jnp.float32),
            )
            return new_state, res

    @scopes.scoped(scopes.ENGINE_GATHER)
    def _finish_resolved(
        self, state: TableState, res: UniqueLookup, keep_rows: bool = True
    ) -> UniqueLookup:
        """Value half of a lookup: gather the resolved rows from
        `state.values` and apply the admission mask. Reads the CURRENT
        values — in the pipelined scan this runs after the previous step's
        apply, which is exactly what keeps the lookahead staleness-free.
        `keep_rows=False` drops the raw-row residual (callers that will
        never reuse it — the stale-by-one apply — avoid carrying a second
        [U, D] buffer across dispatches); `rows.size == 0` stays the
        documented "no residual, re-gather at apply" sentinel."""
        safe_ix = jnp.where(res.slot_ix >= 0, res.slot_ix, 0)
        emb = self._gather(state.values, safe_ix, state.capacity)
        if self.quantized:
            emb = self._dequant(emb, safe_ix, state)
        blocked_default = jnp.asarray(
            self.cfg.ev.init.default_value_no_permission, emb.dtype
        )
        masked = jnp.where(res.admitted[:, None], emb, blocked_default)
        rows = emb if keep_rows else jnp.zeros((0, 0), jnp.float32)
        return dataclasses.replace(res, embeddings=masked, rows=rows)

    def lookup_readonly(
        self, state: TableState, ids: jnp.ndarray, pad_value: int = -1,
        salt: Optional[int] = None,
    ) -> jnp.ndarray:
        """Serving lookup. For grouped/stacked tables pass the per-feature
        salt used at training time so missing keys serve the same
        initializer vector training would have created."""
        return _lookup_readonly_jit(self, state, ids, pad_value, salt)

    def _lookup_readonly_impl(
        self, state: TableState, ids: jnp.ndarray, pad_value: int = -1,
        salt=None,
    ) -> jnp.ndarray:
        """Serving-path lookup: no insertion, no counter updates. Missing keys
        serve their initializer value (what a fresh key would have trained
        from), padding serves zeros."""
        cfg = self.cfg
        shape = ids.shape
        flat = ids.reshape(-1)
        sentinel = jnp.asarray(empty_key(cfg), flat.dtype)
        is_pad = flat == jnp.asarray(pad_value, flat.dtype)
        flat = jnp.where(is_pad, sentinel, flat)
        keys, slot_ix, _, _ = self._probe(
            state.keys, flat, jnp.zeros(flat.shape, bool)
        )
        del keys  # unchanged: no creation
        with scopes.scope(scopes.ENGINE_GATHER):
            present = slot_ix >= 0
            safe_ix = jnp.where(present, slot_ix, 0)
            emb = self._gather(state.values, safe_ix, state.capacity)
            if self.quantized:
                emb = self._dequant(emb, safe_ix, state)
            emb = jnp.where(
                present[:, None], emb, self._init_rows(flat, salt)
            )
            emb = jnp.where(is_pad[:, None], 0.0, emb)
            return emb.reshape(*shape, cfg.dim)

    # ---------------------------------------------------------------- updates

    def scatter_update(
        self,
        state: TableState,
        slot_ix: jnp.ndarray,
        new_values: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        seed: jnp.ndarray | int = 0,
    ) -> TableState:
        """Write rows back (optimizers use this through their own slot logic).
        Pass the global step as `seed` when the table is bf16 so stochastic
        rounding draws fresh bits each step."""
        ok = slot_ix >= 0
        if mask is not None:
            ok = ok & mask
        values = self._scatter(
            state.values, jnp.where(ok, slot_ix, -1), new_values,
            state.capacity, seed=seed,
        )
        ix = jnp.where(ok, slot_ix, state.capacity)
        # Standalone writes (no same-step train lookup stamped these rows)
        # keep their own dirty marking so incremental saves see them.
        meta = state.meta.at[META_DIRTY, ix].set(1, mode="drop")
        return state.replace(values=values, meta=meta)

    # ------------------------------------------------------- evict & rebuild

    def occupied(self, state: TableState) -> jnp.ndarray:
        return state.keys != jnp.asarray(empty_key(self.cfg), state.keys.dtype)

    def size(self, state: TableState) -> jnp.ndarray:
        """Live key count — EV's Size()/tf.EVGetSize analog."""
        return jnp.sum(self.occupied(state)).astype(jnp.int32)

    def evict_mask(self, state: TableState, step: jnp.ndarray | int) -> jnp.ndarray:
        """Which occupied slots the eviction policies would drop
        (docs/docs_en/Feature-Eviction.md: GlobalStepEvict + L2WeightEvict)."""
        cfg = self.cfg
        occ = self.occupied(state)
        drop = jnp.zeros_like(occ)
        gse = cfg.ev.global_step_evict
        if gse is not None and gse.steps_to_live > 0:
            drop = drop | (
                jnp.asarray(step, jnp.int32) - state.version > gse.steps_to_live
            )
        l2e = cfg.ev.l2_weight_evict
        if l2e is not None and l2e.l2_weight_threshold >= 0:
            from deeprec_tpu.ops.packed import unpack_array

            norm2 = jnp.sum(
                unpack_array(state.values, state.capacity).astype(jnp.float32)
                ** 2,
                axis=1,
            )
            drop = drop | (norm2 < l2e.l2_weight_threshold)
        return occ & drop

    def rebuild(
        self, state: TableState, keep: Optional[jnp.ndarray] = None,
        new_capacity: Optional[int] = None,
        slot_fills: Optional[Tuple[Tuple[str, float], ...]] = None,
    ) -> TableState:
        """Re-insert surviving entries into a fresh table.

        Used for (a) eviction — linear probing cannot delete in place without
        breaking chains, and rebuilds also re-compact them — and (b) growth to
        a larger capacity. O(C), runs at checkpoint cadence, fully on device.
        """
        cfg = self.cfg
        C_new = new_capacity or state.capacity
        if C_new & (C_new - 1):
            raise ValueError("new_capacity must be a power of two")
        occ = self.occupied(state)
        if keep is not None:
            occ = occ & keep
        sentinel = jnp.asarray(empty_key(cfg), state.keys.dtype)
        uids = jnp.where(occ, state.keys, sentinel)

        fresh_keys = jnp.full((C_new,), sentinel, state.keys.dtype)
        fresh_keys, slot_ix, created, failed = self._probe(fresh_keys, uids, occ)
        # Survivors always fit: C_new >= live count and probing is unbounded
        # only by max_probes — extremely unlikely to fail at <=50% load, but
        # surface it if it happens.
        ix = jnp.where(slot_ix >= 0, slot_ix, C_new)

        from deeprec_tpu.ops.packed import pack_array, unpack_array

        def move(arr, fill):
            out = jnp.full((C_new,) + arr.shape[1:], fill, arr.dtype)
            return out.at[ix].set(arr, mode="drop")

        def move_rows(arr, fill):
            """Per-row 2-D arrays relocate in LOGICAL layout, then repack
            at the new capacity's factor (growth can change eligibility —
            rebuild runs at checkpoint cadence, the relayout is fine).
            pack_width applies the cfg.packed backend gate."""
            logical = unpack_array(arr, state.capacity)
            moved = move(logical, fill)
            return pack_array(moved, self.pack_width(logical.shape[1], C_new))

        from deeprec_tpu.optim.sparse import SCALAR_PREFIX

        # Relocate the fused metadata in one scatter; vacated slots take the
        # per-column fills (freq 0 / version -1 / dirty 0).
        meta = empty_meta(C_new).at[:, ix].set(state.meta, mode="drop")

        return TableState(
            keys=fresh_keys,
            values=move_rows(state.values, 0),
            meta=meta,
            slots={
                # Per-table scalar slots (e.g. AdamAsync beta powers, shape
                # [1, 1]) are not per-key rows — pass them through. Freed
                # per-key rows reset to the optimizer's slot INIT value
                # (slot_fills), not 0 — an Adagrad accumulator reborn at 0
                # would rsqrt(0) into NaN on a zero-grad dim.
                k: (
                    v
                    if k.startswith(SCALAR_PREFIX)
                    else move_rows(v, dict(slot_fills or ()).get(k, 0))
                )
                for k, v in state.slots.items()
            },
            bloom=state.bloom,
            insert_fails=jnp.sum(failed).astype(jnp.int32),
            qscale=(
                None if state.qscale is None else move(state.qscale, 0.0)
            ),
        )

    def evict(self, state: TableState, step: jnp.ndarray | int,
              slot_fills: Optional[Tuple[Tuple[str, float], ...]] = None
              ) -> TableState:
        return _evict_jit(self, state, jnp.asarray(step, jnp.int32), slot_fills)

    def grow(self, state: TableState, new_capacity: int,
             slot_fills: Optional[Tuple[Tuple[str, float], ...]] = None
             ) -> TableState:
        """Host-orchestrated growth (recompiles downstream jits once per
        capacity — the price of dynamic tables in a static-shape world).
        Pass the optimizer's slot_fills so rows later created in the new
        empty slots start from the slot INIT value, not 0."""
        return self.rebuild(state, new_capacity=new_capacity,
                            slot_fills=slot_fills)


# --------------------------------------------------------------------------
# Jitted trampolines: public methods route through these so eager callers
# (tests, serving glue) hit the compile cache instead of op-by-op dispatch.
# Inside a user jit they inline into the surrounding program.

import functools as _functools


@_functools.partial(jax.jit, static_argnums=(0, 4, 5, 6))
def _lookup_unique_jit(table, state, ids, step, train, pad_value, unique_size):
    return table._lookup_unique_impl(state, ids, step, train, pad_value, unique_size)


@_functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _lookup_readonly_jit(table, state, ids, pad_value, salt):
    return table._lookup_readonly_impl(state, ids, pad_value, salt)


@_functools.partial(jax.jit, static_argnums=0)
def probe_jit(table, keys, uids, want_create):
    """Jitted lookup-or-create probe for restore/replay paths: the eager
    while_loop dispatches op-by-op and dominated delta-replay latency
    (poll_updates under serving load). Compile-cached per (table,
    shapes) — pair with power-of-two row bucketing (import_rows)."""
    return table._probe(keys, uids, want_create)


@_functools.partial(jax.jit, static_argnums=(0, 3))
def _evict_jit(table, state, step, slot_fills):
    drop = table.evict_mask(state, step)
    return table.rebuild(state, keep=~drop, slot_fills=slot_fills)
