"""Checkpointing: full + incremental saves of sparse tables and dense params.

Parity with DeepRec's EV checkpoint machinery (SURVEY.md §3.3):
  * Full save: per table, the compacted tensors keys/values/freqs/versions
    (+ optimizer slots and filter sketch) with partition offsets — the
    "9 parts" export of SaveV2(has_ev=true)
    (docs/docs_en/Embedding-Variable.md "Checkpoint",
    embedding_var_ckpt_data.cc). Non-admitted (filtered) keys are saved with
    their frequency so admission counters survive restore
    (TF_EV_SAVE_FILTERED_FEATURES behavior).
  * Incremental save: only rows dirtied since the last save — the IncrSave /
    IndicesIncrRecorder delta path (core/kernels/incr_save_restore_ops.h:43),
    used for fast PS failover and serving delta updates.
  * Restore: latest full checkpoint, then replay deltas in order
    (Incremental-Checkpoint.md:3-7). Keys are re-inserted by probing, so a
    checkpoint restores onto ANY topology — different mesh size or grown
    capacity — which is what elastic re-scaling needs (elastic_training.proto
    semantics without the gRPC choreography).

Format: a directory per step, numpy .npz per table plus dense.npz and a JSON
manifest. Host-side; runs at checkpoint cadence, not on the hot path.

Off-the-hot-path choreography (round 9): every save is split into a STAGE
half (device work only: for incremental saves a jitted dirty-row compaction
so the device->host transfer scales with the dirty fraction, not capacity;
for full saves a donation-safe device snapshot) and a WRITE half (host
numpy materialization + npz IO + manifest-last commit). `save()` runs both
on the caller; `save_async()` / `save_incremental_async()` run the write
half on a background writer thread so the npz IO overlaps the next train
dispatches — at most one save in flight, `wait()` drains it, and a killed
writer leaves a manifest-less dir that `_list()` already ignores (the
manifest stays the completeness marker).

Checksummed chains (round 12): every npz array's digest is recorded in the
manifest at write time, delta manifests carry a `base` link to the save
they apply over, and `verify()`/`valid_chain()` replay the checks on the
read side. A corrupt or torn link is QUARANTINED (dir renamed to
`*.quarantined`) and consumers fall back to the longest valid chain
prefix; a quarantined step newer than the latest full escalates the
trainer's next save to full (`_effective_kind`), which re-anchors the
chain — the self-healing loop docs/fault-tolerance.md specifies.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
import shutil
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeprec_tpu.analysis.annotations import not_thread_safe
from deeprec_tpu.embedding.table import EmbeddingTable, TableState, empty_key
from deeprec_tpu.training.trainer import TrainState, Trainer
from deeprec_tpu.utils import hashing, scopes

_log = logging.getLogger(__name__)


class CheckpointCorrupt(RuntimeError):
    """A committed checkpoint dir failed integrity verification (missing
    file, torn manifest, digest mismatch). Consumers treat the dir as
    absent — quarantine + longest-valid-prefix fallback — rather than
    letting this escape into serving."""


def _array_digest(arr: np.ndarray) -> str:
    """Per-array content digest recorded in the manifest at write time and
    re-checked by `CheckpointManager.verify`. crc32 over the raw bytes plus
    dtype/shape: fast enough to run inline with the npz write (GB/s), and
    any payload bit-flip the zip layer misses still fails here."""
    a = np.ascontiguousarray(arr)
    crc = zlib.crc32(a.tobytes()) & 0xFFFFFFFF
    shape = "x".join(map(str, a.shape))
    return f"crc32:{crc:08x}:{a.dtype.str}:{shape}"


# ----------------------------------------------------------- table export


def is_per_row(name: str) -> bool:
    """Checkpoint-array routing by NAME (never by shape, which is ambiguous):
    per-row arrays are compacted/partitioned; per-table arrays (CBF sketch,
    scalar optimizer slots) are carried whole."""
    if name in ("keys", "values", "freqs", "versions"):
        return True
    return name.startswith("slot:") and not name.startswith("slot:scalar/")


def export_table_arrays(
    table: EmbeddingTable, state_np: Dict[str, np.ndarray], only_dirty: bool
) -> Dict[str, np.ndarray]:
    """Compact one LOCAL table state (host numpy arrays) to its live rows.

    The checkpoint format is LOGICAL rows — packed small-dim arrays
    (ops/packed.py) unpack via a free numpy reshape here, so checkpoints
    are portable across layout choices."""
    from deeprec_tpu.ops.packed import unpack_array

    cfg = table.cfg
    keys = state_np["keys"]
    C = keys.shape[0]
    state_np = {
        name: (
            unpack_array(arr, C)
            if name == "values"
            or (name.startswith("slot:") and is_per_row(name))
            else arr
        )
        for name, arr in state_np.items()
    }
    occ = keys != empty_key(cfg)
    if only_dirty:
        occ = occ & state_np["dirty"]
    if (
        not cfg.ev.ckpt.save_filtered_features
        and cfg.ev.counter_filter is not None
        and cfg.ev.counter_filter.filter_freq > 0
    ):
        # CheckpointOption / TF_EV_SAVE_FILTERED_FEATURES=False: drop
        # sub-threshold keys at save time (admission counters restart).
        # COUNTER filter only: its admission counter IS the row freq. In
        # CBF mode sub-threshold keys never occupy rows (the counter lives
        # in the sketch), so every resident row is admitted and a row-freq
        # threshold would wrongly drop just-admitted keys.
        occ = occ & (state_np["freq"] >= cfg.ev.counter_filter.filter_freq)
    idx = np.nonzero(occ)[0]
    out = {
        "keys": keys[idx],
        "values": state_np["values"][idx],
        "freqs": state_np["freq"][idx],
        "versions": state_np["version"][idx],
    }
    for sname, arr in state_np.items():
        if sname.startswith("slot:"):
            out[sname] = arr[idx] if is_per_row(sname) else arr
    if state_np.get("bloom") is not None:
        out["bloom"] = state_np["bloom"]
    return out


def _to_host(x) -> np.ndarray:
    """Materialize an array on THIS host — including multi-host global
    arrays, whose shards are assembled across processes (shared-FS
    checkpointing: every process sees the full value, process 0 writes)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


def _meta_columns(meta_np: np.ndarray) -> Dict[str, np.ndarray]:
    """Unpack the fused [..., 3, C] metadata into the checkpoint's COLUMNAR
    freq/version/dirty arrays — the on-disk format is unchanged by the
    packed device layout, so old checkpoints restore as-is and new ones
    restore into old code."""
    from deeprec_tpu.embedding.table import META_DIRTY, META_FREQ, META_VERSION

    return {
        "freq": meta_np[..., META_FREQ, :],
        "version": meta_np[..., META_VERSION, :],
        "dirty": meta_np[..., META_DIRTY, :] != 0,
    }


def _state_to_np(ts: TableState) -> Dict[str, np.ndarray]:
    d = {
        "keys": _to_host(ts.keys),
        "values": _to_host(ts.values),
        **_meta_columns(_to_host(ts.meta)),
    }
    for sname, arr in ts.slots.items():
        d["slot:" + sname] = _to_host(arr)
    if ts.bloom is not None:
        d["bloom"] = _to_host(ts.bloom)
    return d


def import_rows(
    table: EmbeddingTable,
    state: TableState,
    rows: Dict[str, np.ndarray],
    strict: bool = True,
    bucket: bool = False,
    chunk: Optional[int] = None,
) -> TableState:
    """Insert checkpointed rows into a (fresh or live) local table state.

    bucket=True pads the row count to the next power of two before the
    probe/scatter: every distinct count is a distinct static shape, and
    delta replays at serving cadence (poll_updates) would otherwise bake
    a fresh XLA program per update. Padding keys hold the empty-key
    sentinel, which _probe treats as invalid — inert by construction.
    One-shot full restores skip it (each shape compiles once anyway, and
    padding would transiently copy the whole values array). Only PER-ROW
    arrays pad; per-table entries (scalar optimizer slots, bloom) pass
    through untouched.

    chunk=N (overrides bucket) imports in sequential fixed-size slices of
    exactly N rows (last slice padded): ONE static shape per table, ever.
    This is the zero-stall serving discipline — power-of-two bucketing
    still traces a fresh XLA program the first time each bucket size
    appears, and that trace holds the GIL for hundreds of ms while live
    requests wait. With a fixed chunk the program compiles once at
    startup/warmup and every later full reload or delta replay is pure
    cache-hit dispatch. Per-table entries (scalar slots, bloom) are
    whole-table values, identical in every slice, so re-applying them per
    slice is idempotent. Costs one full values-array copy per slice —
    pick a chunk that keeps the slice count small at your row scale.
    """
    n = rows["keys"].shape[0]
    if n == 0:
        if "bloom" in rows and state.bloom is not None:
            state = state.replace(bloom=jnp.asarray(rows["bloom"]))
        return state
    if chunk is not None and n > chunk:
        for off in range(0, n, chunk):
            sl = {
                k: (v[off:off + chunk] if is_per_row(k) else v)
                for k, v in rows.items()
            }
            state = import_rows(table, state, sl, strict=strict, chunk=chunk)
        return state
    m = chunk if chunk is not None else (
        (1 << (n - 1).bit_length()) if bucket else n
    )

    def _padded(k, a):
        per_row = k in ("keys", "values", "freqs", "versions") or (
            k.startswith("slot:") and is_per_row(k)
        )
        if m == n or not per_row:
            return a
        a = np.asarray(a)
        fill = empty_key(table.cfg) if k == "keys" else 0
        return np.concatenate(
            [a, np.full((m - n,) + a.shape[1:], fill, a.dtype)]
        )

    rows = {k: _padded(k, v) for k, v in rows.items()}
    from deeprec_tpu.embedding.table import probe_jit

    keys = jnp.asarray(rows["keys"])
    new_keys, slot_ix, created, failed = probe_jit(
        table, state.keys, keys, jnp.ones((m,), bool)
    )
    if strict and bool(jnp.any(failed)):
        raise RuntimeError(
            f"table {table.cfg.name}: {int(jnp.sum(failed))} keys failed to "
            f"insert on restore — grow the capacity"
        )
    from deeprec_tpu.ops.packed import scatter_rows_any

    ix = jnp.where(slot_ix >= 0, slot_ix, state.capacity)
    put_ix = jnp.where(slot_ix >= 0, slot_ix, -1)
    # Restored rows are LOGICAL; scatter_rows_any re-packs on the way in.
    # Exact restore for f32; bf16 values round stochastically (identity
    # for rows that came out of a bf16 table — already representable).
    # int8 serving residency quantizes ON IMPORT: checkpoints stay fp32
    # on disk, the per-row scale lands in TableState.qscale, and the
    # quantize ops run at the same fixed chunk shape as the scatter —
    # the zero-retrace delta-replay contract holds unchanged.
    val_rows = jnp.asarray(rows["values"], np.float32)
    qscale = state.qscale
    if getattr(table, "quantized", False):
        from deeprec_tpu.embedding.table import quantize_rows_int8

        val_rows, scale = quantize_rows_int8(val_rows)
        qscale = qscale.at[ix].set(scale, mode="drop")
    values = scatter_rows_any(
        state.values, put_ix, val_rows, state.capacity,
    )
    from deeprec_tpu.embedding.table import META_FREQ, META_VERSION

    meta = state.meta.at[META_FREQ, ix].set(
        jnp.asarray(rows["freqs"], jnp.int32), mode="drop"
    )
    meta = meta.at[META_VERSION, ix].set(
        jnp.asarray(rows["versions"], jnp.int32), mode="drop"
    )
    slots = dict(state.slots)
    for sname, arr in state.slots.items():
        key = "slot:" + sname
        if key not in rows:
            continue
        r = jnp.asarray(rows[key])
        if is_per_row(key):
            slots[sname] = scatter_rows_any(
                arr, put_ix, r.astype(jnp.float32), state.capacity
            )
        else:
            slots[sname] = r
    bloom = state.bloom
    if "bloom" in rows and bloom is not None:
        bloom = jnp.asarray(rows["bloom"])
    return state.replace(
        keys=new_keys, values=values, meta=meta, slots=slots, bloom=bloom,
        qscale=qscale,
    )


# ----------------------------------------- device-side dirty compaction

import functools as _ft

from deeprec_tpu.embedding.table import META_DIRTY, META_FREQ, META_VERSION


@_ft.partial(jax.jit, static_argnums=(0, 3))
def _rebuild_keep_jit(table, state: TableState, keep: jnp.ndarray,
                      slot_fills) -> TableState:
    """Jitted keep-mask rebuild for delta-replay pruning (_prune_to_live):
    compile-cached per (table, slot_fills, shapes) so serving-cadence
    replays never re-trace the probe loop."""
    return table.rebuild(state, keep=keep, slot_fills=slot_fills)


@_ft.partial(jax.jit, static_argnums=(0, 3))
def _rebuild_keep_sharded_jit(table, state: TableState, keep: jnp.ndarray,
                              slot_fills) -> TableState:
    return jax.vmap(
        lambda s, kp: table.rebuild(s, keep=kp, slot_fills=slot_fills)
    )(state, keep)


@_ft.partial(jax.jit, static_argnums=(1,))
def _dirty_count_jit(state: TableState, sentinel: int) -> jnp.ndarray:
    """Occupied-and-dirty row count of one LOCAL table state — the one
    scalar an incremental save reads from the device to size its
    compacted export."""
    occ = state.keys != jnp.asarray(sentinel, state.keys.dtype)
    return jnp.sum(occ & (state.meta[META_DIRTY] != 0)).astype(jnp.int32)


@_ft.partial(jax.jit, static_argnums=(1, 2))
def _compact_dirty_jit(
    state: TableState, sentinel: int, size: int
) -> Dict[str, jnp.ndarray]:
    """Compact one LOCAL table state's dirty rows ON DEVICE at static
    budget `size` (ops/compact.py prefix-sum compaction, ascending slot
    order — the same order the legacy host-side `np.nonzero` export
    produced, so files stay byte-identical after truncation).

    Everything returned is a FRESH buffer (jit outputs never alias
    non-donated inputs), so an async writer can materialize it while the
    training loop donates the live state through the next dispatches.
    Rows past the true dirty count are garbage the host truncates; the
    full key array rides along (`_all_keys`) for the delta's live set.
    """
    from deeprec_tpu.ops.compact import rank_compact
    from deeprec_tpu.ops.packed import gather_rows_any

    C = state.capacity
    sent = jnp.asarray(sentinel, state.keys.dtype)
    occ = state.keys != sent
    dirty = occ & (state.meta[META_DIRTY] != 0)
    idx, _, _ = rank_compact(dirty, size)
    safe = jnp.where(idx >= 0, idx, 0)
    out = {
        "keys": jnp.where(idx >= 0, state.keys[safe], sent),
        "values": gather_rows_any(state.values, safe, C),
        "freqs": state.meta[META_FREQ, safe],
        "versions": state.meta[META_VERSION, safe],
        "_all_keys": jnp.copy(state.keys),
    }
    for sname, arr in state.slots.items():
        key = "slot:" + sname
        out[key] = (
            gather_rows_any(arr, safe, C) if is_per_row(key)
            else jnp.copy(arr)
        )
    if state.bloom is not None:
        out["bloom"] = jnp.copy(state.bloom)
    return out


@jax.jit
def _copy_tree(tree):
    """Donation-safe device snapshot: fresh buffers for every leaf, so the
    async writer's host copies survive the training loop donating the
    originals (jnp.copy lowers to an XLA copy — outputs never alias)."""
    return jax.tree.map(jnp.copy, tree)


def _prefetch_host(tree) -> None:
    """Best-effort: start the device->host copies now so the writer
    thread's np.asarray calls find the bytes already on their way."""
    for leaf in jax.tree.leaves(tree):
        fn = getattr(leaf, "copy_to_host_async", None)
        if fn is not None:
            try:
                fn()
            except Exception:
                pass


def _tree_bytes(tree) -> int:
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(tree)
        if hasattr(leaf, "dtype")
    )


@dataclasses.dataclass
class _SavePlan:
    """Everything the WRITE half needs, detached from the live TrainState:
    device snapshots / compacted exports (fresh buffers), dataset positions
    snapshotted at stage time (the training loop advances readers while an
    async writer runs), and the manifest ingredients."""

    path: str
    kind: str
    step: int
    parts: bool
    write: bool
    state: Optional[TrainState]  # full saves: the (possibly snapshotted) state
    incr: Optional[Dict[str, Dict[str, list]]]  # incr: bundle->tag->[(sid, arrays, n)]
    dense: Any
    opt_state: Any
    positions: Optional[Dict[str, dict]]
    stats: Dict[str, float]
    # Per-bundle routing fingerprint at STAGE time (the async writer must
    # not read the live trainer's plans — a maintain() can adopt a new
    # plan while the write half runs). "uniform" = hash routing.
    routing: Dict[str, str] = dataclasses.field(default_factory=dict)


# -------------------------------------------------------- checkpoint manager


def _tree_to_npz_dict(tree) -> Dict[str, np.ndarray]:
    leaves, _ = jax.tree_util.tree_flatten(tree)
    return {f"leaf_{i}": np.asarray(l) for i, l in enumerate(leaves)}


def _tree_from_npz_dict(template, data) -> object:
    leaves, treedef = jax.tree_util.tree_flatten(template)
    new_leaves = [
        jnp.asarray(data[f"leaf_{i}"]).astype(l.dtype).reshape(l.shape)
        if hasattr(l, "dtype")
        else data[f"leaf_{i}"]
        for i, l in enumerate(leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


class CheckpointManager:
    """Save/restore for a Trainer (single-device or sharded).

    Layout:
        <dir>/full-<step>/manifest.json, dense.npz, table_<bundle>[_tK].npz
        <dir>/incr-<step>/...            (deltas since previous save)
    """

    def __init__(self, directory: str, trainer: Trainer, keep: int = 3,
                 sharded_io: Optional[bool] = None,
                 datasets: Optional[Dict[str, object]] = None):
        """sharded_io: write per-process shard-part files instead of the
        gathered single-file format (pod-scale: no process_allgather on
        save, no host-side global materialization on restore). Default None
        = auto: parts when the trainer is sharded AND multi-process; the
        gathered format is kept for single-process runs where it is cheap
        and produces fewer files. Either format restores onto any topology;
        sharded trainers also restore either format.

        datasets: {name: reader} of input-state carriers (anything with
        ``save() -> dict`` / ``restore(dict)`` — KafkaStreamReader,
        TCPStreamReader, FileTailReader, WorkQueue). Their positions are
        written with every checkpoint and restored with the model, the
        reference's dataset-state-in-checkpoint behavior (KafkaDataset
        offsets ride TF checkpoints, kafka_dataset_op.cc SaveInternal).
        Positions are PER-PROCESS (each process checkpoints its own
        readers); after an elastic topology change a missing per-process
        file is skipped — data rebalancing across a rescale is the shared
        WorkQueue's job, not a byte-offset's."""
        self.dir = directory
        self.trainer = trainer
        self.keep = keep
        self.sharded_io = sharded_io
        self.datasets = dict(datasets or {})
        # Async-writer state: at most one save in flight; wait() drains and
        # re-raises. on_write is a test seam invoked in the writer thread
        # before any file IO (crash/overlap injection).
        self._writer: Optional[threading.Thread] = None
        self._writer_err: Optional[Tuple[BaseException, str]] = None
        self._force_full = False  # failed incr writer -> next save is full
        self.on_write = None
        # Integrity state: dirs that already passed verify() (files are
        # immutable once the manifest commits, so one pass is enough);
        # quarantine_count / last_quarantined surface through serving
        # health (Predictor.health, /healthz).
        self._verified: set = set()
        self.quarantine_count = 0
        self.last_quarantined: Optional[str] = None
        # Stall/traffic accounting (bench.py, tools/bench_ckpt.py):
        # ckpt_stall_ms accumulates CALLER-side blocking time across saves;
        # last_save records {kind, path, async, stall_ms, transfer_bytes,
        # write_ms (async, once the writer finishes)}.
        self.ckpt_stall_ms: float = 0.0
        self.last_save: Dict[str, Any] = {}
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------- helpers

    def _bundle_states(self, state: TrainState, bname: str) -> List[Tuple[str, Dict]]:
        """Split a (possibly stacked and/or sharded) bundle state into LOCAL
        per-table host states, tagged 'tK' for stacked member K. Shard dims
        are concatenated: rows from all shards merge into one export (the
        partition_offset records the split for forensics)."""
        b = self.trainer.bundles[bname]
        ts = state.tables[bname]
        out = []
        members = range(len(b.features)) if b.stacked else [None]
        for k in members:
            sub = jax.tree.map(lambda a: a[k], ts) if b.stacked else ts
            out.append((f"t{k}" if k is not None else "t", _state_to_np(sub)))
        return out

    def _is_sharded(self) -> bool:
        return hasattr(self.trainer, "num_shards")

    def _export_bundle(self, state, bname, only_dirty) -> Dict[str, Dict[str, np.ndarray]]:
        from deeprec_tpu.embedding.table import empty_key

        b = self.trainer.bundles[bname]
        exports = {}
        for tag, np_state in self._bundle_states(state, bname):
            if self._is_sharded():
                # leading dim = shard axis: compact each shard, concatenate,
                # remember offsets (DeepRec's -partition_offset tensor)
                parts = []
                offsets = [0]
                N = np_state["keys"].shape[0]
                for s in range(N):
                    local = {k: v[s] for k, v in np_state.items()}
                    parts.append(export_table_arrays(b.table, local, only_dirty))
                    offsets.append(offsets[-1] + parts[-1]["keys"].shape[0])
                merged = {}
                for k in parts[0]:
                    if is_per_row(k):
                        merged[k] = np.concatenate([p[k] for p in parts])
                    elif k == "bloom":
                        # keep each shard's sketch: restoring onto the SAME
                        # shard count is then exact (sub-threshold admission
                        # counts survive); re-sharding falls back to a
                        # rebuild from row freqs (see _import_local)
                        merged["bloom_parts"] = np.stack([p[k] for p in parts])
                    else:  # per-table scalar slot: identical on all shards
                        merged[k] = parts[0][k]
                merged["partition_offset"] = np.asarray(offsets, np.int64)
                exports[tag] = merged
            else:
                exports[tag] = export_table_arrays(b.table, np_state, only_dirty)
            if only_dirty:
                # Deltas carry the FULL live-key set (keys only, compact):
                # restore prunes resurrected keys that were evicted between
                # saves — dirty rows alone cannot express an eviction.
                keys = np_state["keys"]
                occ = keys != empty_key(b.table.cfg)
                exports[tag]["live_keys"] = keys[occ]
        return exports

    # ------------------------------------------------ pod-scale parts format
    #
    # At pod scale the gathered format above stops working: a full
    # process_allgather per save means every host materializes every table.
    # The parts format writes one file per PROCESS per table containing only
    # that process's addressable shards' compacted rows (the analog of
    # DeepRec's per-PS checkpoint partitions, Embedding-Variable.md
    # "Checkpoint" 9-part layout — except parts here follow the device mesh,
    # not a PS assignment). Restore streams every part file and re-routes
    # each key to its owner shard by hash, so a parts checkpoint restores
    # onto ANY topology (different process count, mesh size, or capacity),
    # exactly like the gathered format.

    def _use_parts(self) -> bool:
        if not self._is_sharded():
            return False
        if self.sharded_io is not None:
            return self.sharded_io
        return jax.process_count() > 1

    def _shard_axis(self, bname) -> int:
        """Position of the shard axis in this bundle's state leaves
        ([T, N, ...] stacked, [N, ...] plain)."""
        return 1 if self.trainer.bundles[bname].stacked else 0

    @staticmethod
    def _owned_ids(leaf, k) -> List[int]:
        """Shard indices addressable on this process (all of them when
        single-process)."""
        return sorted({s.index[k].start or 0 for s in leaf.addressable_shards})

    @staticmethod
    def _local_block(leaf, k, s) -> np.ndarray:
        """One owned shard's data with the shard axis dropped — reads the
        addressable shard directly, never the global value."""
        for sh in leaf.addressable_shards:
            if (sh.index[k].start or 0) == s:
                data = np.asarray(sh.data)
                assert data.shape[k] == 1, (
                    f"expected one shard index per device, got {data.shape}"
                )
                return np.squeeze(data, axis=k)
        raise KeyError(f"shard {s} is not addressable on this process")

    def _export_bundle_parts(
        self, state, bname, only_dirty
    ) -> Dict[str, Dict[str, np.ndarray]]:
        """Compact THIS process's shards of one bundle (no cross-process
        collectives). Arrays mirror _export_bundle plus routing metadata:
        shard_ids (which shards the rows came from, partition_offset-aligned)
        and num_shards (sharding at save time, for exact-sketch restore)."""
        b = self.trainer.bundles[bname]
        ts = state.tables[bname]
        k = self._shard_axis(bname)
        owned = self._owned_ids(ts.keys, k)
        members = range(len(b.features)) if b.stacked else [None]
        exports = {}
        for m in members:
            tag = f"t{m}" if m is not None else "t"

            def np_state_for(s, m=m):
                def get(leaf):
                    blk = self._local_block(leaf, k, s)
                    return blk[m] if m is not None else blk

                d = {
                    "keys": get(ts.keys),
                    "values": get(ts.values),
                    # unpack the fused metadata HOST-side (the device leaf
                    # is [3, C_local]; the file format stays columnar)
                    **_meta_columns(get(ts.meta)),
                }
                for sname, arr in ts.slots.items():
                    d["slot:" + sname] = get(arr)
                if ts.bloom is not None:
                    d["bloom"] = get(ts.bloom)
                return d

            parts, offsets, blooms, live = [], [0], [], []
            for s in owned:
                np_state = np_state_for(s)
                parts.append(export_table_arrays(b.table, np_state, only_dirty))
                offsets.append(offsets[-1] + parts[-1]["keys"].shape[0])
                if np_state.get("bloom") is not None:
                    blooms.append(np_state["bloom"])
                if only_dirty:
                    occ = np_state["keys"] != empty_key(b.table.cfg)
                    live.append(np_state["keys"][occ])
            merged = {}
            for key in parts[0]:
                if key == "bloom":
                    continue  # per-shard sketches ride bloom_parts below
                merged[key] = (
                    np.concatenate([p[key] for p in parts])
                    if is_per_row(key)
                    else parts[0][key]
                )
            if blooms:
                merged["bloom_parts"] = np.stack(blooms)
            merged["partition_offset"] = np.asarray(offsets, np.int64)
            merged["shard_ids"] = np.asarray(owned, np.int64)
            merged["num_shards"] = np.asarray(self.trainer.num_shards, np.int64)
            if only_dirty:
                merged["live_keys"] = (
                    np.concatenate(live)
                    if live
                    else np.empty((0,), parts[0]["keys"].dtype)
                )
            exports[tag] = merged
        return exports

    # ------------------------------------- incremental staging (device half)

    @staticmethod
    def _local_device_block(leaf, k: int, s: int):
        """One owned shard's block with the shard axis dropped, as a DEVICE
        array (the np-returning `_local_block` is the full-transfer legacy
        read; the compacted exporter must not pull [C_local, D] leaves to
        the host just to pick a few dirty rows out of them)."""
        for sh in leaf.addressable_shards:
            if (sh.index[k].start or 0) == s:
                return jnp.squeeze(sh.data, axis=k)
        raise KeyError(f"shard {s} is not addressable on this process")

    def _member_local_state(self, ts: TableState, m: Optional[int],
                            s: Optional[int], k: int) -> TableState:
        """LOCAL TableState view (device leaves) for member `m` of shard
        `s` (None = unstacked / unsharded)."""
        def get(leaf):
            x = self._local_device_block(leaf, k, s) if s is not None else leaf
            return x[m] if m is not None else x

        return jax.tree.map(get, ts)

    def _stage_incr(self, state: TrainState):
        """Device half of an incremental save: per (bundle, member, shard),
        read ONE dirty-count scalar, quantize it to a power-of-two budget
        (ops/compact.quantize_rows — drift re-traces at most log2(C) times
        per table) and run the jitted compaction. Returns
        ({bundle: {tag: [(shard_id, device_arrays, n)]}}, transfer_bytes)
        where transfer_bytes is what actually crosses device->host: the
        padded compacted rows + the [C] key array per shard — dirty-
        fraction-scaled, not capacity-scaled."""
        from deeprec_tpu.ops.compact import quantize_rows

        out: Dict[str, Dict[str, list]] = {}
        jobs = []  # (pkgs-list, shard_id, sentinel, sub_state, count_device)
        for bname, b in self.trainer.bundles.items():
            ts = state.tables[bname]
            sent = empty_key(b.table.cfg)
            k = self._shard_axis(bname) if self._is_sharded() else 0
            if not self._is_sharded():
                sids: List[Optional[int]] = [None]
            elif self._use_parts():
                sids = list(self._owned_ids(ts.keys, k))
            else:
                sids = list(range(self.trainer.num_shards))
            members = range(len(b.features)) if b.stacked else [None]
            out[bname] = {}
            for m in members:
                tag = f"t{m}" if m is not None else "t"
                pkgs: list = []
                out[bname][tag] = pkgs
                for s in sids:
                    # Pass 1: dispatch every count (async) — the first
                    # int() below drains the dispatch queue ONCE for all
                    # of them instead of one flush per (bundle, member,
                    # shard).
                    sub = self._member_local_state(ts, m, s, k)
                    jobs.append((pkgs, s, sent, sub,
                                 _dirty_count_jit(sub, sent)))
        total = 0
        for pkgs, s, sent, sub, cnt in jobs:
            n = int(cnt)
            size = quantize_rows(n, sub.capacity)
            arrays = _compact_dirty_jit(sub, sent, size)
            total += _tree_bytes(arrays)
            pkgs.append((s, arrays, n))
        return out, total

    # -------------------------------------- incremental assembly (IO half)

    def _materialize_pkg(self, b, arrays: Dict[str, jnp.ndarray], n: int):
        """One shard's staged compaction -> (row dict truncated to the true
        dirty count, live keys, bloom, per-table scalar entries). Applies
        the same save-time counter-filter drop as `export_table_arrays`, on
        the already-small compacted arrays."""
        cfg = b.table.cfg
        np_arrays = {key: np.asarray(v) for key, v in arrays.items()}
        all_keys = np_arrays.pop("_all_keys")
        bloom = np_arrays.pop("bloom", None)
        per_table = {
            key: v for key, v in np_arrays.items()
            if key.startswith("slot:") and not is_per_row(key)
        }
        rows = {
            key: v[:n] for key, v in np_arrays.items() if key not in per_table
        }
        if (
            not cfg.ev.ckpt.save_filtered_features
            and cfg.ev.counter_filter is not None
            and cfg.ev.counter_filter.filter_freq > 0
        ):
            keep = rows["freqs"] >= cfg.ev.counter_filter.filter_freq
            rows = {key: v[keep] for key, v in rows.items()}
        live = all_keys[all_keys != empty_key(cfg)]
        return rows, live, bloom, per_table

    def _assemble_incr(self, plan: _SavePlan, bname: str,
                       parts: bool) -> Dict[str, Dict[str, np.ndarray]]:
        """Merge a bundle's staged per-shard compactions into the exact
        file layout the legacy host-side incremental export produced
        (gathered single / gathered sharded / parts) — restore code is
        untouched."""
        b = self.trainer.bundles[bname]
        exports = {}
        for tag, pkgs in plan.incr[bname].items():
            rows_list, live_list, blooms, offsets = [], [], [], [0]
            per_table: Dict[str, np.ndarray] = {}
            shard_ids = []
            for sid, arrays, n in pkgs:
                rows, live, bloom, scal = self._materialize_pkg(b, arrays, n)
                rows_list.append(rows)
                live_list.append(live)
                if bloom is not None:
                    blooms.append(bloom)
                per_table.update(scal)
                offsets.append(offsets[-1] + rows["keys"].shape[0])
                shard_ids.append(sid)
            if len(pkgs) == 1 and pkgs[0][0] is None:
                # plain Trainer: single gathered file, no partition metadata
                merged = {**rows_list[0], **per_table}
                if blooms:
                    merged["bloom"] = blooms[0]
            else:
                merged = {
                    key: np.concatenate([r[key] for r in rows_list])
                    for key in rows_list[0]
                }
                merged.update(per_table)
                if blooms:
                    merged["bloom_parts"] = np.stack(blooms)
                merged["partition_offset"] = np.asarray(offsets, np.int64)
                if parts:
                    merged["shard_ids"] = np.asarray(shard_ids, np.int64)
                    merged["num_shards"] = np.asarray(
                        self.trainer.num_shards, np.int64
                    )
            merged["live_keys"] = (
                np.concatenate(live_list)
                if live_list
                else np.empty((0,), rows_list[0]["keys"].dtype)
            )
            exports[tag] = merged
        return exports

    def _clear_dirty(self, state: TrainState) -> TrainState:
        # Zero the META_DIRTY row of the fused metadata leaf; the columnar
        # multiply broadcasts over any leading (group/shard) axes and keeps
        # the arrays' device placement.
        _keep = jnp.asarray([1, 1, 0], jnp.int32)[:, None]
        tables = {
            bname: ts.replace(meta=ts.meta * _keep)
            if not isinstance(ts, dict)
            else ts
            for bname, ts in state.tables.items()
        }
        return TrainState(
            step=state.step, tables=tables, dense=state.dense,
            opt_state=state.opt_state,
        )

    # ---------------------------------------------------------------- save

    def _is_writer(self) -> bool:
        """Multi-host: every process assembles the global arrays (shared-FS
        layout needs the files once), process 0 writes them.

        Memory model: saves gather each table to host RAM (a full
        process_allgather per save, incremental included) and multi-host
        restore materializes it on one device per process — correct up to
        host/device memory, which covers single-slice pods. A per-process
        shard-part file format (no global gather anywhere) is the
        pod-scale follow-up.
        """
        if jax.process_count() > 1 and not self._is_sharded():
            raise RuntimeError(
                "multi-process checkpointing requires a ShardedTrainer "
                "(a plain Trainer under jax.distributed has no global mesh "
                "to gather from / place onto)"
            )
        return jax.process_index() == 0

    @staticmethod
    def _sync(tag: str) -> None:
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(tag)

    def save(self, state: TrainState) -> Tuple[TrainState, str]:
        """Full checkpoint. Returns (state with dirty bits cleared, path).
        Multi-host safe: all processes participate in the gather, process 0
        writes, and nobody returns before the manifest exists."""
        return self._save(state, "full")

    def save_incremental(self, state: TrainState) -> Tuple[TrainState, str]:
        """Delta checkpoint: rows touched since the previous (full or incr)
        save, compacted ON DEVICE so the device->host transfer scales with
        the dirty fraction. The consumer replays deltas over the latest
        full save."""
        return self._save(state, "incr")

    # ------------------------------------------------------- async saves

    def save_async(self, state: TrainState) -> Tuple[TrainState, str]:
        """Full checkpoint with the write half on a background thread.

        The caller-side cost is the device snapshot dispatch (fresh
        buffers, so later donation of the live state cannot touch them)
        plus starting the host copies; np.savez + manifest run on the
        writer while the next dispatches train. Returns immediately with
        (dirty-cleared state, path); the checkpoint is durable only once
        `wait()` returns — a crash mid-write leaves a manifest-less dir
        that restore ignores (the existing crash contract). At most one
        save is in flight: a second save_*_async first drains the first.
        Transiently holds one extra device-side copy of the tables;
        multi-process runs fall back to the synchronous path (the barrier
        choreography must run on the dispatch thread)."""
        return self._save_async(state, "full")

    def save_incremental_async(self, state: TrainState) -> Tuple[TrainState, str]:
        """Delta checkpoint off the training thread: the device-compacted
        dirty rows (small, dirty-fraction-sized buffers) are staged on the
        caller, the npz write happens on the writer thread."""
        return self._save_async(state, "incr")

    def _save_async(self, state: TrainState, kind: str) -> Tuple[TrainState, str]:
        if jax.process_count() > 1:
            # sync_global_devices from a writer thread would interleave
            # with the training thread's collectives — degrade to the
            # synchronous multi-host path, which is already correct.
            return self._save(state, kind)
        self.wait()  # at most one save in flight
        kind = self._effective_kind(kind)
        t0 = time.perf_counter()
        with scopes.host_span(scopes.CKPT_SAVE):  # the caller-side half
            plan = self._stage(state, kind, snapshot=True)
        # Account (and rebind last_save) BEFORE the writer starts: a fast
        # writer could otherwise finish and stamp write_ms into the
        # PREVIOUS save's record right as this one replaces it.
        record = self._account(plan, t0, background=True)
        self._writer = threading.Thread(
            target=self._writer_main, args=(plan, record), daemon=True,
            name=f"ckpt-writer-{kind}-{plan.step}",
        )
        self._writer.start()
        return self._clear_dirty(state), plan.path

    def _writer_main(self, plan: _SavePlan, record: Dict[str, Any]) -> None:
        try:
            if self.on_write is not None:
                self.on_write(plan.path)  # test seam (crash/overlap tests)
            t0 = time.perf_counter()
            t0w = time.time()
            self._write_plan(plan)  # noqa: DRT004 — single-writer invariant: _save_async drains the previous writer, readers wait() first
            record["write_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
            # obs timeline span: the background npz+manifest write — the
            # "checkpoint writer" track of the train→delta→serve trace
            # (no-op unless DEEPREC_TRACE is configured)
            from deeprec_tpu.obs import trace as obs_trace

            obs_trace.phase_span(f"ckpt_write_{plan.kind}", t0w,
                                 time.time(), cat="train")
            if plan.kind == "full":
                self._force_full = False  # chain re-anchored durably
        except BaseException as e:  # surfaced by wait()/next save/restore
            self._writer_err = (e, plan.kind)

    def wait(self) -> None:
        """Drain the in-flight async save, if any. Until this returns the
        checkpoint is not durable (no manifest). Re-raises a writer
        failure — after which the half-written dir has no manifest and is
        invisible to restore, exactly like a crash. A failed INCREMENTAL
        writer additionally escalates the next save to FULL: that delta's
        dirty bits were already cleared on the training thread, so only a
        full re-anchor can put its rows back in the chain."""
        t = getattr(self, "_writer", None)
        if t is not None:
            t.join()
            self._writer = None
        err = getattr(self, "_writer_err", None)
        self._writer_err = None
        if err is not None:
            e, kind = err
            if kind == "incr":
                self._force_full = True
            raise RuntimeError(f"async checkpoint writer failed: {e}") from e

    def close(self) -> None:
        self.wait()

    def _effective_kind(self, kind: str) -> str:
        if kind != "incr":
            return kind
        if getattr(self, "_force_full", False):
            return "full"  # see wait(): a lost delta voids the incr chain
        if self._chain_has_gap():
            # A consumer quarantined a corrupt/torn link newer than the
            # latest full: deltas past the gap can never replay, so the
            # next save must re-anchor the chain (self-healing contract,
            # same semantics as the failed-incr-writer escalation).
            return "full"
        return kind

    # ------------------------------------------------------- save halves

    @scopes.host_spanned(scopes.CKPT_SAVE)
    def _save(self, state: TrainState, kind: str) -> Tuple[TrainState, str]:
        self.wait()  # serialize behind any in-flight async save
        kind = self._effective_kind(kind)
        t0 = time.perf_counter()
        plan = self._stage(state, kind, snapshot=False)
        self._write_plan(plan)
        if kind == "full":
            self._force_full = False
        self._account(plan, t0, background=False)
        return self._clear_dirty(state), plan.path

    def _account(self, plan: _SavePlan, t0: float,
                 background: bool) -> Dict[str, Any]:
        stall = (time.perf_counter() - t0) * 1e3
        self.ckpt_stall_ms = getattr(self, "ckpt_stall_ms", 0.0) + stall
        self.last_save = {
            "kind": plan.kind, "path": plan.path, "async": background,
            "stall_ms": round(stall, 3), **plan.stats,
        }
        return self.last_save

    def _stage(self, state: TrainState, kind: str, snapshot: bool) -> _SavePlan:
        """Device half of a save: everything that must read the live state.
        With snapshot=True every carried array is a FRESH buffer (device
        copies / jit outputs), so the plan stays valid while the training
        loop donates the live state through subsequent dispatches."""
        step = int(state.step)
        path = os.path.join(self.dir, f"{kind}-{step}")
        # The manifest at this path is about to change (clear + rewrite);
        # drop any cached copy so a later restore() on this manager
        # validates against the new one.
        getattr(self, "_manifest_cache", {}).pop(path, None)
        self._verified.discard(path)
        write = self._is_writer()
        parts = self._use_parts()
        positions = (
            {name: r.save() for name, r in self.datasets.items()}
            if self.datasets else None
        )
        incr = None
        snap_state = state
        if kind == "incr" and jax.process_count() > 1 and not parts:
            # Explicit sharded_io=False on a multi-process run: shards this
            # process cannot address have no device-local block to compact.
            # Keep the legacy gathered export (process_allgather + host
            # dirty mask) — correctness over the transfer diet here.
            transfer = _tree_bytes(state.tables)
        elif kind == "incr":
            incr, transfer = self._stage_incr(state)
            snap_state = None
        elif snapshot:
            snap_state = TrainState(
                step=state.step, tables=_copy_tree(state.tables),
                dense=state.dense, opt_state=state.opt_state,
            )
            transfer = _tree_bytes(snap_state.tables)
        else:
            transfer = _tree_bytes(state.tables)
        dense = _copy_tree(state.dense) if snapshot else state.dense
        opt = _copy_tree(state.opt_state) if snapshot else state.opt_state
        transfer += _tree_bytes(dense) + _tree_bytes(opt)
        if snapshot:
            _prefetch_host(snap_state.tables if snap_state is not None else incr)
            _prefetch_host((dense, opt))
        return _SavePlan(
            path=path, kind=kind, step=step, parts=parts, write=write,
            state=snap_state, incr=incr, dense=dense, opt_state=opt,
            positions=positions, stats={"transfer_bytes": int(transfer)},
            routing={
                bname: self._routing_fp(bname)
                for bname in self.trainer.bundles
            },
        )

    def _routing_fp(self, bname: str) -> str:
        """The trainer's active routing fingerprint for one bundle —
        "uniform" for plan-less trainers (and every pre-placement
        checkpoint, whose manifest has no routing record at all)."""
        fn = getattr(self.trainer, "routing_fingerprint", None)
        return fn(bname) if fn is not None else "uniform"

    @staticmethod
    def _savez(digests: Dict[str, Dict[str, str]], path: str, fname: str,
               arrays: Dict[str, np.ndarray]) -> None:
        """np.savez + per-array digest recording: the digests land in the
        manifest (written LAST), so any committed checkpoint carries the
        checksums `verify()` replays. Digests are computed from the exact
        arrays handed to np.savez — what's on disk must hash to this."""
        arrays = {k: np.asarray(v) for k, v in arrays.items()}
        np.savez(os.path.join(path, fname), **arrays)
        digests[fname] = {k: _array_digest(v) for k, v in arrays.items()}

    @not_thread_safe
    def _write_plan(self, plan: _SavePlan) -> None:
        """Host half of a save: materialize, write npz files, commit the
        manifest LAST (completeness marker), GC. Runs on the caller (sync)
        or the writer thread (async — single-process only, so every
        `_sync` below is a no-op there). @not_thread_safe: it mutates the
        manager's bookkeeping (digest memo, GC state, the checkpoint dir
        itself) with no lock — the single-writer invariant (at most one
        writer thread in flight, `_save_async` drains the previous one and
        every read path calls `wait()` first) is the serialization."""
        path, kind, step = plan.path, plan.kind, plan.step
        write, parts = plan.write, plan.parts
        digests: Dict[str, Dict[str, str]] = {}
        try:
            if write or parts or self.datasets:
                os.makedirs(path, exist_ok=True)
            if parts:
                # Pod-scale path: every process writes ONLY its addressable
                # shards' rows — no process_allgather, no host ever holds a
                # table it doesn't own a shard of.
                #
                # A crashed earlier attempt at this step (no manifest written)
                # can leave part files behind — including pids beyond this
                # run's process_count after an elastic downscale, or gathered
                # single files from a pre-rescale save that would shadow the
                # fresh parts on restore. Restore globs part*.npz, so stale
                # files would be silently merged: the writer clears the
                # manifest FIRST (so a crash mid-clear/mid-write leaves an
                # incomplete dir that _list() ignores, not a dir that
                # restores empty), then every table file, behind a barrier,
                # before anyone writes.
                pid = jax.process_index()
                if write:
                    import glob as _glob
                    mf = os.path.join(path, "manifest.json")
                    if os.path.exists(mf):
                        os.remove(mf)
                    # table_*.npz matches gathered AND .partNNNNN.npz
                    # files; stale dataset positions (e.g. pids beyond a
                    # downscaled topology) must go too, or a later wider
                    # restore rewinds readers to a dead run's offsets
                    for stale in _glob.glob(
                        os.path.join(path, "table_*.npz")
                    ) + _glob.glob(
                        os.path.join(path, "datasets.part*.json")
                    ):
                        os.remove(stale)
                self._sync(f"ckpt-{kind}-{step}-clear")
                for bname in self.trainer.bundles:
                    exported = (
                        self._assemble_incr(plan, bname, parts=True)
                        if kind == "incr"
                        else self._export_bundle_parts(plan.state, bname, False)
                    )
                    for tag, arrays in exported.items():
                        # Digest the writer process's OWN part files; other
                        # processes' parts are covered by the part-count
                        # check in _iter_part_rows, not by checksums.
                        self._savez(
                            digests, path,
                            f"table_{bname}_{tag}.part{pid:05d}.npz", arrays,
                        )
                self._write_positions(path, plan.positions)
                # The manifest is the completeness marker (_list() ignores
                # dirs without one): it must not exist until every process
                # has finished writing its part files AND dataset positions.
                self._sync(f"ckpt-{kind}-{step}-parts")
            else:
                for bname in self.trainer.bundles:
                    exported = (
                        self._assemble_incr(plan, bname, parts=False)
                        if plan.incr is not None
                        # plan.incr None + kind incr = the multi-process
                        # gathered fallback: legacy host-side dirty mask
                        else self._export_bundle(
                            plan.state, bname, kind == "incr"
                        )
                    )
                    for tag, arrays in exported.items():
                        if write:
                            self._savez(
                                digests, path, f"table_{bname}_{tag}.npz",
                                arrays,
                            )
            if not parts:
                # parts mode wrote positions before its pre-manifest
                # barrier above; the gathered path writes them here.
                self._write_positions(path, plan.positions)
                self._sync(f"ckpt-{kind}-{step}-datasets")
            if write:
                self._savez(digests, path, "dense.npz",
                            _tree_to_npz_dict(plan.dense))
                self._savez(digests, path, "opt.npz",
                            _tree_to_npz_dict(plan.opt_state))
                manifest = {"step": step, "kind": kind, "digests": digests,
                            "routing": plan.routing}
                if parts:
                    manifest["format"] = "parts"
                    manifest["parts"] = jax.process_count()
                    manifest["num_shards"] = self.trainer.num_shards
                if kind == "incr":
                    # Chain linkage: the step of the save this delta applies
                    # over. Restore walks base-links from the full anchor —
                    # a delta whose base is missing (quarantined or deleted
                    # middle link) sits beyond a gap and must not replay.
                    manifest["base"] = self._chain_tip(before=step)
                if kind == "full":
                    manifest["bundles"] = {
                        bn: [f.name for f in b.features]
                        for bn, b in self.trainer.bundles.items()
                    }
                # Atomic manifest commit: a crash mid-write must leave NO
                # manifest (dir invisible), never a torn one.
                mtmp = os.path.join(path, ".manifest.json.tmp")
                with open(mtmp, "w") as f:
                    json.dump(manifest, f)
                os.replace(mtmp, os.path.join(path, "manifest.json"))
                # GC after BOTH kinds: full saves age out old fulls, and
                # either kind sweeps incr dirs orphaned by an aged-out base.
                self._gc()
        finally:
            # The barrier must be reached even if the writer's I/O raises:
            # without it every other process blocks in sync_global_devices
            # forever. (A writer error mid-export still mismatches the
            # remaining gathers — that fails loudly at the runtime level,
            # which beats a silent deadlock.)
            self._sync(f"ckpt-{kind}-{step}")

    def _write_positions(self, path: str,
                         positions: Optional[Dict[str, dict]]) -> None:
        """Every process writes its OWN readers' positions
        (dataset-state-in-checkpoint, KafkaDataset parity). The positions
        were snapshotted at STAGE time — an async writer must record where
        the readers were when the checkpointed state was captured, not
        wherever the still-running training loop has advanced them to."""
        if not positions:
            return
        dpath = os.path.join(
            path, f"datasets.part{jax.process_index():05d}.json"
        )
        with open(dpath, "w") as f:
            json.dump(positions, f)

    # ------------------------------------------------------------- restore

    def _list(self, kind: str) -> List[int]:
        pat = re.compile(rf"^{kind}-(\d+)$")
        out = []
        for d in os.listdir(self.dir):
            m = pat.match(d)
            if m and os.path.exists(os.path.join(self.dir, d, "manifest.json")):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_full(self) -> Optional[int]:
        fulls = self._list("full")
        return fulls[-1] if fulls else None

    # ------------------------------------------- chain integrity (verify)

    def _chain_tip(self, before: Optional[int] = None) -> int:
        """Step of the newest committed link the next delta applies over:
        the latest full plus any newer deltas (-1 when the dir is empty).
        `before` bounds the scan to steps < before (the save being written
        must not see itself)."""
        steps = self._list("full") + self._list("incr")
        if before is not None:
            steps = [s for s in steps if s < before]
        return max(steps, default=-1)

    def _verify_quiet(self, path: str) -> Optional[str]:
        """Integrity-check one committed checkpoint dir against its
        manifest digests. Returns None when intact, else a reason string.
        Covers: torn/unparseable manifest, missing files, npz that fail to
        read (truncation tears the zip), and per-array digest mismatches
        (payload bit-flips). Dirs without digests (pre-checksum saves)
        verify their files are at least readable. Results are memoized —
        committed files are immutable, so each dir pays the read once."""
        if path in self._verified:
            return None
        try:
            with open(os.path.join(path, "manifest.json")) as f:
                manifest = json.load(f)
        except OSError as e:
            return f"manifest unreadable: {e}"
        except ValueError as e:
            return f"manifest torn: {e}"
        digests = manifest.get("digests")
        if digests:
            for fname, arrays in digests.items():
                fpath = os.path.join(path, fname)
                if not os.path.exists(fpath):
                    return f"{fname}: missing from committed checkpoint"
                try:
                    with np.load(fpath) as z:
                        names = set(z.files)
                        for aname, want in arrays.items():
                            if aname not in names:
                                return f"{fname}:{aname}: array absent"
                            got = _array_digest(z[aname])
                            if got != want:
                                return (f"{fname}:{aname}: digest mismatch "
                                        f"({got} != recorded {want})")
                except Exception as e:  # zip CRC / truncation / bad header
                    return f"{fname}: unreadable ({type(e).__name__}: {e})"
        self._verified.add(path)
        return None

    def verify(self, path: str) -> None:
        """Raise CheckpointCorrupt if `path` fails integrity checks."""
        err = self._verify_quiet(path)
        if err is not None:
            raise CheckpointCorrupt(f"checkpoint {path}: {err}")

    def quarantine(self, path: str, reason: str) -> Optional[str]:
        """Move a corrupt/torn dir out of the chain namespace (rename to
        `*.quarantined[.N]`) so every consumer — this process and any
        other sharing the FS — stops seeing it as a chain link. Returns
        the new path, or None if a racing consumer quarantined it first.
        The rename is the signal the TRAINER self-heals from: a
        quarantined step newer than the latest full means the delta chain
        has a gap, and `_effective_kind` escalates the next save to full."""
        dst = path + ".quarantined"
        i = 1
        while os.path.exists(dst):
            dst = f"{path}.quarantined.{i}"
            i += 1
        try:
            os.rename(path, dst)
        except OSError:
            return None  # another consumer won the rename race
        self.quarantine_count += 1
        self.last_quarantined = dst
        getattr(self, "_manifest_cache", {}).pop(path, None)
        self._verified.discard(path)
        _log.warning("checkpoint quarantined: %s -> %s (%s)",
                     path, dst, reason)
        return dst

    def valid_chain(self) -> Tuple[List[str], int]:
        """The longest verified full+delta chain, quarantining any corrupt
        link it finds. Returns (dir paths in replay order, tip step).

        Walk: newest intact full, then deltas in step order while (a) each
        verifies and (b) its manifest `base` links to the previous step —
        a corrupt delta is quarantined and truncates the chain there; a
        base mismatch (missing middle link) truncates WITHOUT quarantining
        the later, intact-but-unusable deltas. A corrupt full falls back
        to the next-older full. Raises FileNotFoundError when no intact
        full exists."""
        excluded: set = set()
        while True:
            fulls = [s for s in self._list("full") if s not in excluded]
            if not fulls:
                raise FileNotFoundError(
                    f"no intact full checkpoint under {self.dir}"
                )
            fs = fulls[-1]
            fpath = os.path.join(self.dir, f"full-{fs}")
            err = self._verify_quiet(fpath)
            if err is not None:
                self.quarantine(fpath, err)
                excluded.add(fs)
                continue
            chain, prev = [fpath], fs
            for s in self._list("incr"):
                if s <= fs:
                    continue
                p = os.path.join(self.dir, f"incr-{s}")
                err = self._verify_quiet(p)
                if err is not None:
                    self.quarantine(p, err)
                    break  # later deltas sit beyond the gap
                base = self._manifest(p).get("base")
                if base is not None and base != prev:
                    break  # missing middle link: stop, keep later dirs
                chain.append(p)
                prev = s
            return chain, prev

    def chain_dirs(self) -> List[str]:
        """Basenames of the current valid chain (serving poll contract:
        corrupt links are quarantined as a side effect, never returned).
        Empty when no intact full exists yet."""
        try:
            chain, _ = self.valid_chain()
        except FileNotFoundError:
            return []
        return [os.path.basename(p) for p in chain]

    def _chain_has_gap(self) -> bool:
        """True when a quarantined dir's step is newer than the latest
        intact full — the delta chain is missing a link only a full
        re-anchor can repair. Checked by `_effective_kind` on every save,
        so a quarantine by ANY consumer of the shared FS (e.g. the serving
        process) escalates this trainer's next save to full."""
        fulls = self._list("full")
        latest = fulls[-1] if fulls else -1
        pat = re.compile(r"^(?:full|incr)-(\d+)\.quarantined")
        try:
            names = os.listdir(self.dir)
        except OSError:
            return False
        return any(
            (m := pat.match(d)) is not None and int(m.group(1)) > latest
            for d in names
        )

    @scopes.host_spanned(scopes.CKPT_RESTORE)
    def restore(self, template: Optional[TrainState] = None,
                chunk: Optional[int] = None) -> TrainState:
        """Latest full checkpoint + all newer deltas, onto the trainer's
        CURRENT topology (mesh size / process count / capacity may all
        differ from save time — this is the elastic-rescale mechanism).
        Sharded multi-process trainers stream per-shard: each process reads
        the row files and keeps only keys its shards own — no global
        gather, no host-side global materialization.

        `chunk` (serving restores) imports rows in fixed-size slices so
        the import program has ONE static shape across every reload —
        ignored on the sharded streaming path, which already imports
        file-sized chunks and runs off the serving hot path."""
        self.wait()  # an in-flight async save must land (or fail) first
        if not self._list("full"):
            raise FileNotFoundError(f"no full checkpoint under {self.dir}")
        # Verified chain: corrupt or torn links are quarantined and the
        # restore falls back to the longest valid prefix — a bad delta
        # (or even a bad full) degrades to an older consistent state, it
        # never raises into the caller as a parse/shape error.
        chain, step = self.valid_chain()
        self._restore_datasets(chain)
        if self._is_sharded() and (
            jax.process_count() > 1 or self._use_parts()
        ):
            return self._restore_streaming(template, chain, step)
        state = template if template is not None else self.trainer.init(0)
        for path in chain:
            state = self._apply_ckpt(state, path, load_dense=True,
                                     chunk=chunk)
        return TrainState(
            step=jnp.asarray(step, jnp.int32),
            tables=state.tables,
            dense=state.dense,
            opt_state=state.opt_state,
        )

    def warm_replay(self, state: TrainState, chunk: int) -> None:
        """Compile the delta-replay programs — the chunked row import and
        the keep-mask prune rebuild — against `state`'s table shapes, so
        the FIRST live replay (poll_updates under traffic) is pure
        cache-hit dispatch instead of a GIL-held trace. The dummy import
        uses empty-key sentinel rows, inert by construction; all outputs
        are discarded. Single-host layouts only (sharded streaming
        restores run off the serving path)."""
        from deeprec_tpu.embedding.table import empty_key

        for bname, b in self.trainer.bundles.items():
            ts = state.tables[bname]
            sub = jax.tree.map(lambda a: a[0], ts) if b.stacked else ts
            keys_np = np.asarray(sub.keys)
            if keys_np.ndim != 1:
                continue
            cfg = b.table.cfg
            rows = {
                "keys": np.full((chunk,), empty_key(cfg), keys_np.dtype),
                "values": np.zeros((chunk, cfg.dim), np.float32),
                "freqs": np.zeros((chunk,), np.int32),
                "versions": np.zeros((chunk,), np.int32),
            }
            for sname, arr in sub.slots.items():
                if is_per_row("slot:" + sname):
                    a = np.asarray(arr)
                    rows["slot:" + sname] = np.zeros(
                        (chunk,) + a.shape[1:], np.float32
                    )
            out = import_rows(b.table, sub, rows, strict=False, chunk=chunk)
            fills = self.trainer._slot_fills(b)
            jax.block_until_ready(_rebuild_keep_jit(
                b.table, sub, jnp.ones(keys_np.shape, bool), fills
            ))
            jax.block_until_ready(out)

    def restore_into(self, state: TrainState, path: str,
                     chunk: Optional[int] = None,
                     load_dense: bool = True) -> TrainState:
        """Replay ONE checkpoint dir (full or incr) onto `state` and
        return the resulting TrainState — the shadow-copy building block
        of zero-stall serving updates (Predictor.poll_updates).

        Contract: the input `state` is NEVER mutated — all updates are
        functional (fresh arrays), so a reader holding the old reference
        keeps serving a complete, consistent model while the caller
        assembles the next one; the caller publishes the returned state
        with one atomic reference swap. The replayed result is
        bit-identical on table contents to applying the same dir in
        place (pinned by tests/test_serving_update.py). The returned
        step advances to the dir's manifest step (never backwards)."""
        out = self._apply_ckpt(state, path, load_dense=load_dense,
                               chunk=chunk)
        step = int(state.step)
        mf = os.path.join(path, "manifest.json")
        if os.path.exists(mf):
            with open(mf) as f:
                step = max(step, json.load(f)["step"])
        return TrainState(
            step=jnp.asarray(step, jnp.int32),
            tables=out.tables,
            dense=out.dense,
            opt_state=out.opt_state,
        )

    def _restore_datasets(self, chain: List[str]) -> None:
        """Rewind registered input readers to the NEWEST chain dir that
        carries this process's dataset positions. Missing files (pre-
        datasets checkpoints, or a rescaled topology) are skipped — the
        model state still restores; data rebalancing across topologies is
        the WorkQueue's job."""
        if not self.datasets:
            return
        fname = f"datasets.part{jax.process_index():05d}.json"
        for path in reversed(chain):
            p = os.path.join(path, fname)
            if not os.path.exists(p):
                continue
            with open(p) as f:
                saved = json.load(f)
            for name, reader in self.datasets.items():
                if name in saved:
                    reader.restore(saved[name])
            return

    @staticmethod
    def _get_member(sub, m):
        """Member m's view of a (possibly stacked) local table state."""
        return jax.tree.map(lambda a: a[m], sub) if m is not None else sub

    @staticmethod
    def _set_member(sub, new, m):
        """Write member m's updated state back into the stacked local state."""
        if m is None:
            return new
        return jax.tree.map(lambda a, u: a.at[m].set(u), sub, new)

    def _restore_streaming(
        self, template: Optional[TrainState], chain: List[str], step: int
    ) -> TrainState:
        """Pod-scale restore for sharded trainers: per checkpoint dir, each
        process streams row files one at a time, routes keys by hash to the
        shards it owns, and imports into host-local per-shard states built
        from its addressable template shards. Reads either format (parts or
        legacy gathered files) and any save topology; the result is
        assembled directly into global arrays, shard by shard."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deeprec_tpu.embedding import filters as _filters
        from deeprec_tpu.parallel.mesh import put_global

        tr = self.trainer
        N = tr.num_shards
        state = template if template is not None else tr.init(0)
        mesh = tr.mesh
        out_tables = {}
        for bname, b in tr.bundles.items():
            ts = state.tables[bname]
            k = self._shard_axis(bname)
            owned = self._owned_ids(ts.keys, k)
            members = list(range(len(b.features))) if b.stacked else [None]
            # Host-local owned-shard states (leaves keep the member axis for
            # stacked bundles, shard axis dropped).
            local = {
                s: jax.tree.map(
                    lambda leaf, s=s: jnp.asarray(self._local_block(leaf, k, s)),
                    ts,
                )
                for s in owned
            }
            cbf = b.table.cfg.ev.cbf_filter
            for path in chain:
                # Exact per-shard sketch reuse needs save-time ROUTING to
                # match, not just the shard count (see _import_local) —
                # manifests without a routing record predate plans and
                # routed uniformly.
                sketch_exact_ok = (
                    self._manifest(path).get("routing", {})
                    .get(bname, "uniform") == self._routing_fp(bname)
                )
                for m in members:
                    tag = f"t{m}" if m is not None else "t"
                    live_chunks: List[np.ndarray] = []
                    exact_sketch: Dict[int, np.ndarray] = {}
                    # CBF re-shard fallback: rows imported this dir, per shard
                    resharded_rows: Dict[int, List[Tuple[np.ndarray, np.ndarray]]] = {}
                    seen_any = False
                    is_incr = os.path.basename(path).startswith("incr-")
                    for rows in self._iter_part_rows(path, bname, tag):
                        seen_any = True
                        rows.pop("partition_offset", None)
                        sids = rows.pop("shard_ids", None)
                        save_n = int(np.asarray(rows.pop("num_shards", -1)))
                        lv = rows.pop("live_keys", None)
                        if lv is not None:
                            live_chunks.append(np.asarray(lv))
                        bp = rows.pop("bloom_parts", None)
                        rows.pop("bloom", None)  # legacy merged sketch
                        if bp is not None:
                            if sids is None:  # legacy gathered file
                                sids = np.arange(bp.shape[0])
                                save_n = bp.shape[0]
                            if save_n == N and sketch_exact_ok:
                                for i, sid in enumerate(np.asarray(sids)):
                                    if int(sid) in local:
                                        exact_sketch[int(sid)] = bp[i]
                        keys = rows["keys"]
                        if keys.shape[0] == 0:
                            continue
                        owner = self._restore_owner(bname, m, keys, N)
                        for s in owned:
                            sel = owner == s
                            if not sel.any():
                                continue
                            shard_rows = {
                                kk: (vv[sel] if is_per_row(kk) else vv)
                                for kk, vv in rows.items()
                            }
                            sub = local[s]
                            subm = self._get_member(sub, m)
                            subm = import_rows(b.table, subm, shard_rows)
                            if cbf is not None and subm.bloom is not None:
                                resharded_rows.setdefault(s, []).append(
                                    (shard_rows["keys"], shard_rows["freqs"])
                                )
                            local[s] = self._set_member(sub, subm, m)
                    if not seen_any:
                        continue
                    # Sketch restore: exact per-shard parts when the save
                    # topology matches; otherwise rebuild from the rows each
                    # shard imported this dir (same fallback semantics as
                    # _import_local — sub-threshold-only keys restart).
                    if cbf is not None:
                        for s in owned:
                            sub = local[s]
                            subm = self._get_member(sub, m)
                            if subm.bloom is None:
                                continue
                            if s in exact_sketch:
                                subm = subm.replace(
                                    bloom=jnp.asarray(
                                        exact_sketch[s], jnp.int32
                                    )
                                )
                            elif s in resharded_rows:
                                bloom = jnp.zeros_like(subm.bloom)
                                ks = np.concatenate(
                                    [p[0] for p in resharded_rows[s]]
                                )
                                fs = np.concatenate(
                                    [p[1] for p in resharded_rows[s]]
                                )
                                bloom, _ = _filters.cbf_add(
                                    cbf, bloom, jnp.asarray(ks),
                                    jnp.asarray(fs, jnp.int32),
                                )
                                subm = subm.replace(bloom=bloom)
                            local[s] = self._set_member(sub, subm, m)
                    if is_incr and live_chunks:
                        live = np.concatenate(live_chunks)
                        fills = tr._slot_fills(b)
                        for s in owned:
                            sub = local[s]
                            subm = self._get_member(sub, m)
                            keep = jnp.asarray(
                                np.isin(np.asarray(subm.keys), live)
                            )
                            subm = b.table.rebuild(
                                subm, keep=keep, slot_fills=fills
                            )
                            local[s] = self._set_member(sub, subm, m)
            # Assemble global arrays: each process contributes exactly its
            # owned shards via the callback (only addressable indices are
            # ever requested).
            sh = NamedSharding(mesh, tr._table_spec(bname))
            leaves_t, treedef = jax.tree_util.tree_flatten(ts)
            local_leaves = {
                s: jax.tree_util.tree_flatten(local[s])[0] for s in owned
            }

            def mk(i, gl):
                def cb(idx):
                    s = idx[k].start or 0
                    return np.expand_dims(
                        np.asarray(local_leaves[s][i]), axis=k
                    )

                return jax.make_array_from_callback(gl.shape, sh, cb)

            out_tables[bname] = jax.tree_util.tree_unflatten(
                treedef, [mk(i, gl) for i, gl in enumerate(leaves_t)]
            )
        # Dense/opt/step are replicated; the writer's npz is read by every
        # process off the shared FS (tiny next to the tables).
        dense, opt_state = state.dense, state.opt_state
        for path in chain:
            dpath = os.path.join(path, "dense.npz")
            if os.path.exists(dpath):
                dense = _tree_from_npz_dict(state.dense, np.load(dpath))
            opath = os.path.join(path, "opt.npz")
            if os.path.exists(opath):
                opt_state = _tree_from_npz_dict(
                    state.opt_state, np.load(opath)
                )
        repl = NamedSharding(mesh, P())
        return TrainState(
            step=put_global(jnp.asarray(step, jnp.int32), repl),
            tables=out_tables,
            dense=jax.tree.map(
                lambda t, a: put_global(np.asarray(a), repl), state.dense, dense
            ),
            opt_state=jax.tree.map(
                lambda t, a: put_global(np.asarray(a), repl),
                state.opt_state, opt_state,
            ),
        )

    @staticmethod
    def _part_files(path: str, bname: str, tag: str) -> List[str]:
        import glob as _glob

        return sorted(
            _glob.glob(os.path.join(path, f"table_{bname}_{tag}.part*.npz"))
        )

    def _manifest(self, path: str) -> dict:
        """The dir's manifest, cached per path (restore re-enters per
        bundle × member × chain dir; don't re-parse each time)."""
        cache = getattr(self, "_manifest_cache", None)
        if cache is None:
            cache = self._manifest_cache = {}
        if path not in cache:
            try:
                with open(os.path.join(path, "manifest.json")) as f:
                    cache[path] = json.load(f)
            except OSError:
                cache[path] = {}  # pre-manifest legacy dir
            except ValueError as e:
                # A manifest that EXISTS but doesn't parse is a torn write;
                # degrading to {} would disable exactly the stale/partial
                # validation this dir needs. Fail the dir instead.
                raise ValueError(
                    f"checkpoint {path}: manifest.json exists but is "
                    f"unparseable ({e}) — torn save; refusing to restore"
                )
        return cache[path]

    def _iter_part_rows(self, path: str, bname: str, tag: str):
        """Yield row dicts for one table from a checkpoint dir, one file at
        a time (bounded memory) — a single gathered file or N part files.
        Validates the part-file count against the manifest so a stale or
        partial save fails loudly instead of merging duplicate rows. Zero
        files is tolerated only for bundles the manifest doesn't declare
        (restoring a checkpoint that predates a newly added table)."""
        mf = self._manifest(path)
        single = os.path.join(path, f"table_{bname}_{tag}.npz")
        # In a parts-format dir a gathered file can only be stale residue
        # (pre-rescale save at the same step) — never prefer it.
        if mf.get("format") != "parts" and os.path.exists(single):
            yield dict(np.load(single))
            return
        files = self._part_files(path, bname, tag)
        expected = mf.get("parts")
        declared = bname in mf.get("bundles", {})
        if expected is not None and len(files) != expected and (
            files or declared
        ):
            raise ValueError(
                f"checkpoint {path}: {len(files)} part files for table "
                f"{bname}/{tag} but manifest records {expected} — stale or "
                f"partial save; refusing to merge"
            )
        for pf in files:
            yield dict(np.load(pf))

    def _load_rows(self, path: str, bname: str, tag: str):
        """All row sources for one table merged into a single dict — the
        small-scale restore path (plain Trainer / single-process sharded),
        where holding one table's live rows on the host is fine."""
        chunks = list(self._iter_part_rows(path, bname, tag))
        if not chunks:
            return None
        if len(chunks) == 1:
            chunks[0].pop("shard_ids", None)
            chunks[0].pop("num_shards", None)
            return chunks[0]
        merged = {}
        for key in chunks[0]:
            if key in ("partition_offset", "shard_ids", "num_shards",
                       "bloom_parts"):
                continue
            merged[key] = (
                np.concatenate([c[key] for c in chunks])
                if is_per_row(key) or key == "live_keys"
                else chunks[0][key]
            )
        if "bloom_parts" in chunks[0]:
            # reassemble per-shard sketches in shard order so same-topology
            # restores stay exact regardless of which process wrote which part
            pairs = []
            for c in chunks:
                pairs.extend(zip(np.asarray(c["shard_ids"]).tolist(),
                                 c["bloom_parts"]))
            pairs.sort(key=lambda p: p[0])
            merged["bloom_parts"] = np.stack([b for _, b in pairs])
        return merged

    def _apply_ckpt(self, state: TrainState, path: str, load_dense: bool,
                    chunk: Optional[int] = None) -> TrainState:
        # Delta replays recur at serving cadence with a different row
        # count each time — bucket those to stabilize compiled shapes;
        # one-shot full restores import exact-size. A serving caller
        # passes `chunk` instead: ONE static import shape for full and
        # delta alike (see import_rows), so no replay ever traces a new
        # XLA program while requests are in flight.
        bucket = os.path.basename(path).startswith("incr-")
        mf_routing = self._manifest(path).get("routing", {})
        tables = dict(state.tables)
        for bname, b in self.trainer.bundles.items():
            ts = tables[bname]
            members = range(len(b.features)) if b.stacked else [None]
            new_members = []
            for k in members:
                tag = f"t{k}" if k is not None else "t"
                sub = jax.tree.map(lambda a: a[k], ts) if b.stacked else ts
                rows = self._load_rows(path, bname, tag)
                if rows is not None:
                    rows.pop("partition_offset", None)
                    live = rows.pop("live_keys", None)
                    sub = self._import_local(
                        b.table, sub, rows, bucket=bucket, chunk=chunk,
                        bname=bname, member=k,
                        sketch_exact_ok=(
                            mf_routing.get(bname, "uniform")
                            == self._routing_fp(bname)
                        ),
                    )
                    if live is not None:
                        # delta semantics: anything absent from the delta's
                        # live set was evicted since the previous save
                        sub = self._prune_to_live(b, sub, live)
                new_members.append(sub)
            if b.stacked:
                ts = jax.tree.map(lambda *xs: jnp.stack(xs), *new_members)
            else:
                ts = new_members[0]
            tables[bname] = ts
        dense, opt_state = state.dense, state.opt_state
        if load_dense and os.path.exists(os.path.join(path, "dense.npz")):
            dense = _tree_from_npz_dict(state.dense, np.load(os.path.join(path, "dense.npz")))
        if load_dense and os.path.exists(os.path.join(path, "opt.npz")):
            opt_state = _tree_from_npz_dict(
                state.opt_state, np.load(os.path.join(path, "opt.npz"))
            )
        return TrainState(step=state.step, tables=tables, dense=dense,
                          opt_state=opt_state)

    def _prune_to_live(self, b, sub: TableState, live: np.ndarray) -> TableState:
        """Drop keys not in the delta's live set (evicted between saves) —
        rebuild-based, so probe chains heal and freed optimizer slot rows
        restart at the optimizer's init value. Jit-wrapped with a stable
        cache key (table, fills): the old eager closure re-traced the
        rebuild probe loop on EVERY delta replay, a GIL-held stall at
        serving cadence (poll_updates) — now it compiles once per table
        shape and every later replay is cache-hit dispatch."""
        from deeprec_tpu.embedding.table import empty_key

        fills = self.trainer._slot_fills(b)
        keys = np.asarray(sub.keys)
        # Nothing evicted since the previous save (every occupied key is in
        # the live set) -> the rebuild is an identity: skip it. Deltas at
        # serving cadence with stable key sets pay zero rebuild work.
        occupied_live = np.isin(keys, live) | (keys == empty_key(b.table.cfg))
        if occupied_live.all():
            return sub
        if keys.ndim == 2:  # sharded: [N, C_local]
            keep = np.stack([np.isin(k, live) for k in keys])
            return _rebuild_keep_sharded_jit(
                b.table, sub, jnp.asarray(keep), fills
            )
        return _rebuild_keep_jit(
            b.table, sub, jnp.asarray(np.isin(keys, live)), fills
        )

    def _restore_owner(self, bname, member, keys, N) -> np.ndarray:
        """Owner shard of restored keys: the trainer's ACTIVE placement
        plan when it carries one (ShardedTrainer.restore_owner), else the
        uniform hash. Routing by the live plan — not the hash, not the plan
        at save time — is what makes a checkpoint saved under plan A
        restore correctly into a trainer running plan B: each row lands on
        the shard where plan B's route will look it up."""
        fn = getattr(self.trainer, "restore_owner", None)
        if fn is not None and bname is not None:
            return np.asarray(fn(bname, member, keys), np.int32)
        return np.asarray(hashing.hash_shard(jnp.asarray(keys), N))

    def _import_local(self, table, sub: TableState, rows,
                      bucket: bool = False,
                      chunk: Optional[int] = None,
                      bname=None, member=None,
                      sketch_exact_ok: bool = True) -> TableState:
        """Import rows into a local (possibly shard-stacked) table state.

        `sketch_exact_ok` gates the per-shard exact CBF-sketch reuse: a
        saved sketch describes the rows save-time ROUTING put on that
        shard, so matching shard count alone is no longer enough — the
        caller compares the manifest's routing fingerprint against the
        restoring trainer's (a plan change falls back to rebuilding the
        sketches from the rows each shard actually imports)."""
        if self._is_sharded():
            N = self.trainer.num_shards
            owner = self._restore_owner(bname, member, rows["keys"], N)
            shards = []
            bloom_parts = rows.get("bloom_parts")
            same_topology = (
                bloom_parts is not None and bloom_parts.shape[0] == N
                and sketch_exact_ok
            )
            for s in range(N):
                sel = owner == s
                shard_rows = {
                    k: (v[sel] if is_per_row(k) else v)
                    for k, v in rows.items()
                    if k != "bloom_parts"
                }
                # Same shard count: each shard gets its own saved sketch back
                # (exact, sub-threshold counts included). Re-shard: rebuild
                # from owned rows' freqs — exact for admitted keys,
                # sub-threshold-only keys restart (documented semantic).
                # Never hand a summed global sketch to every shard: that
                # would inflate ~N× per save/restore cycle.
                shard_rows.pop("bloom", None)  # legacy merged-sketch files
                local = jax.tree.map(lambda a: a[s], sub)
                local = import_rows(table, local, shard_rows,
                                    bucket=bucket, chunk=chunk)
                cbf = table.cfg.ev.cbf_filter
                if cbf is not None and local.bloom is not None and same_topology:
                    local = local.replace(
                        bloom=jnp.asarray(bloom_parts[s], jnp.int32)
                    )
                elif cbf is not None and local.bloom is not None:
                    from deeprec_tpu.embedding import filters as _filters

                    bloom = jnp.zeros_like(local.bloom)
                    if shard_rows["keys"].shape[0] > 0:
                        bloom, _ = _filters.cbf_add(
                            cbf,
                            bloom,
                            jnp.asarray(shard_rows["keys"]),
                            jnp.asarray(shard_rows["freqs"], jnp.int32),
                        )
                    local = local.replace(bloom=bloom)
                shards.append(local)
            return jax.tree.map(lambda *xs: jnp.stack(xs), *shards)
        return import_rows(table, sub, rows, bucket=bucket, chunk=chunk)

    # ----------------------------------------------------------------- gc

    def _gc(self):
        if self.keep <= 0:
            return  # keep everything (legacy contract)
        fulls = self._list("full")
        for s in fulls[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"full-{s}"), ignore_errors=True)
        fulls = fulls[-self.keep:]
        if not fulls:
            return
        # Incr dirs whose base full aged out of `keep` are orphaned: a
        # delta at step s only ever replays over a full with step < s, and
        # the oldest such full left is fulls[0] — without this sweep a
        # long run accumulates unbounded incr directories between every
        # pair of long-dead fulls (deltas newer than a KEPT full stay:
        # they are that full's replay chain).
        for i in self._list("incr"):
            if i <= fulls[0]:
                shutil.rmtree(
                    os.path.join(self.dir, f"incr-{i}"), ignore_errors=True
                )
        # Quarantined dirs are kept for forensics while relevant, but age
        # out with the chain they broke (same bound as orphaned incrs).
        pat = re.compile(r"^(?:full|incr)-(\d+)\.quarantined")
        try:
            names = os.listdir(self.dir)
        except OSError:
            return
        for d in names:
            m = pat.match(d)
            if m and int(m.group(1)) <= fulls[0]:
                shutil.rmtree(os.path.join(self.dir, d), ignore_errors=True)
