"""Unified typed configuration tree.

DeepRec spreads configuration over three mechanisms — ConfigProto extensions
(/root/reference/tensorflow/core/protobuf/config.proto), dozens of env vars,
and per-EV option objects (tensorflow/python/ops/variables.py:180-300:
EmbeddingVariableOption / InitializerOption / GlobalStepEvict / L2WeightEvict /
StorageOption / CounterFilter / CBFFilter / CheckpointOption). Here everything
is one tree of frozen dataclasses, hashable so they can be passed as jit
static arguments.
"""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import Optional


class StorageType(enum.Enum):
    """Where a table's payload lives.

    Parity with the storage enum in
    /root/reference/tensorflow/core/framework/embedding/config.proto:10-25.
    On TPU the tiers collapse to: HBM (device arrays), DRAM (host store via
    the native KV lib), and HBM_DRAM (HBM working set + host overflow, the
    analog of DeepRec's HbmDramStorage). PMEM/SSD/LevelDB map onto the host
    tier's file-backed mode.
    """

    HBM = "hbm"
    DRAM = "dram"
    HBM_DRAM = "hbm_dram"
    # three-tier combo (hbm_dram_ssd_storage.h analog): device working set,
    # bounded host DRAM tier, log-structured disk tier below it
    HBM_DRAM_SSD = "hbm_dram_ssd"

    @classmethod
    def from_reference(cls, name) -> "StorageType":
        """Map any of the reference's 13 StorageType values — proto
        names OR field numbers (embedding/config.proto:5-27) — onto the
        TPU tiers, so configs
        written against DeepRec resolve without edits. The physical
        reality on a TPU-VM: compute reads come from HBM, the host has
        DRAM, and below that there is a filesystem — PMEM does not exist
        and LevelDB/SSDHASH are both \"a disk-backed log\", so
          * PMEM_* tiers map to the host DRAM tier,
          * SSDHASH / LEVELDB tiers map to the log-structured disk tier,
          * every multi-level combo keeps its LEVEL STRUCTURE with each
            level mapped as above (e.g. DRAM_PMEM -> HBM_DRAM: a fast
            working set over a larger colder store).
        """
        if isinstance(name, cls):
            return name
        # DeepRec's canonical config form is the proto ENUM VALUE (an int
        # in Python: config_pb2.StorageType.DRAM_SSDHASH == 12) — accept
        # the field numbers as well as the names.
        by_number = {
            0: "DEFAULT", 1: "DRAM", 2: "PMEM_MEMKIND", 3: "PMEM_LIBPMEM",
            4: "SSDHASH", 5: "LEVELDB", 6: "HBM", 11: "DRAM_PMEM",
            12: "DRAM_SSDHASH", 13: "HBM_DRAM", 14: "DRAM_LEVELDB",
            101: "DRAM_PMEM_SSDHASH", 102: "HBM_DRAM_SSDHASH",
        }
        if isinstance(name, int) and not isinstance(name, bool):
            if name not in by_number:
                raise ValueError(
                    f"unknown reference StorageType number {name}; known "
                    f"field numbers: {sorted(by_number)}"
                )
            name = by_number[name]
        key = str(name).strip().upper()
        table = {
            "DEFAULT": cls.HBM,
            "HBM": cls.HBM,
            "DRAM": cls.DRAM,
            "PMEM_MEMKIND": cls.DRAM,
            "PMEM_LIBPMEM": cls.DRAM,
            "SSDHASH": cls.HBM_DRAM_SSD,
            "LEVELDB": cls.HBM_DRAM_SSD,
            "DRAM_PMEM": cls.HBM_DRAM,
            "DRAM_SSDHASH": cls.HBM_DRAM_SSD,
            "HBM_DRAM": cls.HBM_DRAM,
            "DRAM_LEVELDB": cls.HBM_DRAM_SSD,
            "DRAM_PMEM_SSDHASH": cls.HBM_DRAM_SSD,
            "HBM_DRAM_SSDHASH": cls.HBM_DRAM_SSD,
        }
        if key in table:
            return table[key]
        try:  # our own value strings ("hbm_dram", ...)
            return cls(str(name).lower())
        except ValueError:
            raise ValueError(
                f"unknown storage type {name!r}; reference names "
                f"{sorted(table)} and native values "
                f"{[m.value for m in cls]} are accepted"
            ) from None


@dataclasses.dataclass(frozen=True)
class InitializerOption:
    """EV initializer semantics.

    DeepRec (docs/docs_en/Embedding-Variable.md "EV Initializer"): an
    initializer generates a [default_value_dim, dim] matrix; a new key k is
    assigned row (k % default_value_dim). `kind="stateless_normal"` is the
    TPU-native improvement: a per-key deterministic normal computed from the
    key hash — same statistical effect with no stored matrix and bitwise
    reproducibility across shards/restarts/growth.
    """

    kind: str = "stateless_normal"  # stateless_normal | matrix_normal | constant
    stddev: float = 0.05
    mean: float = 0.0
    constant: float = 0.0
    default_value_dim: int = 4096
    # Value served for keys blocked by an admission filter
    # (EmbeddingVariableOption.init.default_value_no_permission).
    default_value_no_permission: float = 0.0


@dataclasses.dataclass(frozen=True)
class CounterFilter:
    """Admit a feature only after it has been seen `filter_freq` times.

    Parity: tf.CounterFilter (variables.py:279) /
    counter_filter_policy.h. Until admission a key is tracked (frequency
    counter) but serves `default_value_no_permission` and receives no
    gradient updates.
    """

    filter_freq: int = 0


@dataclasses.dataclass(frozen=True)
class CBFFilter:
    """Counting-Bloom-filter admission: like CounterFilter but the counter
    lives in a compact sketch, and keys below threshold never occupy a table
    slot at all.

    Parity: tf.CBFFilter (variables.py:284) / bloom_filter_policy.h.
    """

    filter_freq: int = 0
    max_element_size: int = 1 << 20
    false_positive_probability: float = 0.01
    counter_bits: int = 16  # sketch counters saturate at 2^bits - 1

    def num_cells(self) -> int:
        # Standard Bloom sizing: m = -n ln p / (ln 2)^2, rounded up to pow2.
        m = -self.max_element_size * math.log(self.false_positive_probability) / (
            math.log(2.0) ** 2
        )
        return max(1024, 1 << int(math.ceil(math.log2(max(m, 1.0)))))

    def num_hashes(self) -> int:
        k = (self.num_cells() / max(self.max_element_size, 1)) * math.log(2.0)
        return max(1, min(8, int(round(k))))


@dataclasses.dataclass(frozen=True)
class GlobalStepEvict:
    """TTL eviction: drop keys not updated in the last `steps_to_live` steps.

    Parity: tf.GlobalStepEvict (variables.py:204) /
    globalstep_shrink_policy.h; spec docs/docs_en/Feature-Eviction.md.
    Runs at checkpoint/eviction time, not on the lookup hot path.
    """

    steps_to_live: int = 0


@dataclasses.dataclass(frozen=True)
class L2WeightEvict:
    """Drop keys whose embedding L2 norm is below threshold.

    Parity: tf.L2WeightEvict (variables.py:210) / l2weight_shrink_policy.h.
    """

    l2_weight_threshold: float = -1.0


@dataclasses.dataclass(frozen=True)
class StorageOption:
    """Multi-tier storage placement for one table.

    Parity: tf.StorageOption (variables.py:230). `capacity` bounds the HBM
    tier (slots); overflow keys spill to the host store when
    storage_type=HBM_DRAM (eviction by LFU/LRU on (freq, version)).
    """

    storage_type: StorageType = StorageType.HBM
    storage_path: Optional[str] = None
    cache_strategy: str = "lfu"  # lfu | lru
    # HBM_DRAM_SSD: max rows held in the host DRAM tier before the coldest
    # spill to the disk tier (0 = unbounded, disk tier unused)
    host_capacity: int = 0

    def __post_init__(self):
        # Accept reference StorageType names and plain strings (configs
        # written against DeepRec's enum resolve without edits).
        if not isinstance(self.storage_type, StorageType):
            object.__setattr__(
                self, "storage_type",
                StorageType.from_reference(self.storage_type),
            )


@dataclasses.dataclass(frozen=True)
class CheckpointOption:
    """Per-table checkpoint behavior — parity with tf.CheckpointOption
    (variables.py:217) / TF_EV_SAVE_FILTERED_FEATURES: full checkpoints
    normally keep sub-threshold (filter-blocked) keys so admission
    counters survive restarts; save_filtered_features=False drops them at
    save time (smaller serving-bound checkpoints, same effect as the
    shrink tool but at the source)."""

    save_filtered_features: bool = True


@dataclasses.dataclass(frozen=True)
class EmbeddingVariableOption:
    """Per-table feature bundle — parity with tf.EmbeddingVariableOption
    (variables.py:261)."""

    init: InitializerOption = InitializerOption()
    counter_filter: Optional[CounterFilter] = None
    cbf_filter: Optional[CBFFilter] = None
    global_step_evict: Optional[GlobalStepEvict] = None
    l2_weight_evict: Optional[L2WeightEvict] = None
    storage: StorageOption = StorageOption()
    ckpt: CheckpointOption = CheckpointOption()

    def __post_init__(self):
        if self.counter_filter is not None and self.cbf_filter is not None:
            raise ValueError("at most one admission filter per table")


def validate_unique_budget(ub, where: str) -> None:
    """Shared grammar check for the unique-budget knob — one definition
    for TableConfig and SparseFeature so the accepted forms can never
    diverge: None | "auto" | "off" | positive int."""
    if not (
        ub is None
        or ub in ("auto", "off")
        or (isinstance(ub, int) and not isinstance(ub, bool) and ub > 0)
    ):
        raise ValueError(
            f"{where}: unique_budget must be None, 'auto', 'off' or a "
            f"positive int, got {ub!r}"
        )


@dataclasses.dataclass(frozen=True)
class TableConfig:
    """Static configuration of one hash-embedding table.

    The analog of creating an EmbeddingVariable via tf.get_embedding_variable
    (variable_scope.py:2146): `dim` is the embedding width, `capacity` the
    fixed HBM slot count (power of two; DeepRec's tables grow dynamically —
    here growth is host-orchestrated rehash to a larger capacity, see
    table.grow()).
    """

    name: str
    dim: int
    capacity: int = 1 << 16
    key_dtype: str = "int32"  # int32 | int64 (int64 requires jax x64)
    # Residency dtype of the value rows. float32/bfloat16 are full
    # train+serve dtypes (bf16 writes stochastic-round). "int8" is a
    # SERVING-ONLY residency (train fp32, serve quantized): rows store as
    # int8 with a per-row fp32 scale (TableState.qscale), dequantized in
    # the lookup gather; checkpoint restore quantizes on import
    # (import_rows). Train-mode lookups on an int8 table raise — the
    # Predictor(quantize="int8") path is how this gets engaged.
    value_dtype: str = "float32"  # float32 | bfloat16 | int8 (serve-only)
    combiner: str = "mean"  # mean | sum | sqrtn
    max_probes: int = 64
    # Hot-path kernel choice: "xla" = plain gather/scatter ops, "pallas" =
    # the fused DMA kernels in ops/fused_lookup.py (row gather + stochastic-
    # rounded scatter), "auto" = whichever tools/bench_lookup.py crowned on
    # this hardware: pallas, measured faster on v5e wherever the kernels are
    # eligible (f32 tables, dim%128==0 — Mosaic HBM-tiling constraint); the
    # ops self-gate ineligible shapes back to XLA. Off-TPU every choice
    # falls back to identical-semantics XLA.
    kernel: str = "auto"  # auto | xla | pallas
    # Packed small-dim storage layout (ops/packed.py): "auto" packs only on
    # TPU, where the layout's rationale holds — XLA pads a [C, dim<128] f32
    # array's minor dim to 128 lanes, so packing saves 128/dim x HBM and
    # gather bandwidth. On CPU there is no lane padding and the pack/unpack
    # shuffle is pure overhead (measured: -36% DLRM train throughput on a CPU,
    # docs/perf.md), so "auto" resolves to unpacked there. "on"/"off" force it
    # either way (tests exercise the packed path on CPU via "on").
    packed: str = "auto"  # auto | on | off
    # Unique-budget for the budgeted dedup (ops/dedup.py): per lookup,
    # ids dedup to at most `unique_budget` uniques and EVERY downstream op
    # (probe, gather, freq/version scatters, init, backward segment-sum,
    # the sharded a2a/allgather payload) is sized at the budget instead of
    # the full flattened batch. Ids past the budget serve the
    # admission-blocked default for that step and count in the table's
    # `dedup_overflow` (the a2a_overflow contract).
    #   int    — fixed budget (real unique ids per lookup)
    #   "auto" — trainer-derived: capacity-clamped slack over an EMA of
    #            measured unique fractions (Trainer.update_budgets /
    #            maintain()); until the first measurement the lookup runs
    #            at U = N and seeds the EMA counters
    #   None   — legacy U = N sort-unique (logged once per table so the
    #            waste is visible); "off" the same, silently.
    unique_budget: Optional[object] = None  # None | "off" | "auto" | int
    # Wire format of the sharded TRAIN exchanges (ShardedTable): the value
    # payload of the allgather/psum_scatter and a2a embedding returns, and
    # the gradient payload of the backward exchange, are cast to this dtype
    # on the wire. "bfloat16" (default) halves ICI/collective bytes; the
    # owner side always accumulates segment-sums in fp32, and EVAL/serving
    # exchanges always ride exact fp32 regardless of this knob (a read-only
    # pass must reproduce resident rows exactly). Id payloads are ints and
    # unaffected.
    exchange_dtype: str = "bfloat16"  # bfloat16 | float32
    ev: EmbeddingVariableOption = EmbeddingVariableOption()

    def __post_init__(self):
        if self.capacity & (self.capacity - 1):
            raise ValueError(f"capacity must be a power of two, got {self.capacity}")
        if self.dim <= 0:
            raise ValueError("dim must be positive")
        if self.kernel not in ("auto", "xla", "pallas"):
            raise ValueError(f"unknown kernel {self.kernel!r}")
        if self.packed not in ("auto", "on", "off"):
            raise ValueError(f"unknown packed mode {self.packed!r}")
        if self.value_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"table {self.name}: value_dtype must be 'float32', "
                f"'bfloat16' or 'int8', got {self.value_dtype!r}"
            )
        if self.exchange_dtype not in ("bfloat16", "float32"):
            raise ValueError(
                f"table {self.name}: exchange_dtype must be 'bfloat16' or "
                f"'float32', got {self.exchange_dtype!r}"
            )
        validate_unique_budget(self.unique_budget, f"table {self.name}")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh layout: `dp` replicates the dense model / splits the batch,
    `mp` shards embedding tables (DeepRec CollectiveStrategy.embedding_scope
    analog, group_embedding_collective_strategy.py:68-86)."""

    dp: int = 1
    mp: int = 1
    axis_dp: str = "dp"
    axis_mp: str = "mp"


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Full + incremental checkpoint cadence — parity with
    MonitoredTrainingSession(save_checkpoint_secs=, save_incremental_checkpoint_secs=)
    (docs/docs_en/Incremental-Checkpoint.md)."""

    directory: str = "ckpt"
    save_steps: int = 1000
    incremental_save_steps: int = 0  # 0 disables incremental saves
    keep: int = 3
