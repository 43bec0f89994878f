"""deeprec_tpu — a TPU-native sparse-recommendation training framework.

Brand-new JAX/XLA/Pallas implementation of the capability set of DeepRec
(Alibaba's TF-1.15 recommendation engine, studied read-only at
/root/reference/): dynamic hash-table embeddings with admission filters and
eviction, frequency-aware sparse optimizers, pod-sharded tables over ICI
collectives, staged input pipelines, full+incremental checkpointing, a
modelzoo and a serving path. See SURVEY.md for the blueprint.
"""
import time as _time

_IMPORT_T0 = _time.time(), _time.perf_counter()  # first: the import is timed

from deeprec_tpu.config import (  # noqa: E402
    CBFFilter,
    CheckpointConfig,
    CheckpointOption,
    CounterFilter,
    EmbeddingVariableOption,
    GlobalStepEvict,
    InitializerOption,
    L2WeightEvict,
    MeshConfig,
    StorageOption,
    StorageType,
    TableConfig,
)
from deeprec_tpu.embedding.table import EmbeddingTable, TableState, UniqueLookup
from deeprec_tpu.embedding.combiners import combine
from deeprec_tpu.features import DenseFeature, SparseFeature

__version__ = "0.1.0"

# The package's import as a part of set-up (jax's is inside it where the
# package is the first to import jax): deeprec_setup_seconds_total{stage="import"}.
from deeprec_tpu.obs import compile_log as _compile_log  # noqa: E402

_compile_log.record(_compile_log.SETUP, "import", _IMPORT_T0[0], _time.time(),
                    _time.perf_counter() - _IMPORT_T0[1])
