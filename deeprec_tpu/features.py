"""Feature specs — the feature_column analog.

DeepRec models declare inputs via feature_column
(categorical_column_with_embedding, python/feature_column/feature_column_v2.py:2080,
embedding_column, numeric_column). Here a model takes a list of FeatureSpecs;
the trainer resolves sparse ones against hash-embedding tables and hands the
model pooled ([B, D]) or sequence ([B, L, D] + mask) embeddings.

Batches are plain dicts: sparse features as int id arrays [B] or [B, L] padded
with `pad_value`; dense features as float arrays [B, W]; the label under
`label`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from deeprec_tpu.config import TableConfig, validate_unique_budget


@dataclasses.dataclass(frozen=True)
class SparseFeature:
    """A categorical (id/multi-id) feature backed by a hash-embedding table.

    pooling: 'mean' | 'sum' | 'sqrtn' pool the bag to [B, D];
             'none' delivers the full sequence [B, L, D] plus mask (for
             attention models: DIN/DIEN/BST).
    shared_table: name of another SparseFeature whose table this one reuses
             (DeepRec shared_embedding_columns analog).
    max_len: optional declared bag length L. Features are auto-grouped for
             fused GroupEmbedding lookups only when their id shapes match;
             set distinct max_len values to keep differently-shaped features
             in separate groups.
    unique_budget: per-feature override of TableConfig.unique_budget (the
             dedup unique budget, ops/dedup.py): int fixed budget,
             "auto" trainer-derived, "off" to force the legacy U=N path,
             None (default) to inherit the table's setting. Features
             sharing a bundle resolve to the largest member budget.
    """

    name: str
    table: Optional[TableConfig] = None
    pooling: str = "mean"
    pad_value: int = -1
    shared_table: Optional[str] = None
    max_len: Optional[int] = None
    unique_budget: Optional[object] = None  # None | "off" | "auto" | int

    def __post_init__(self):
        if (self.table is None) == (self.shared_table is None):
            raise ValueError(
                f"{self.name}: exactly one of table/shared_table must be set"
            )
        validate_unique_budget(self.unique_budget, f"feature {self.name}")


@dataclasses.dataclass(frozen=True)
class DenseFeature:
    """A numeric feature column, passed through (models normalize as needed)."""

    name: str
    width: int = 1


def sparse_features(specs) -> list:
    return [f for f in specs if isinstance(f, SparseFeature)]


def dense_features(specs) -> list:
    return [f for f in specs if isinstance(f, DenseFeature)]


def table_configs(specs) -> dict:
    """Unique tables declared by a spec list (shared tables deduped)."""
    out = {}
    for f in sparse_features(specs):
        if f.table is not None:
            out[f.name] = f.table
    return out


def resolve_table_name(spec: SparseFeature) -> str:
    return spec.shared_table if spec.shared_table is not None else spec.name
