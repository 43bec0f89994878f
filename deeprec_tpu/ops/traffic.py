"""Embedding-engine traffic accounting: one model, asserted against reality.

The train-step hot path of a hash-embedding table is a fixed set of
gathers/scatters per unique id (docs/perf.md "Roofline methodology").  This
module is the single source of truth for that set, in two forms:

  * **Bytes** (`table_step_traffic`): per-table per-step HBM bytes of the
    engine plus, for sharded tables, the wire bytes of the collective
    exchange at a given wire dtype.  `tools/roofline.py` divides these by
    measured step time; `bench.py` records them as
    `engine_bytes_per_step` so a before/after is an artifact, not a claim.
  * **Op counts** (`expected_lookup_apply_ops`): how many stablehlo
    gather/scatter ops the single-table lookup+apply program should lower
    to.  `bench.py` measures the real counts off the lowered program
    (`count_stablehlo_ops`); `tools/roofline.py --assert-traffic` fails CI
    when model and measurement drift — so the model can never silently
    describe a hot path the code no longer runs.

Both forms carry a `diet` switch describing the pre/post state of the
traffic-diet PR (forward-residual reuse + fused metadata + dropped
apply-side re-stamps), which is how the "before" column of the accounting
stays reproducible after the "before" code is gone.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

META_COLS = 3  # freq / version / dirty, int32 each (embedding/table.py)


# ----------------------------------------------------------- imbalance model
#
# The wire terms below model the MEAN per-device exchange payload; under a
# uniform hash and zipf traffic the max shard does a multiple of that, and
# after the in-step pipelining PR the exchange straggler is exactly what
# bounds step time. These two helpers are the shared vocabulary between the
# placement cost model (parallel/placement.py), the live owner counters
# (Trainer.dedup_stats per_shard) and the bench/CI gate
# (`bench.py --placement`, `roofline.py --assert-imbalance`): everyone
# reports load as exchange bytes and skew as max/mean of that.


def exchange_row_bytes(
    *, dim: int, wire_bytes: int = 4, key_bytes: int = 4
) -> float:
    """Wire bytes ONE exchanged row costs its owner shard per step:
    embedding down + grad up at the wire dtype, plus the id + count int32
    ride-along. This is the per-arrival weight of the placement cost
    model and of the per-shard `exchange_bytes` telemetry."""
    return float(2 * dim * wire_bytes + key_bytes + 4)


def shard_imbalance(loads) -> float:
    """max/mean of a per-shard load vector — 1.0 is perfectly balanced,
    N is everything-on-one-shard. Defined as 1.0 for empty/zero loads
    (nothing exchanged is not skewed)."""
    import numpy as np

    l = np.asarray(loads, dtype=np.float64)
    if l.size == 0:
        return 1.0
    mean = float(l.mean())
    if mean <= 0.0:
        return 1.0
    return float(l.max()) / mean


# ------------------------------------------------- a2a budget model (plan v2)
#
# The a2a exchange buckets ids by destination with a static per-bucket
# budget. Placement v1 modeled the budget as hash-uniform spread
# (slack·U/N) plus one GLOBAL hot-key headroom — the plan's worst
# per-destination hot concentration added to EVERY bucket. Placement v2
# replaces that with a per-destination budget VECTOR derived from the
# plan's own routing: destination d pays the tail share (the uniques the
# plan's hot table does NOT route explicitly — slack·(U−H)/N) plus
# exactly the hot-key arrivals the plan routes to d. The compiled bucket
# is the vector's max (all_to_all moves equal chunks — SPMD programs
# cannot ship ragged per-destination buckets), which is still strictly
# tighter than the global-headroom bucket whenever the plan routes enough
# hot keys to shrink the tail share past the 8-row rounding.
# `ShardedTable._a2a_budget` calls `a2a_dest_budgets` directly, so the
# model and the program share one formula by construction; bench.py's
# drift arm additionally records the bucket the trace actually used next
# to the modeled vector (measured == modeled, the residency discipline).


def a2a_dest_budgets(
    *,
    unique: int,
    num_shards: int,
    slack: float = 2.0,
    dest_hot=None,
    hot_count: int = 0,
    floor: int = 8,
):
    """Per-destination a2a bucket budgets [N] (rows).

    `dest_hot` is the plan's per-destination explicit hot-key arrival
    counts (None = uniform hash: no hot routing) and `hot_count` the
    number of plan hot keys removed from the hash-spread tail (each hot
    key is a local unique that the plan routes explicitly, so it never
    competes for tail slots). dest_hot=None/hot_count=0 reproduces the
    legacy slack·U/N budget bit-for-bit. Each budget rounds up to a
    VPU-friendly multiple of 8 with a floor of `floor`.

    Drift-safety margin: the tail subtraction is capped at U/4, so even
    when the ENTIRE routed hot set goes cold at once (a rotated key
    distribution — the window between a drift and the replan that chases
    it) every destination still budgets ≥ 3/4·slack × the uniform
    per-dest spread of what is then an all-tail stream (1.5× the
    expected per-dest load at the default slack=2 — real variance
    headroom, not just the mean). Shortfall beyond that degrades via the
    sentinel bucket (default-served, counted), never drops rows."""
    import math

    import numpy as np

    N = int(num_shards)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    h_eff = min(max(0, int(hot_count)), int(unique) // 4)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    tail = math.ceil(max(0, int(unique) - h_eff) * slack / N)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    hot = (
        np.zeros((N,), np.int64)
        if dest_hot is None
        else np.asarray(dest_hot, np.int64)  # noqa: DRT002 — host plan constants (numpy), never a device value
    )
    if hot.shape != (N,):
        raise ValueError(
            f"dest_hot must be a length-{N} vector, got shape {hot.shape}"
        )
    b = np.maximum(int(floor), ((tail + hot + 7) // 8) * 8)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    return b.astype(np.int64)


def a2a_bucket_rows(
    *,
    unique: int,
    num_shards: int,
    slack: float = 2.0,
    dest_hot=None,
    hot_count: int = 0,
    floor: int = 8,
) -> int:
    """The uniform physical bucket the a2a program compiles: the max of
    the per-destination budget vector (all_to_all chunks are equal)."""
    return int(a2a_dest_budgets(
        unique=unique, num_shards=num_shards, slack=slack,
        dest_hot=dest_hot, hot_count=hot_count, floor=floor,
    ).max())


def a2a_bucket_rows_global(
    *,
    unique: int,
    num_shards: int,
    slack: float = 2.0,
    hot_max: int = 0,
    floor: int = 8,
) -> int:
    """The placement-v1 global-headroom bucket: the full hash-spread tail
    (hot keys NOT subtracted) plus the plan's worst per-destination hot
    concentration on every bucket. Kept as the reproducible "before"
    column of the per-dest budget diet (the traffic-diet discipline)."""
    import math

    per = math.ceil(int(unique) * slack / num_shards) + int(hot_max)
    return max(int(floor), ((per + 7) // 8) * 8)


def a2a_exchange_wire_bytes(
    *,
    bucket_rows: int,
    num_shards: int,
    dim: int,
    wire_bytes: int = 4,
    key_bytes: int = 4,
) -> float:
    """Per-device per-step wire bytes of the budgeted a2a exchange at a
    physical bucket of `bucket_rows`: id + count buckets out, embeddings
    back, grads out — (N−1) remote buckets each direction (the bucket a
    shard addresses to itself never leaves the chip)."""
    per_dir = (num_shards - 1) * int(bucket_rows)
    return float(
        per_dir * (key_bytes + 4) + 2 * per_dir * dim * wire_bytes
    )


# ------------------------------------------ hierarchical (two-tier) model
#
# The 2-D mesh splits the flat device axis into a cheap `intra` tier
# (same host group: ICI/NVLink) and an expensive `inter` tier (DCN).
# The hierarchical exchange aggregates ids per host-group on the cheap
# tier first — cross-device duplicates collapse at a relay before
# anything crosses the expensive tier — so the inter-tier bucket is
# budgeted off the GROUP uniques (U_g ≤ group_factor·U ≤ intra·U), not
# off intra·U raw gathered rows. `ShardedTable._hier_budget` calls
# `hier_dest_budgets` directly: model and program share one formula by
# construction, and `bench.py --mesh` records both per-tier modeled and
# measured bytes for `roofline.py --assert-hierarchy` to gate.


def hier_group_unique_budget(
    *, unique: int, intra: int, group_factor: Optional[float] = None,
) -> int:
    """Static budget U_g for the per-host-group unique ids after the
    intra-tier aggregation. `group_factor=None` means exact (intra·U —
    no dedup assumed, the inter bucket can never bind on group overlap);
    a float f budgets U_g = ceil(f·U), capped at intra·U, expressing the
    expected cross-device id overlap inside a group (f→1 as devices in a
    group see the same hot ids). Rounded up to a multiple of 8."""
    import math

    U, I = int(unique), int(intra)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    cap = I * U
    if group_factor is None:
        return cap
    ug = min(cap, math.ceil(float(group_factor) * U))  # noqa: DRT002 — group_factor is a host float knob, no device value
    return min(cap, ((ug + 7) // 8) * 8)


def hier_relay_rows(*, unique: int, intra: int) -> int:
    """Static size of the relay dedup stage: the intra-tier allgather
    hands every device intra·U rows; the relay (device i of each group
    handles gathered ids whose owner sits at intra position i) dedups
    over that full static extent — compute-only, nothing crosses a
    wire at this size."""
    return int(intra) * int(unique)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value


def hier_dest_budgets(
    *,
    unique: int,
    intra: int,
    inter: int,
    slack: float = 2.0,
    group_factor: Optional[float] = None,
    dest_hot=None,
    hot_count: int = 0,
    floor: int = 8,
):
    """Per-destination-GROUP budgets [J] (rows) of the inter-tier a2a.

    Each relay holds ~U_g/intra of its group's uniques (owner intra-pos
    partitions the group uniques across relays under a uniform hash), and
    buckets them by owner GROUP — J destinations. This reuses the per-dest
    budget discipline of `a2a_dest_budgets` verbatim at the group tier:
    `dest_hot` is the plan's per-device hot arrival vector [N] folded to
    per-group maxima over the relay position (all relays compile one
    bucket), `hot_count` the plan hot keys removed from the tail (split
    across relays). Overflow degrades via the sentinel bucket exactly as
    in the flat a2a — default-served, counted, never dropped."""
    import math

    import numpy as np

    I, J = int(intra), int(inter)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    ug = hier_group_unique_budget(
        unique=unique, intra=I, group_factor=group_factor
    )
    relay_u = math.ceil(ug / I)
    group_hot = None
    if dest_hot is not None:
        hot = np.asarray(dest_hot, np.int64)  # noqa: DRT002 — host plan constants (numpy), never a device value
        if hot.shape != (J * I,):
            raise ValueError(
                f"dest_hot must be a length-{J * I} per-device vector, "
                f"got shape {hot.shape}"
            )
        group_hot = hot.reshape(J, I).max(axis=1)
    return a2a_dest_budgets(
        unique=relay_u, num_shards=J, slack=slack,
        dest_hot=group_hot, hot_count=math.ceil(int(hot_count) / I),  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
        floor=floor,
    )


def hier_bucket_rows(
    *,
    unique: int,
    intra: int,
    inter: int,
    slack: float = 2.0,
    group_factor: Optional[float] = None,
    dest_hot=None,
    hot_count: int = 0,
    floor: int = 8,
) -> int:
    """The uniform physical inter-tier bucket (max of the per-group
    budget vector — all_to_all chunks are equal)."""
    return int(hier_dest_budgets(
        unique=unique, intra=intra, inter=inter, slack=slack,
        group_factor=group_factor, dest_hot=dest_hot, hot_count=hot_count,
        floor=floor,
    ).max())


def hier_exchange_bytes(
    *,
    unique: int,
    intra: int,
    inter: int,
    dim: int,
    wire_bytes: int = 4,
    key_bytes: int = 4,
    slack: float = 2.0,
    group_factor: Optional[float] = None,
    dest_hot=None,
    hot_count: int = 0,
    intra_bw_gbs: Optional[float] = None,
    inter_bw_gbs: Optional[float] = None,
) -> Dict[str, float]:
    """Per-device per-step wire bytes of the hierarchical exchange, split
    by tier (the whole point of the 2-D mesh: the tiers have different
    bandwidths, so one aggregate byte count hides the term that matters).

    intra tier (cheap) per device:
      id+count allgather        (I−1)·U·(kb+4)
      value psum_scatter        (I−1)·U·D·wb   (tiled partial sums)
      grad allgather            (I−1)·U·D·wb
    inter tier (expensive) per device, bucket B_g = hier_bucket_rows:
      id+count buckets out      (J−1)·B_g·(kb+4)
      embeddings back           (J−1)·B_g·D·wb
      grads out                 (J−1)·B_g·D·wb

    With `intra_bw_gbs`/`inter_bw_gbs` (GB/s per device, e.g. ICI vs DCN
    injection bandwidth) the dict also carries modeled per-tier
    milliseconds — the roofline form `bench.py --mesh` records."""
    U, D, I, J = int(unique), int(dim), int(intra), int(inter)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    kb, wb = int(key_bytes), int(wire_bytes)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    Bg = hier_bucket_rows(
        unique=U, intra=I, inter=J, slack=slack, group_factor=group_factor,
        dest_hot=dest_hot, hot_count=hot_count,
    )
    intra_b = float(
        (I - 1) * U * (kb + 4) + 2 * (I - 1) * U * D * wb
    )
    inter_b = float(
        (J - 1) * Bg * (kb + 4) + 2 * (J - 1) * Bg * D * wb
    )
    out: Dict[str, float] = {
        "intra_bytes": intra_b,
        "inter_bytes": inter_b,
        "total_bytes": intra_b + inter_b,
        "bucket_rows": float(Bg),
        "group_unique_budget": float(hier_group_unique_budget(
            unique=U, intra=I, group_factor=group_factor
        )),
    }
    if intra_bw_gbs:
        out["intra_ms"] = intra_b / (float(intra_bw_gbs) * 1e9) * 1e3
    if inter_bw_gbs:
        out["inter_ms"] = inter_b / (float(inter_bw_gbs) * 1e9) * 1e3
    return out


def flat_exchange_tier_bytes(
    *,
    unique: int,
    num_shards: int,
    intra: int,
    comm: str = "a2a",
    dim: int = 16,
    wire_bytes: int = 4,
    key_bytes: int = 4,
    slack: float = 2.0,
) -> Dict[str, float]:
    """The FLAT exchange's per-device bytes mapped onto the two-tier
    topology: of its N−1 remote peers, I−1 sit inside the host group
    (intra tier) and N−I across groups (inter tier). This is the
    baseline column of the hierarchy diet — `roofline.py
    --assert-hierarchy` pins hier inter_bytes ≤ total/intra and
    ≤ 0.5 × this function's inter_bytes at the reference shape."""
    U, D, N, I = int(unique), int(dim), int(num_shards), int(intra)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    kb, wb = int(key_bytes), int(wire_bytes)  # noqa: DRT002 — trace-time budget arithmetic on static shapes, no device value
    if comm == "a2a":
        Bd = a2a_bucket_rows(unique=U, num_shards=N, slack=slack)
        row = (kb + 4) + 2 * D * wb
        return {
            "intra_bytes": float((I - 1) * Bd * row),
            "inter_bytes": float((N - I) * Bd * row),
            "total_bytes": float((N - 1) * Bd * row),
        }
    if comm == "allgather":
        row = (kb + 4) + 2 * D * wb
        return {
            "intra_bytes": float((I - 1) * U * row),
            "inter_bytes": float((N - I) * U * row),
            "total_bytes": float((N - 1) * U * row),
        }
    raise ValueError(f"unknown comm {comm!r}")


# --------------------------------------------- replanning amortization model


def migration_bytes(moved_rows: int, *, row_bytes: float) -> float:
    """Modeled one-shot cost of migrating `moved_rows` between shards at
    plan adoption: `exchange_row_bytes` over the moved rows — the same
    per-row unit as the placement load model, so gain/step and cost live
    in one currency and the amortization horizon is a plain division."""
    return float(moved_rows) * float(row_bytes)


def replan_gain_bytes(loads_current, loads_candidate) -> float:
    """Modeled per-step byte gain of adopting a candidate plan: the drop
    in the MAX-shard exchange load (after round 11's pipelining the
    exchange straggler is what bounds step time, so straggler bytes are
    the honest unit — mean load is invariant under re-routing)."""
    import numpy as np

    cur = np.asarray(loads_current, np.float64)
    cand = np.asarray(loads_candidate, np.float64)
    if cur.size == 0 or cand.size == 0:
        return 0.0
    return float(cur.max() - cand.max())


# --------------------------------------------------------------- bytes model


def table_step_traffic(
    *,
    unique: int,
    dim: int,
    value_bytes: int = 4,
    key_bytes: int = 4,
    slot_widths: Sequence[int] = (0,),
    diet: bool = True,
    counter_filter: bool = False,
    num_shards: int = 1,
    comm: Optional[str] = None,
    wire_bytes: int = 4,
    a2a_slack: float = 2.0,
    imbalance: float = 1.0,
) -> Dict[str, float]:
    """Per-table per-step traffic of the embedding engine.

    `unique` is the number of unique rows the step touches (post-dedup, the
    budgeted U); `slot_widths` the optimizer's per-row slot widths (f32).
    Steady state: the initializer scatter for newly created rows is
    excluded (it is proportional to table GROWTH, not step traffic).

    Returns {"hbm_bytes", "wire_bytes", "total_bytes"} — wire_bytes is 0
    for unsharded tables; for num_shards > 1 it models the per-device
    payload of the `comm` exchange ("allgather" | "a2a") at `wire_bytes`
    per value/grad element (4 = fp32, 2 = bf16; ids/counts always ride
    int32).

    `imbalance` is the max/mean per-shard owner-load skew
    (`shard_imbalance`): wire_bytes stays the MEAN payload, and a
    "wire_bytes_max_shard" entry models the straggler shard that actually
    bounds the exchange (mean x imbalance) — the quantity the placement
    plan flattens.
    """
    U, D, vb, kb = unique, dim, value_bytes, key_bytes
    slot_b = sum(w * 4 for w in slot_widths)

    # --- HBM: per-unique-id engine traffic (gathers read, scatters write;
    # .add reads and writes).
    probe = 2 * kb * U  # key gather + claim scatter
    value = (1 * D * vb) * U  # lookup row gather — the apply reuses it
    value += (1 * D * vb) * U  # apply row scatter
    slots = 2 * slot_b * U  # apply slot gather + scatter
    if diet:
        # one fused [3] gather + one fused [3] scatter
        meta = 2 * META_COLS * 4 * U
    else:
        # forward: freq RMW (r+w) + version set + dirty set; admission
        # freq gather when a counter filter gates; apply re-gather of the
        # value rows and the duplicate version/dirty re-stamps.
        meta = (2 * 4 + 4 + 1) * U
        meta += (4 * U) if counter_filter else 0
        meta += (4 + 1) * U  # apply-side version/dirty re-stamp
        value += (1 * D * vb) * U  # apply-side value re-gather
    hbm = probe + value + slots + meta

    # --- wire: per-device exchange payload for sharded tables.
    wire = 0.0
    if num_shards > 1 and comm:
        N = num_shards
        if comm == "allgather":
            # ids + counts allgather (int32), value psum_scatter, grad
            # allgather — each moves ~(N-1)·U rows per device.
            wire += (N - 1) * U * (kb + 4)
            wire += (N - 1) * U * D * wire_bytes  # embeddings down
            wire += (N - 1) * U * D * wire_bytes  # grads up
        elif comm == "a2a":
            # Placement v2: the bucket is the max of the per-destination
            # budget vector (uniform hash: hot terms zero — identical to
            # the legacy slack·U/N bucket).
            Bd = a2a_bucket_rows(unique=U, num_shards=N, slack=a2a_slack)
            wire += a2a_exchange_wire_bytes(
                bucket_rows=Bd, num_shards=N, dim=D,
                wire_bytes=wire_bytes, key_bytes=kb,
            )
        else:
            raise ValueError(f"unknown comm {comm!r}")
    return {
        "hbm_bytes": float(hbm),
        "wire_bytes": float(wire),
        "wire_bytes_max_shard": float(wire) * max(1.0, float(imbalance)),
        "total_bytes": float(hbm + wire),
    }


def fused_sparse_step_traffic(
    *,
    positions: int,
    batch: int,
    unique: int,
    dim: int,
    value_bytes: int = 4,
    key_bytes: int = 4,
    slot_widths: Sequence[int] = (0,),
    fused: bool = True,
) -> Dict[str, float]:
    """Modeled HBM bytes of one fwd+bwd sparse bag step (lookup + combine
    + optimizer apply) for one table — the quantity `roofline.py
    --assert-fused` gates on.

    `positions` is the flattened id-stream length N = B·L, `batch` the bag
    count B, `unique` the budgeted U. The split-phase model
    (`fused=False`) counts every HBM materialization the XLA path makes,
    including the O(N·D) expansion terms the fused kernels eliminate: the
    `emb_u[inverse]` gather that materializes [N, D] before the combine
    reduction, and the mirrored [N, D] per-position grad contributions the
    backward `.at[inverse].add` expands before segment-summing. The fused
    model (`fused=True`) keeps only the irreducible stream: ids in, unique
    rows DMA'd once, bags out, grads in, unique value/slot rows
    read-modify-written once — the [U, D] and [N, D] intermediates live
    and die in VMEM.
    """
    N, B, U, D = positions, batch, unique, dim
    vb, kb = value_bytes, key_bytes
    slot_b = sum(w * 4 for w in slot_widths)

    if not fused:
        hbm = 2 * kb * N  # dedup: key gather + claim scatter over N lanes
        hbm += U * D * vb  # unique row gather (read)
        hbm += 2 * U * D * vb  # [U, D] emb_u round-trip (write, re-read)
        hbm += N * D * vb  # combine: emb_u[inverse] expands to [N, D]
        hbm += B * D * 4  # combined bags out (f32)
        hbm += B * D * 4  # backward: bag grads in (f32)
        hbm += N * D * 4  # per-position grad contribs expand to [N, D]
        hbm += 2 * U * D * 4  # [U, D] grad_u round-trip (scatter, re-read)
        hbm += 2 * U * D * vb  # apply: value row gather + scatter
        hbm += 2 * slot_b * U  # apply: slot gather + scatter
    else:
        hbm = kb * N  # forward reads the id stream once; probe is in VMEM
        hbm += U * D * vb  # unique rows DMA'd HBM -> VMEM once
        hbm += B * D * 4  # combined bags out (f32)
        hbm += B * D * 4  # backward: bag grads in (f32)
        hbm += kb * N  # backward re-reads ids/inverse
        hbm += 2 * U * D * vb  # value rows: DMA in + updated DMA out
        hbm += 2 * slot_b * U  # slot rows: DMA in + out
        if vb == 2:
            hbm += U * D * 4  # row-keyed SR bits (u32) for bf16 tables
    return {"hbm_bytes": float(hbm)}


def dlrm_reference_traffic(
    *,
    batch: int = 2048,
    num_tables: int = 26,
    dim: int = 16,
    unique_fraction: float = 1.0,
    slot_widths: Sequence[int] = (16,),
    diet: bool = True,
    num_shards: int = 1,
    comm: Optional[str] = None,
    exchange_dtype: str = "float32",
    pipeline_mode: str = "off",
) -> Dict[str, float]:
    """Whole-model per-step traffic at the reference DLRM shape (26 single-
    hot features, dim 16, Adagrad).  `unique_fraction` scales the per-table
    touched rows (the dedup budget); sharded shapes split the batch across
    devices and add the exchange term.  `pipeline_mode != "off"` adds the
    lookahead's double-buffer residency under "pipeline_buffer_bytes"
    (per-step traffic itself is unchanged by pipelining — same ops,
    reordered)."""
    wire_bytes = 2 if exchange_dtype == "bfloat16" else 4
    local_batch = batch // max(num_shards, 1)
    U = max(1, int(round(local_batch * unique_fraction)))
    per_table = table_step_traffic(
        unique=U, dim=dim, slot_widths=slot_widths, diet=diet,
        num_shards=num_shards, comm=comm, wire_bytes=wire_bytes,
    )
    out = {k: v * num_tables for k, v in per_table.items()}
    out["pipeline_buffer_bytes"] = num_tables * pipeline_buffer_bytes(
        unique=U, dim=dim, positions=local_batch, num_shards=num_shards,
        comm=comm, pipeline_mode=pipeline_mode,
    )
    return out


# ------------------------------------------------------ serving residency


def serving_residency_bytes(
    *, capacity: int, dim: int, value_dtype: str = "float32",
) -> float:
    """Resident HBM bytes of ONE serving table's value storage at a given
    residency dtype — the quantity `Predictor(quantize=...)` halves/quarters
    and `roofline.py --assert-serving` pins against the measured arrays:

      float32  : C * D * 4
      bfloat16 : C * D * 2
      int8     : C * D * 1  +  C * 4   (per-row fp32 dequant scale)

    Keys/meta are excluded (identical across residencies — the comparison
    is about the value rows, the term that scales with dim). The packed
    small-dim layout is byte-neutral ([C//P, P*D] holds the same C*D
    elements), so the model needs no layout arm."""
    vb = {"float32": 4, "bfloat16": 2, "int8": 1}
    if value_dtype not in vb:
        raise ValueError(f"unknown residency dtype {value_dtype!r}")
    b = float(capacity) * float(dim) * vb[value_dtype]
    if value_dtype == "int8":
        b += float(capacity) * 4  # per-row fp32 scale (TableState.qscale)
    return float(b)


# ------------------------------------------------------- retrieval sweep


def retrieval_sweep_bytes(
    *, corpus_rows: int, dim: int, value_dtype: str = "int8",
    block_rows: int = 4096,
) -> float:
    """HBM bytes ONE full-corpus retrieval sweep reads
    (serving/retrieval.py + ops/topk.py): the resident item matrix at
    its storage dtype, the per-row dequant scale (int8 residency only),
    and the validity mask. `corpus_rows` is the POW2-PADDED resident
    capacity (a multiple of `block_rows` — the blocked sweep reads whole
    blocks, padding included; padding rows score -inf and cost their
    bytes, which is why the engine keeps the block count pow2-tight).

      float32  : C * D * 4  +  C        (values + valid mask)
      bfloat16 : C * D * 2  +  C
      int8     : C * D * 1  +  C * 4  +  C   (+ per-row fp32 scale)

    The [B, k] top-k carry and the per-block score tile live on-chip and
    are excluded — the sweep's defining property is that the full [C]
    score vector never touches HBM. `RetrievalEngine.sweep_info()`
    measures the same quantity off the actual device arrays and
    `roofline.py --assert-retrieval` pins measured == modeled (shape
    math, not an estimate — the serving-residency discipline)."""
    vb = {"float32": 4, "bfloat16": 2, "int8": 1}
    if value_dtype not in vb:
        raise ValueError(f"unknown residency dtype {value_dtype!r}")
    if block_rows <= 0 or corpus_rows % block_rows:
        raise ValueError(
            f"corpus_rows {corpus_rows} must be a positive multiple of "
            f"block_rows {block_rows}")
    b = float(corpus_rows) * float(dim) * vb[value_dtype]
    if value_dtype == "int8":
        b += float(corpus_rows) * 4  # per-row fp32 dequant scale
    b += float(corpus_rows)  # validity mask (1 byte/row)
    return float(b)


# ------------------------------------------------------- compute reuse


def serving_reuse_speedup(
    *, hit_rate: float, hit_cost_ratio: float = 0.0,
) -> float:
    """Modeled effective-qps factor of the serving compute-reuse layer
    (serving/reuse.py) at a given answer-cache hit rate, closed-loop:

        speedup = 1 / (1 - h + h * c)

    where ``h`` is the hit rate and ``c`` the cost of serving a hit
    relative to a full evaluation (fingerprint + dict lookup vs a device
    dispatch; ~0 for the answer cache, larger for the user-tower cache
    where the candidate-only lane still runs the item tower). Amdahl on
    the per-request serial cost: at h=0.5, c=0 the tier answers 2x the
    requests per second from the same compute — the ROADMAP's >=2x
    target IS this curve at the zipf-population hit rate.

    `tools/bench_serving.py compute_reuse` records the measured factor
    next to this model and `roofline.py --assert-reuse` gates the
    measured one; the model is the capacity-planning knob (what hit rate
    does a target speedup need?)."""
    h = float(hit_rate)
    c = float(hit_cost_ratio)
    if not 0.0 <= h <= 1.0:
        raise ValueError(f"hit_rate must be in [0, 1], got {h}")
    if c < 0.0:
        raise ValueError(f"hit_cost_ratio must be >= 0, got {c}")
    denom = (1.0 - h) + h * c
    if denom <= 0.0:
        raise ValueError("hit_rate 1.0 with zero hit cost: infinite model")
    return 1.0 / denom


def reuse_hit_rate_for_speedup(
    *, speedup: float, hit_cost_ratio: float = 0.0,
) -> float:
    """Inverse of `serving_reuse_speedup`: the answer-cache hit rate a
    target effective-qps factor requires (capacity planning: size the
    cache/population so the zipf head clears this rate)."""
    s = float(speedup)
    c = float(hit_cost_ratio)
    if s < 1.0:
        raise ValueError(f"speedup must be >= 1, got {s}")
    if c >= 1.0:
        raise ValueError(f"hit_cost_ratio must be < 1, got {c}")
    return (1.0 - 1.0 / s) / (1.0 - c)


def zipf_expected_hit_rate(*, users: int, alpha: float,
                           resident: int) -> float:
    """Expected answer-cache hit rate for a zipf(alpha) population of
    `users` distinct request keys with the hottest `resident` keys
    cached (steady state, capacity >= resident): the probability mass of
    the resident head,

        sum_{r<resident} r^-alpha / sum_{r<users} r^-alpha.

    The shape `bench_serving --user-zipf A --users N` drives; recorded
    beside the measured hit rate so the bench can show the LRU converges
    on the head."""
    if users < 1 or resident < 0:
        raise ValueError(f"bad population users={users} resident={resident}")
    ranks = [float(r + 1) ** (-float(alpha)) for r in range(int(users))]  # noqa: DRT002 — host-side analytic model, no device values
    total = sum(ranks)
    return sum(ranks[: min(int(resident), int(users))]) / total


# ---------------------------------------------------------- pipelining model


def pipeline_buffer_bytes(
    *,
    unique: int,
    dim: int,
    positions: Optional[int] = None,
    value_bytes: int = 4,
    key_bytes: int = 4,
    num_shards: int = 1,
    comm: Optional[str] = None,
    pipeline_mode: str = "lookahead",
) -> float:
    """Extra RESIDENT bytes per table of the one-batch lookahead
    (`pipeline_mode != "off"`): the pipelined K-step scan double-buffers
    one in-flight lookup — the carried batch's finished embedding buffer,
    its routing arrays and the owner-side residual live alongside the
    current step's. This is capacity, not per-step traffic: the per-step
    byte totals of `table_step_traffic` are unchanged by pipelining (the
    same ops run, reordered), which is why `roofline.py --assert-traffic`
    needs no pipeline-mode arms — this function accounts the HBM headroom
    the lookahead costs instead.

    `positions` is the flattened id-position count of the batch (B·L per
    table); the carried inverse/mask/batch-ids are batch-shaped, not
    unique-shaped, so under a dedup budget (U < positions) they dominate
    the int side of the carry. Defaults to `unique` (the no-dedup U = N
    case)."""
    if pipeline_mode == "off":
        return 0.0
    U, D = unique, dim
    pos = unique if positions is None else int(positions)
    b = U * key_bytes  # carried uids
    b += U * 4  # counts
    b += pos * 4  # inverse (batch-shaped [B, L])
    b += pos * key_bytes  # the prefetched batch's ids themselves
    b += pos * 1  # per-position mask in the carried views
    b += U * D * value_bytes  # finished local embedding buffer
    b += U * D * value_bytes  # owner-side residual rows (reuse_rows diet)
    if num_shards > 1 and comm == "a2a":
        b += U * 4  # send_slot routing metadata
    return float(b)


def modeled_overlap_step(
    *,
    dense_ms: float,
    route_ms: float,
    other_ms: float,
    mode: str = "off",
    chunks: int = 1,
) -> float:
    """Modeled step time (ms) under the in-step pipelining schedule.

    `route_ms` is the hoistable half of the lookup — id dedup + id
    exchange + owner probe/metadata (everything the pipelined scan issues
    ahead of the dense compute); `dense_ms` the dense fwd/bwd it hides
    behind; `other_ms` everything that stays serial (value gather +
    embedding exchange, grad exchange, sparse apply, dense update).

      off:       dense + route + other           (strictly sequential)
      lookahead: max(dense, route) + other       (route hidden behind dense)
      chunked:   like lookahead, with the serial half's EXCHANGE portion
                 internally pipelined — the model conservatively keeps
                 other_ms whole (it cannot split gather from wire without
                 a trace), so chunked == lookahead here; the measured
                 difference only exists on sharded exchanges
                 (tools/bench_async.py --pipeline-mode chunked on a mesh).

    `roofline.py --assert-overlap` compares this against the measured
    pipelined step and gates CI on the ratio (overlap efficiency)."""
    dense_ms = max(0.0, float(dense_ms))
    route_ms = max(0.0, float(route_ms))
    other_ms = max(0.0, float(other_ms))
    if mode == "off":
        return dense_ms + route_ms + other_ms
    return max(dense_ms, route_ms) + other_ms


# ------------------------------------------------------------ op-count model


def count_stablehlo_ops(text: str) -> Dict[str, int]:
    """Count gather/scatter ops in a StableHLO module (the output of
    `jax.jit(fn).lower(*args).as_text()`).  Collectives (all_gather etc.)
    spell differently and are not counted."""
    return {
        "gather": len(re.findall(r'"stablehlo\.gather"|stablehlo\.gather\b', text)),
        "scatter": len(re.findall(r'"stablehlo\.scatter"|stablehlo\.scatter\b', text)),
    }


def expected_lookup_apply_ops(
    *,
    diet: bool = True,
    budgeted: bool = True,
    n_row_slots: int = 1,
) -> Dict[str, int]:
    """Expected stablehlo gather/scatter counts for the single-table TRAIN
    `lookup_unique` + `apply_gradients` program (no sharding, no admission
    filter, one per-row optimizer slot unless overridden).

    Base constants are CALIBRATED against the lowered program (they hold on
    the installed jax 0.9.0: tests/test_traffic_diet.py counts the same ops;
    the extra ops over a hand inventory come from jnp.unique's internals
    and clip/where index lowering (the budgeted dedup is sorts and prefix
    sums: it adds none); `count_stablehlo_ops` counts a
    gather or scatter twice, once for the op and once for its attribute; the
    probe's read-only find loop is one gather, +2 here, beside the claim
    loop's two gathers and one scatter).  The diet deltas are the
    structural facts this PR is about and what the CI assertion guards:

      * non-diet adds 4 scatters — the forward's separate freq/version/
        dirty trio plus the apply-side version/dirty re-stamp collapse
        into ONE fused meta scatter under the diet (5 -> 1);
      * the gather count is net-unchanged — the apply-side value re-gather
        the diet removes is replaced by the fused [3, U] meta gather the
        forward adds (which also absorbed the admission freq read).

    `tools/roofline.py --assert-traffic` compares this against the counts
    `bench.py` measures off the actually-lowered program, so any change to
    the engine's op mix must be reflected here (that is the point).
    """
    if budgeted:  # dedup_at_budget front-end (ops/dedup.py)
        counts = {"gather": 12, "scatter": 10}
    else:  # legacy sort-based jnp.unique front-end
        counts = {"gather": 16, "scatter": 18}
    if not diet:
        counts["scatter"] += 4
    extra_slots = n_row_slots - 1
    counts["gather"] += extra_slots
    counts["scatter"] += extra_slots
    return counts
