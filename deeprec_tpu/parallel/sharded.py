"""Pod-sharded embedding tables: lookup/apply inside shard_map.

This is the subsystem that dissolves DeepRec's distributed parameter plane —
the async-PS graph partitioning, the seastar/GRPC++ data plane
(contrib/star/*), StarServer's lock-free PS runtime and SOK's embedding
all2all (addons/sparse_operation_kit) — into compiled XLA collectives over
ICI (SURVEY.md §2.5, §3.5).

Design (per table, inside one `shard_map` region spanning the train step):

  forward:
    local ids --unique--> local uniques U
    all_gather(uids)                 # tiny: G = N*U int32
    owner mask = hash_shard(id) == my_shard
    owner-side global dedup + lookup_or_create on the LOCAL shard state
    embeddings scattered back to gathered layout, zero elsewhere
    psum_scatter over the shard axis  ->  [U, D] local unique embeddings
  backward:
    all_gather(grad_u)               # [G, D]
    segment-sum into owner-unique rows (cross-replica duplicate ids merge
    here — this is what makes the update exact synchronous SGD, unlike the
    racy lock-free applies of StarServer)
    one fused sparse-apply on the local shard

Every collective is a single XLA op riding ICI; there is no parameter-server
process, no RPC stack, no send/recv graph partitioning.

Split-phase lookup (the in-step pipelining substrate, docs/perf.md round
11): the forward decomposes into `route` (local dedup + the ID exchange +
owner-side dedup — a pure function of the id batch), `resolve` (owner probe/
insert, metadata, init — reads keys/meta, never value rows) and `finish`
(value gather + the embedding exchange). The pipelined K-step scan hoists
route+resolve of batch t+1 ahead of batch t's dense compute and places
finish after batch t's apply, which hides the id exchange and the probe
bookkeeping behind the matmuls with zero staleness. `exchange_chunks > 1`
additionally splits the value/grad exchanges into column chunks — several
smaller collectives XLA's async scheduler can pipeline against the
surrounding gather/segment-sum compute (`pipeline_mode="chunked"`).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from deeprec_tpu.embedding.table import EmbeddingTable, TableState, UniqueLookup, empty_key
from deeprec_tpu.optim import apply as optim_apply
from deeprec_tpu.optim.sparse import SparseOptimizer
from deeprec_tpu.parallel.mesh import DATA_AXIS, AxisSpec
from deeprec_tpu.utils import scopes


@struct.dataclass
class ShardedRoute:
    """Apply-independent routing half of a sharded lookup (lives inside
    shard_map): local dedup, the id exchange and the owner-side dedup. A
    pure function of the id batch — it reads NO table state — so the
    pipelined scan hoists it (and the id collective it contains) a full
    step ahead of the tables it will hit."""

    inverse: jnp.ndarray  # [B, L] position -> local unique index
    counts: jnp.ndarray  # [U] local unique counts
    valid: jnp.ndarray  # [U]
    o_uids: jnp.ndarray  # [O] owner-side unique ids this shard received
    o_inverse: jnp.ndarray  # [G] exchanged-position -> owner-unique index
    o_counts: jnp.ndarray  # [O]
    o_valid: jnp.ndarray  # [O]
    owned: jnp.ndarray  # [G] bool — valid rows this shard received/owns
    # Local-dedup overflow (None on the legacy sort path).
    loc_overflow: Optional[jnp.ndarray]
    # a2a path only: [U] position of each local unique id in the [N*Bd]
    # send buffer (-1 = overflow, served default this step) and the scalar
    # overflow count; empty/None for allgather. The hier path reuses
    # send_slot for the RELAY's inter-tier slots ([Rr], -1 = overflow)
    # and a2a_overflow for the relay overflow count.
    send_slot: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((0,), jnp.int32)
    )
    a2a_overflow: Optional[jnp.ndarray] = None
    # hier path only: per gathered intra-tier position [R = I*U] — whether
    # THIS device is the relay for that position's id, and the position's
    # index into the relay-unique rows. Empty for flat comms.
    h_rel_mask: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((0,), bool)
    )
    h_r_inverse: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.zeros((0,), jnp.int32)
    )


@struct.dataclass
class ShardedLookup:
    """Per-device result of a sharded lookup (lives inside shard_map).

    `resolve` returns it with 0-sized placeholder `embeddings` (the value
    half not yet gathered/exchanged); `finish` fills them. Only finished
    results reach the model / the apply."""

    inverse: jnp.ndarray  # [B, L] position -> local unique index
    counts: jnp.ndarray  # [U] local unique counts
    valid: jnp.ndarray  # [U]
    embeddings: jnp.ndarray  # [U, D] local unique embeddings
    owner_res: UniqueLookup  # owner-side lookup (slot ids on the local shard)
    o_inverse: jnp.ndarray  # [G] exchanged-position -> owner-unique index
    owned: jnp.ndarray  # [G] bool — valid rows this shard received/owns
    # a2a path only: [U] position of each local unique id in the [N*Bd] send
    # buffer (-1 = overflow, served default this step); empty for allgather.
    # hier: the RELAY's inter-tier slots ([Rr], -1 = overflow).
    send_slot: jnp.ndarray = struct.field(default_factory=lambda: jnp.zeros((0,), jnp.int32))
    # hier path only (see ShardedRoute): relay mask + relay-unique inverse
    # over the gathered intra-tier layout [R = I*U]; empty for flat comms.
    h_rel_mask: jnp.ndarray = struct.field(default_factory=lambda: jnp.zeros((0,), bool))
    h_r_inverse: jnp.ndarray = struct.field(default_factory=lambda: jnp.zeros((0,), jnp.int32))


class ShardedTable:
    """Collective lookup/apply for one table sharded over `axis` (call the
    methods from inside a shard_map over that axis; state is the LOCAL shard's
    TableState with capacity = global_capacity / num_shards).

    Three exchange strategies:
      * comm="allgather" (default): all_gather ids + psum_scatter embeddings.
        Exact for any skew; comm volume ~ U·D·(N−1) per device.
      * comm="a2a": budgeted id all2all → owner lookup → embedding all2all —
        the SOK lookup_sparse design (SURVEY.md §3.5). Comm volume
        ~ slack·U·D, an ~N/2× reduction. Ids are bucketed by owner with a
        per-destination budget of slack·U/N; overflow beyond the budget
        (astronomically unlikely under a uniform hash at slack=2) serves the
        default value for that step and is counted in state.a2a_overflow —
        the knob for it is a2a_slack, NOT capacity (insert_fails is the
        separate capacity/grow signal).
      * comm="hier": the two-tier exchange of a `make_mesh_2d` mesh
        (docs/multihost.md). Ids are gathered on the cheap `intra` tier,
        cross-device duplicates collapse at a per-group RELAY (device i of
        each group aggregates the group's ids whose owner sits at intra
        position i), and only the aggregated per-group uniques cross the
        expensive `inter` tier in a budgeted all2all (ops/traffic.py
        `hier_dest_budgets` — the PR-15 per-dest discipline applied at the
        group tier). Values and grads retrace both tiers in reverse with
        fp32 accumulation at the relay and the owner; both wires ride
        `exchange_dtype`. Inter-tier overflow serves the default value and
        counts in state.a2a_overflow, same as "a2a". Requires `axis` to be
        the (inter, intra) name tuple plus the `intra`/`inter` sizes.

    On a 2-D mesh the FLAT comms still work unchanged: pass the (inter,
    intra) axis tuple and every collective enumerates devices in flat
    host-major rank order, bit-identical to the 1-D mesh program.

    `exchange_chunks > 1` splits the value/grad payload exchanges into that
    many column chunks — bitwise-identical arithmetic (per-element reduction
    order unchanged; chunks write disjoint columns), but several smaller
    collectives whose wire time XLA can overlap with the neighbouring
    gather/segment-sum compute (software pipelining; the
    `pipeline_mode="chunked"` knob threads through here). The id exchange
    stays whole — it is already tiny.
    """

    def __init__(
        self,
        table: EmbeddingTable,
        num_shards: int,
        axis: AxisSpec = DATA_AXIS,
        comm: str = "allgather",
        a2a_slack: float = 2.0,
        exchange_chunks: int = 1,
        intra: Optional[int] = None,
        inter: Optional[int] = None,
        hier_group_factor: Optional[float] = None,
    ):
        self.table = table
        self.num_shards = num_shards
        self.axis = tuple(axis) if isinstance(axis, (list, tuple)) else axis
        self.comm = comm
        self.a2a_slack = a2a_slack
        self.exchange_chunks = max(1, int(exchange_chunks))
        # Two-tier geometry (comm="hier"): `axis` must be the (inter,
        # intra) tuple of a make_mesh_2d mesh; `hier_group_factor` is the
        # static per-group unique budget U_g = factor·U (None = exact
        # intra·U — group overlap can never overflow the inter bucket).
        self.intra = int(intra) if intra is not None else None
        self.inter = int(inter) if inter is not None else None
        self.hier_group_factor = hier_group_factor
        if comm == "hier":
            if not (isinstance(self.axis, tuple) and len(self.axis) == 2):
                raise ValueError(
                    "comm='hier' needs axis=(inter, intra) name tuple, "
                    f"got {self.axis!r}"
                )
            if not self.intra or not self.inter:
                raise ValueError("comm='hier' needs intra/inter sizes")
            if self.intra * self.inter != num_shards:
                raise ValueError(
                    f"hier mesh {self.inter}x{self.intra} != "
                    f"num_shards {num_shards}"
                )
        # Plan-aware per-destination a2a budget inputs (see _a2a_budget):
        # `plan_dest_hot` is the active plan's per-destination explicit
        # hot-key arrival counts ([N] ints; None = uniform hash) and
        # `plan_hot_count` how many plan hot keys leave the hash-spread
        # tail. Both are static trace-time constants set by
        # ShardedTrainer.update_placement at plan adoption (before the
        # jit rebuild).
        self.plan_dest_hot = None
        self.plan_hot_count = 0
        # Trace-time record of the budget the compiled program actually
        # uses — the measured side of the measured==modeled budget assert
        # (bench.py drift arm, tests/test_placement_v2.py).
        self.last_a2a_unique = None
        self.last_a2a_budgets = None
        self.last_a2a_bucket = None

    # --------------------------------------------------------- split phases

    def route(
        self,
        ids: jnp.ndarray,
        *,
        pad_value: int = -1,
        unique_size: Optional[int] = None,
        plan=None,
    ) -> ShardedRoute:
        """Routing phase: local dedup (`unique_size` engages the hash
        engine at that static budget), the id exchange, and the owner-side
        dedup. Depends only on `ids` — no table state — so it can be
        issued arbitrarily early.

        `plan` is an optional placement-plan leaf dict
        (parallel/placement.py): owner-offset rotation + hot-key routing
        table consulted before `hash_shard`, so zipf head keys spread
        across the mesh instead of hammering their hash-home. None/{}
        keeps the uniform hash (identical program)."""
        if self.comm == "a2a":
            return self._route_a2a(ids, pad_value, unique_size, plan)
        if self.comm == "hier":
            return self._route_hier(ids, pad_value, unique_size, plan)
        return self._route_allgather(ids, pad_value, unique_size, plan)

    def resolve(
        self,
        state: TableState,
        route: ShardedRoute,
        *,
        step: jnp.ndarray | int = 0,
        train: bool = True,
        salt=None,
    ) -> Tuple[TableState, ShardedLookup]:
        """Owner-side key/metadata phase on a prepared route: probe/insert
        on the local shard, fused metadata stamp, init scatter for created
        rows, admission, and the dedup/a2a telemetry counters. Touches
        keys/meta/new rows only — never the value rows an apply writes —
        so resolve(t+1) commutes bit-exactly with apply(t) (the hoist
        contract of the pipelined scan). Returns a pending ShardedLookup
        whose embeddings await `finish`."""
        state, res = self.table._resolve(
            state, route.o_uids, route.o_counts, route.o_valid, step=step,
            train=train, salt=salt,
        )
        state = self._count_dedup(
            state, route.counts, route.valid, route.loc_overflow, train
        )
        if train:
            # Owner-side load telemetry: how many exchanged rows THIS
            # shard owns this step (arrivals — a hot key present on k
            # source shards counts k) and how many distinct keys those
            # dedup to. The per-mesh-position imbalance of these counters
            # is what the placement plan flattens (dedup_stats per_shard,
            # bench.py --placement).
            state = state.replace(
                owner_arrivals=state.owner_arrivals
                + jnp.sum(route.owned).astype(jnp.int32),
                owner_unique=state.owner_unique
                + jnp.sum(route.o_valid).astype(jnp.int32),
            )
        if train and route.a2a_overflow is not None:
            state = state.replace(
                a2a_overflow=state.a2a_overflow + route.a2a_overflow
            )
        return state, ShardedLookup(
            inverse=route.inverse,
            counts=route.counts,
            valid=route.valid,
            embeddings=jnp.zeros((0, 0), jnp.float32),
            owner_res=res,
            o_inverse=route.o_inverse,
            owned=route.owned,
            send_slot=route.send_slot,
            h_rel_mask=route.h_rel_mask,
            h_r_inverse=route.h_r_inverse,
        )

    def finish(
        self,
        state: TableState,
        sl: ShardedLookup,
        *,
        train: bool = True,
        keep_rows: bool = True,
    ) -> ShardedLookup:
        """Value phase: gather the resolved owner rows from the CURRENT
        values array and run the embedding exchange (chunked when
        `exchange_chunks > 1`). In the pipelined scan this runs after the
        previous step's apply — which is exactly what keeps the lookahead
        staleness-free. `keep_rows=False` drops the owner-side residual
        for callers that never reuse it (the stale-by-one apply)."""
        o_res = self.table._finish_resolved(
            state, sl.owner_res, keep_rows=keep_rows
        )
        if self.comm == "a2a":
            return self._finish_a2a(sl, o_res, train)
        if self.comm == "hier":
            return self._finish_hier(sl, o_res, train)
        return self._finish_allgather(sl, o_res, train)

    def lookup_unique(
        self,
        state: TableState,
        ids: jnp.ndarray,
        *,
        step: jnp.ndarray | int = 0,
        train: bool = True,
        pad_value: int = -1,
        salt=None,
        unique_size: Optional[int] = None,
        plan=None,
    ) -> Tuple[TableState, ShardedLookup]:
        """`unique_size` (static) engages the budgeted dedup at that
        budget BEFORE the exchange: the all_gather/all2all id payload, the
        owner-side work and the embedding return all shrink by the same
        U/N factor. None keeps the legacy sort-unique at U = N.

        Composition of the split phases — route → resolve → finish; the
        pipelined trainers call the phases individually."""
        route = self.route(
            ids, pad_value=pad_value, unique_size=unique_size, plan=plan
        )
        state, sl = self.resolve(
            state, route, step=step, train=train, salt=salt
        )
        return state, self.finish(state, sl, train=train)

    # ------------------------------------------------------- shared helpers

    def _wire_dtype(self, train: bool):
        """Dtype of the value/grad payloads on the wire. TRAIN exchanges
        ride cfg.exchange_dtype (default bf16 — halves ICI bytes both ways;
        the owner side always segment-sums in fp32, and in the forward each
        gathered position has exactly ONE nonzero contributor, so even the
        psum_scatter reduction is exact at the wire precision). Eval and
        serving exchanges stay exact fp32 regardless."""
        if train and self.table.cfg.exchange_dtype == "bfloat16":
            return jnp.bfloat16
        return jnp.float32

    def _col_chunks(self, D: int):
        """Static [start, stop) column blocks of the value/grad exchange —
        `exchange_chunks` near-equal pieces (each >= 1 column)."""
        k = max(1, min(self.exchange_chunks, int(D)))
        bounds = [round(i * D / k) for i in range(k + 1)]
        return [(a, b) for a, b in zip(bounds, bounds[1:]) if b > a]

    def _owner_dedup(self, g_ids, g_counts, include, sentinel,
                     budgeted: bool = False):
        """Dedup exchanged ids on the owner side (the same id may arrive from
        many peers) and segment-sum their counts. Under a budget the dedup
        is `dedup_at_budget` sized to hold every exchanged id (a few pad
        slots over G), so the owner side never overflows."""
        G = g_ids.shape[0]
        if budgeted:
            from deeprec_tpu.ops import dedup

            o_uids, o_inverse, o_counts, _ = dedup.dedup_at_budget(
                jnp.where(include, g_ids, sentinel),
                dedup.resolve_size(G, G),
                sentinel=empty_key(self.table.cfg),
                weights=jnp.where(include, g_counts, 0),
            )
            return o_uids, o_inverse, o_counts, o_uids != sentinel
        o_uids, o_inverse, _ = jnp.unique(
            jnp.where(include, g_ids, sentinel), size=G, fill_value=sentinel,
            return_inverse=True, return_counts=True,
        )
        o_valid = o_uids != sentinel
        o_counts = (
            jnp.zeros((G,), jnp.int32)
            .at[o_inverse]
            .add(jnp.where(include, g_counts, 0))
        )
        return o_uids, o_inverse, jnp.where(o_valid, o_counts, 0), o_valid

    def _count_dedup(self, state, counts, valid, overflow, train):
        """Accumulate the dedup telemetry counters on the local shard's
        state (mirrors EmbeddingTable._lookup_unique_impl)."""
        if not train:
            return state
        return state.replace(
            dedup_unique=state.dedup_unique + jnp.sum(valid).astype(jnp.int32),
            dedup_ids=state.dedup_ids + jnp.sum(counts),
            dedup_overflow=(
                state.dedup_overflow + overflow
                if overflow is not None
                else state.dedup_overflow
            ),
        )

    # -------------------------------------------------------- allgather path

    def _route_allgather(self, ids, pad_value, unique_size,
                         plan=None) -> ShardedRoute:
        from deeprec_tpu.ops import dedup
        from deeprec_tpu.parallel import placement

        N = self.num_shards
        axis = self.axis
        sent_py = empty_key(self.table.cfg)
        uids, inverse, counts, valid, loc_ovf = dedup.route_ids(
            ids, pad_value=pad_value, sentinel=sent_py,
            unique_size=unique_size,
        )
        sentinel = jnp.asarray(sent_py, uids.dtype)

        # Exchange unique ids (cheap: ints) so every shard sees all
        # candidates — under a budget the gathered G = N·U shrinks with U.
        g_uids = jax.lax.all_gather(uids, axis, tiled=True)  # [G]
        g_counts = jax.lax.all_gather(counts, axis, tiled=True)  # [G]
        me = jax.lax.axis_index(axis)
        owned = (placement.plan_owner(g_uids, N, plan) == me) & (
            g_uids != sentinel
        )
        o_uids, o_inverse, o_counts, o_valid = self._owner_dedup(
            g_uids, g_counts, owned, sentinel, budgeted=unique_size is not None
        )
        return ShardedRoute(
            inverse=inverse, counts=counts, valid=valid,
            o_uids=o_uids, o_inverse=o_inverse, o_counts=o_counts,
            o_valid=o_valid, owned=owned, loc_overflow=loc_ovf,
        )

    def _finish_allgather(self, sl: ShardedLookup, o_res: UniqueLookup,
                          train: bool) -> ShardedLookup:
        # Back to gathered layout; non-owned rows contribute zero, then one
        # reduce-scatter hands each replica its own unique rows. The value
        # payload rides the wire dtype (train: bf16 by default) — exact as a
        # reduction because each row has one nonzero contributor.
        wire = self._wire_dtype(train)
        e_g = o_res.embeddings[sl.o_inverse] * sl.owned[:, None].astype(
            o_res.embeddings.dtype
        )
        parts = []
        for ci, (a, b) in enumerate(self._col_chunks(e_g.shape[1])):
            with scopes.scope(scopes.exchange_chunk(ci)):
                parts.append(jax.lax.psum_scatter(
                    e_g[:, a:b].astype(wire), self.axis,
                    scatter_dimension=0, tiled=True,
                ))
        emb_local = (
            parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        ).astype(jnp.float32)  # [U, D]
        return sl.replace(embeddings=emb_local, owner_res=o_res)

    # ------------------------------------------------------------- a2a path

    def _a2a_budget(self, U: int) -> int:
        from deeprec_tpu.ops import traffic as T

        # Per-destination budget vector (ops/traffic.py a2a_dest_budgets):
        # destination d pays the hash-spread TAIL share — slack·(U−H)/N,
        # H = the plan's hot-key count, keys the routing table sends
        # explicitly and so never compete for tail slots — plus exactly
        # the hot-key arrivals the plan routes to d (every source that
        # sees a hot key sends it to the same planned owner, so the
        # per-(source, dest) concentration is the plan's own bincount).
        # The compiled bucket is the vector's max: all_to_all moves equal
        # chunks, so an SPMD program cannot ship ragged per-destination
        # buckets — but the max is still strictly tighter than the v1
        # global-headroom bucket (full tail + the worst concentration on
        # EVERY bucket) once the plan routes enough hot keys. Uniform
        # hash (no plan) reproduces the legacy slack·U/N budget
        # bit-for-bit. The inputs are static trace-time constants
        # (update_placement sets them before the jit rebuild); genuine
        # shortfall still degrades via the sentinel bucket — default
        # served, counted in a2a_overflow — never dropped rows.
        budgets = T.a2a_dest_budgets(
            unique=U, num_shards=self.num_shards, slack=self.a2a_slack,
            dest_hot=self.plan_dest_hot, hot_count=self.plan_hot_count,
        )
        self.last_a2a_unique = int(U)  # noqa: DRT002 — static trace-time shape, no device value
        self.last_a2a_budgets = budgets
        self.last_a2a_bucket = int(budgets.max())  # noqa: DRT002 — max of a host numpy budget vector, no device value
        return self.last_a2a_bucket

    def _route_a2a(self, ids, pad_value, unique_size,
                   plan=None) -> ShardedRoute:
        from deeprec_tpu.ops import dedup
        from deeprec_tpu.parallel import placement

        N = self.num_shards
        axis = self.axis
        sent_py = empty_key(self.table.cfg)
        uids, inverse, counts, valid, loc_ovf = dedup.route_ids(
            ids, pad_value=pad_value, sentinel=sent_py,
            unique_size=unique_size,
        )
        sentinel = jnp.asarray(sent_py, uids.dtype)
        # Under a budget U shrinks, so the per-destination bucket Bd and
        # both all2all payloads shrink by the same factor.
        U = uids.shape[0]

        # Bucket by owner with a per-destination budget.
        Bd = self._a2a_budget(U)
        owner = jnp.where(
            valid, placement.plan_owner(uids, N, plan), jnp.int32(N)
        )  # invalid sort last
        sort_ix = jnp.argsort(owner, stable=True)
        sorted_owner = owner[sort_ix]
        start = jnp.searchsorted(sorted_owner, jnp.arange(N, dtype=owner.dtype))
        rank = jnp.arange(U, dtype=jnp.int32) - start[
            jnp.clip(sorted_owner, 0, N - 1)
        ].astype(jnp.int32)
        slot_sorted = jnp.where(
            (sorted_owner < N) & (rank < Bd), sorted_owner * Bd + rank, -1
        )
        send_slot = jnp.zeros((U,), jnp.int32).at[sort_ix].set(slot_sorted)
        overflow = (send_slot < 0) & valid
        sslot_safe = jnp.where(send_slot >= 0, send_slot, N * Bd)

        buf_ids = jnp.full((N * Bd,), sentinel, uids.dtype).at[sslot_safe].set(
            uids, mode="drop"
        )
        buf_counts = jnp.zeros((N * Bd,), jnp.int32).at[sslot_safe].set(
            counts, mode="drop"
        )
        # Exchange: row j of the receive buffer = the bucket peer j sent us.
        recv_ids = jax.lax.all_to_all(
            buf_ids.reshape(N, Bd), axis, split_axis=0, concat_axis=0, tiled=True
        ).reshape(-1)
        recv_counts = jax.lax.all_to_all(
            buf_counts.reshape(N, Bd), axis, split_axis=0, concat_axis=0,
            tiled=True,
        ).reshape(-1)

        recv_valid = recv_ids != sentinel
        o_uids, o_inverse, o_counts, o_valid = self._owner_dedup(
            recv_ids, recv_counts, recv_valid, sentinel,
            budgeted=unique_size is not None,
        )
        return ShardedRoute(
            inverse=inverse, counts=counts, valid=valid,
            o_uids=o_uids, o_inverse=o_inverse, o_counts=o_counts,
            o_valid=o_valid, owned=recv_valid, loc_overflow=loc_ovf,
            send_slot=send_slot,
            a2a_overflow=jnp.sum(overflow).astype(jnp.int32),
        )

    def _finish_a2a(self, sl: ShardedLookup, o_res: UniqueLookup,
                    train: bool) -> ShardedLookup:
        cfg = self.table.cfg
        N = self.num_shards
        G2 = sl.o_inverse.shape[0]
        Bd = G2 // N
        # Embedding return payload in the wire dtype (train: bf16 default).
        wire = self._wire_dtype(train)
        e_out = o_res.embeddings[sl.o_inverse].astype(wire)
        e_out = e_out * sl.owned[:, None].astype(wire)
        parts = []
        for ci, (a, b) in enumerate(self._col_chunks(e_out.shape[1])):
            with scopes.scope(scopes.exchange_chunk(ci)):
                parts.append(jax.lax.all_to_all(
                    e_out[:, a:b].reshape(N, Bd, b - a), self.axis,
                    split_axis=0, concat_axis=0, tiled=True,
                ).reshape(G2, b - a))
        e_back = (
            parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        ).astype(jnp.float32)
        # e_back[send_slot[u]] is u's embedding; overflow/invalid -> default.
        emb_local = e_back.at[jnp.where(sl.send_slot >= 0, sl.send_slot, 0)].get(
            mode="clip"
        )
        blocked = jnp.asarray(
            cfg.ev.init.default_value_no_permission, jnp.float32
        )
        emb_local = jnp.where((sl.send_slot >= 0)[:, None], emb_local, blocked)
        return sl.replace(embeddings=emb_local, owner_res=o_res)

    def _apply_a2a(
        self, state, opt, sl, grad_u, *, step, lr, grad_averaging,
        reuse_rows, stamp_meta,
    ) -> TableState:
        N = self.num_shards
        G2 = sl.o_inverse.shape[0]
        Bd = G2 // N
        D = grad_u.shape[1]
        wire = self._wire_dtype(True)  # the backward only exists in train
        sslot_safe = jnp.where(sl.send_slot >= 0, sl.send_slot, G2)
        # Segment-sum into owner-unique rows AT THE OWNER SIZE (== G2 on
        # the legacy path; a few pad slots over it under a budget). The
        # accumulation runs in fp32 on the owner side regardless of the
        # wire dtype. Chunked: each column block rides its own all_to_all
        # and lands in its own (disjoint) o_grad columns — bitwise the
        # same result, but the wire time of chunk k overlaps the
        # segment-sum of chunk k-1.
        O = sl.owner_res.uids.shape[0]
        parts = []
        for ci, (a, b) in enumerate(self._col_chunks(D)):
            g_buf = (
                jnp.zeros((G2, b - a), wire)
                .at[sslot_safe]
                .set(grad_u[:, a:b].astype(wire), mode="drop")
            )
            with scopes.scope(scopes.exchange_chunk(ci)):
                g_recv = jax.lax.all_to_all(
                    g_buf.reshape(N, Bd, b - a), self.axis, split_axis=0,
                    concat_axis=0, tiled=True,
                ).reshape(G2, b - a)
            parts.append(
                jnp.zeros((O, b - a), jnp.float32)
                .at[sl.o_inverse]
                .add(g_recv.astype(jnp.float32)
                     * sl.owned[:, None].astype(jnp.float32))
            )
        o_grad = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        # Same local-mean-loss rescale as the allgather path.
        o_grad = o_grad / jnp.float32(N)
        return optim_apply.apply_gradients(
            self.table, state, opt, sl.owner_res, o_grad, step=step, lr=lr,
            grad_averaging=grad_averaging, reuse_rows=reuse_rows,
            stamp_meta=stamp_meta,
        )

    # ------------------------------------------------- hierarchical path
    #
    # Two-tier exchange over a make_mesh_2d mesh (docs/multihost.md).
    # Forward ids: local dedup (U) → intra-tier allgather ([I·U], cheap
    # wire) → per-group relay dedup (device i of the group aggregates the
    # gathered ids whose owner sits at intra position i — the group's
    # uniques partition across relays, so each id crosses the expensive
    # tier exactly once per source group) → budgeted inter-tier a2a by
    # owner GROUP → owner dedup → resolve. The bucket a relay addresses
    # to owner group j lands on device (j, i) — exactly the owner,
    # because relay position i IS the owner's intra position. Values and
    # grads retrace the tiers in reverse: owner → inter a2a → relay →
    # intra psum_scatter/allgather, fp32 accumulation at relay and
    # owner, `exchange_dtype` on both wires (the forward stays exact at
    # bf16: every psum_scatter position has ONE nonzero contributor and
    # bf16∘bf16 rounding is idempotent; the backward's relay pre-sum
    # regroups the fp32 reduction, an ulp-level reordering — same class
    # as a2a-vs-allgather).

    def _hier_budget(self, U: int) -> int:
        from deeprec_tpu.ops import traffic as T

        # Per-destination-GROUP budget vector (ops/traffic.py
        # hier_dest_budgets): the PR-15 per-dest discipline applied at
        # the group tier — each relay holds ~U_g/I group uniques and
        # buckets them over J owner groups; the plan's per-device hot
        # arrivals fold to per-group maxima. Model and program share one
        # formula by construction; bench.py --mesh records the bucket
        # the trace used next to the modeled vector.
        budgets = T.hier_dest_budgets(
            unique=U, intra=self.intra, inter=self.inter,
            slack=self.a2a_slack, group_factor=self.hier_group_factor,
            dest_hot=self.plan_dest_hot, hot_count=self.plan_hot_count,
        )
        self.last_a2a_unique = int(U)  # noqa: DRT002 — static trace-time shape, no device value
        self.last_a2a_budgets = budgets
        self.last_a2a_bucket = int(budgets.max())  # noqa: DRT002 — max of a host numpy budget vector, no device value
        return self.last_a2a_bucket

    def _route_hier(self, ids, pad_value, unique_size,
                    plan=None) -> ShardedRoute:
        from deeprec_tpu.ops import dedup
        from deeprec_tpu.parallel import placement

        N = self.num_shards
        I, J = self.intra, self.inter
        ea, ia = self.axis  # (inter, intra) — mesh-major
        sent_py = empty_key(self.table.cfg)
        uids, inverse, counts, valid, loc_ovf = dedup.route_ids(
            ids, pad_value=pad_value, sentinel=sent_py,
            unique_size=unique_size,
        )
        sentinel = jnp.asarray(sent_py, uids.dtype)
        U = uids.shape[0]

        # --- intra tier: id/count gather inside the host group.
        with scopes.scope(scopes.HIER_INTRA_IDS):
            g_uids = jax.lax.all_gather(uids, ia, tiled=True)  # [I*U]
            g_counts = jax.lax.all_gather(counts, ia, tiled=True)
        owner = placement.plan_owner(g_uids, N, plan)  # [I*U]
        g_valid = g_uids != sentinel
        i_me = jax.lax.axis_index(ia)
        # Relay selection: flat rank r = g·I + i, so owner % I is the
        # owner's intra position — the coordinate the inter a2a cannot
        # change. Exactly one device per group relays a given position.
        rel_mask = ((owner % jnp.int32(I)) == i_me) & g_valid
        r_uids, r_inverse, r_counts, r_valid = self._owner_dedup(
            g_uids, g_counts, rel_mask, sentinel, budgeted=True
        )
        Rr = r_uids.shape[0]

        # --- inter tier: bucket relay uniques by owner group under the
        # per-group budget; overflow degrades to the sentinel bucket
        # (default-served, counted), never dropped rows.
        Bg = self._hier_budget(U)
        group = jnp.where(
            r_valid,
            placement.plan_owner(r_uids, N, plan) // jnp.int32(I),
            jnp.int32(J),
        )  # invalid sort last
        sort_ix = jnp.argsort(group, stable=True)
        sorted_group = group[sort_ix]
        start = jnp.searchsorted(
            sorted_group, jnp.arange(J, dtype=group.dtype)
        )
        rank = jnp.arange(Rr, dtype=jnp.int32) - start[
            jnp.clip(sorted_group, 0, J - 1)
        ].astype(jnp.int32)
        slot_sorted = jnp.where(
            (sorted_group < J) & (rank < Bg), sorted_group * Bg + rank, -1
        )
        send_slot = jnp.zeros((Rr,), jnp.int32).at[sort_ix].set(slot_sorted)
        overflow = (send_slot < 0) & r_valid
        sslot_safe = jnp.where(send_slot >= 0, send_slot, J * Bg)

        buf_ids = jnp.full((J * Bg,), sentinel, uids.dtype).at[
            sslot_safe
        ].set(r_uids, mode="drop")
        buf_counts = jnp.zeros((J * Bg,), jnp.int32).at[sslot_safe].set(
            r_counts, mode="drop"
        )
        with scopes.scope(scopes.HIER_INTER_IDS):
            recv_ids = jax.lax.all_to_all(
                buf_ids.reshape(J, Bg), ea, split_axis=0, concat_axis=0,
                tiled=True,
            ).reshape(-1)
            recv_counts = jax.lax.all_to_all(
                buf_counts.reshape(J, Bg), ea, split_axis=0, concat_axis=0,
                tiled=True,
            ).reshape(-1)

        # Everything that arrives is owned by me (relay position == my
        # intra position, bucket == my group).
        recv_valid = recv_ids != sentinel
        o_uids, o_inverse, o_counts, o_valid = self._owner_dedup(
            recv_ids, recv_counts, recv_valid, sentinel, budgeted=True
        )
        return ShardedRoute(
            inverse=inverse, counts=counts, valid=valid,
            o_uids=o_uids, o_inverse=o_inverse, o_counts=o_counts,
            o_valid=o_valid, owned=recv_valid, loc_overflow=loc_ovf,
            send_slot=send_slot,
            a2a_overflow=jnp.sum(overflow).astype(jnp.int32),
            h_rel_mask=rel_mask, h_r_inverse=r_inverse,
        )

    def _finish_hier(self, sl: ShardedLookup, o_res: UniqueLookup,
                     train: bool) -> ShardedLookup:
        cfg = self.table.cfg
        J = self.inter
        ea, ia = self.axis
        G2 = sl.o_inverse.shape[0]  # J*Bg
        Bg = G2 // J
        wire = self._wire_dtype(train)
        # --- inter tier back: owner rows → relay buckets.
        e_out = o_res.embeddings[sl.o_inverse].astype(wire)
        e_out = e_out * sl.owned[:, None].astype(wire)
        D = e_out.shape[1]
        blocked = jnp.asarray(
            cfg.ev.init.default_value_no_permission, jnp.float32
        )
        parts = []
        for ci, (a, b) in enumerate(self._col_chunks(D)):
            with scopes.scope(scopes.hier_inter_chunk(ci)):
                e_back = jax.lax.all_to_all(
                    e_out[:, a:b].reshape(J, Bg, b - a), ea,
                    split_axis=0, concat_axis=0, tiled=True,
                ).reshape(G2, b - a).astype(jnp.float32)
            # e_back[send_slot[u]] is relay-unique u's row; inter-tier
            # overflow serves the default (the a2a degrade contract).
            v_r = e_back.at[
                jnp.where(sl.send_slot >= 0, sl.send_slot, 0)
            ].get(mode="clip")
            v_r = jnp.where((sl.send_slot >= 0)[:, None], v_r, blocked)
            # --- intra tier back: relay rows → gathered layout → one
            # reduce-scatter hands each device its own uniques. Exact at
            # the wire dtype: exactly one relay contributes per position.
            e_g = v_r[sl.h_r_inverse] * sl.h_rel_mask[:, None].astype(
                jnp.float32
            )
            with scopes.scope(scopes.hier_intra_chunk(ci)):
                parts.append(jax.lax.psum_scatter(
                    e_g.astype(wire), ia, scatter_dimension=0, tiled=True,
                ))
        emb_local = (
            parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        ).astype(jnp.float32)  # [U, D]
        return sl.replace(embeddings=emb_local, owner_res=o_res)

    def _apply_hier(
        self, state, opt, sl, grad_u, *, step, lr, grad_averaging,
        reuse_rows, stamp_meta,
    ) -> TableState:
        J = self.inter
        ea, ia = self.axis
        G2 = sl.o_inverse.shape[0]
        Bg = G2 // J
        Rr = sl.send_slot.shape[0]
        D = grad_u.shape[1]
        wire = self._wire_dtype(True)  # the backward only exists in train
        O = sl.owner_res.uids.shape[0]
        sslot_safe = jnp.where(sl.send_slot >= 0, sl.send_slot, G2)
        rel = sl.h_rel_mask[:, None].astype(jnp.float32)
        parts = []
        for ci, (a, b) in enumerate(self._col_chunks(D)):
            # Intra tier: grads gather inside the group at the wire
            # dtype; the relay segment-sums its positions in fp32 (the
            # cross-device duplicate merge happens HERE, before the
            # expensive tier — the byte diet of the whole design).
            with scopes.scope(scopes.hier_intra_chunk(ci)):
                g_g = jax.lax.all_gather(
                    grad_u[:, a:b].astype(wire), ia, tiled=True
                )  # [I*U, b-a]
            r_grad = (
                jnp.zeros((Rr, b - a), jnp.float32)
                .at[sl.h_r_inverse]
                .add(g_g.astype(jnp.float32) * rel)
            )
            # Inter tier: relay subtotals ride the budgeted buckets back
            # to the owner (overflowed rows drop, matching their
            # default-served forward); owner accumulates in fp32.
            g_buf = (
                jnp.zeros((G2, b - a), wire)
                .at[sslot_safe]
                .set(r_grad.astype(wire), mode="drop")
            )
            with scopes.scope(scopes.hier_inter_chunk(ci)):
                g_recv = jax.lax.all_to_all(
                    g_buf.reshape(J, Bg, b - a), ea, split_axis=0,
                    concat_axis=0, tiled=True,
                ).reshape(G2, b - a)
            parts.append(
                jnp.zeros((O, b - a), jnp.float32)
                .at[sl.o_inverse]
                .add(g_recv.astype(jnp.float32)
                     * sl.owned[:, None].astype(jnp.float32))
            )
        o_grad = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        # Same local-mean-loss rescale as the flat paths.
        o_grad = o_grad / jnp.float32(self.num_shards)
        return optim_apply.apply_gradients(
            self.table, state, opt, sl.owner_res, o_grad, step=step, lr=lr,
            grad_averaging=grad_averaging, reuse_rows=reuse_rows,
            stamp_meta=stamp_meta,
        )

    # ------------------------------------------------------------- backward

    def apply_gradients(
        self,
        state: TableState,
        opt: SparseOptimizer,
        sl: ShardedLookup,
        grad_u: jnp.ndarray,  # [U, D] grads w.r.t. sl.embeddings
        *,
        step: jnp.ndarray | int = 0,
        lr=None,
        grad_averaging: bool = False,
        reuse_rows: bool = False,
        stamp_meta: bool = True,
    ) -> TableState:
        """reuse_rows/stamp_meta thread to optim_apply.apply_gradients
        (safe legacy defaults; see its docstring). The sharded trainer's
        sync hot path opts into the diet — the owner-side residual
        (sl.owner_res.rows) replaces the apply's value gather — while the
        async stale-by-one apply keeps the defaults."""
        if self.comm == "a2a":
            return self._apply_a2a(
                state, opt, sl, grad_u, step=step, lr=lr,
                grad_averaging=grad_averaging, reuse_rows=reuse_rows,
                stamp_meta=stamp_meta,
            )
        if self.comm == "hier":
            return self._apply_hier(
                state, opt, sl, grad_u, step=step, lr=lr,
                grad_averaging=grad_averaging, reuse_rows=reuse_rows,
                stamp_meta=stamp_meta,
            )
        wire = self._wire_dtype(True)  # the backward only exists in train
        D = grad_u.shape[1]
        # Owner-unique rows: size == G legacy, G + pad under a budget.
        # Accumulate in fp32 whatever the wire dtype was. Chunked: one
        # all_gather + segment-sum per column block (disjoint o_grad
        # columns — bitwise identical, wire/computation pipelined).
        O = sl.owner_res.uids.shape[0]
        parts = []
        for ci, (a, b) in enumerate(self._col_chunks(D)):
            with scopes.scope(scopes.exchange_chunk(ci)):
                g_g = jax.lax.all_gather(
                    grad_u[:, a:b].astype(wire), self.axis, tiled=True
                )  # [G, b-a] — G = N·U shrinks with the unique budget
            parts.append(
                jnp.zeros((O, b - a), jnp.float32)
                .at[sl.o_inverse]
                .add(g_g.astype(jnp.float32)
                     * sl.owned[:, None].astype(jnp.float32))
            )
        o_grad = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        # Per-replica losses are means over the LOCAL batch (B/N); summing N
        # replicas' grads here would make the sparse step N x the
        # single-device one while dense grads get pmean'd. Rescale so both
        # paths see the global-batch-mean gradient.
        o_grad = o_grad / jnp.float32(self.num_shards)
        return optim_apply.apply_gradients(
            self.table,
            state,
            opt,
            sl.owner_res,
            o_grad,
            step=step,
            lr=lr,
            grad_averaging=grad_averaging,
            reuse_rows=reuse_rows,
            stamp_meta=stamp_meta,
        )
