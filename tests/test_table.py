"""Core hash-embedding table tests — the CRUD/filter/eviction coverage of
DeepRec's embedding_variable_ops_test (reference: core/kernels/
embedding_variable_ops_test.cc, python/ops/embedding_variable_ops_test.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeprec_tpu import (
    CBFFilter,
    CounterFilter,
    EmbeddingTable,
    EmbeddingVariableOption,
    GlobalStepEvict,
    InitializerOption,
    L2WeightEvict,
    TableConfig,
    combine,
)


def make_table(**kw):
    base = dict(name="t", dim=8, capacity=256)
    base.update(kw)
    return EmbeddingTable(TableConfig(**base))


def test_create_and_lookup_inserts_keys():
    t = make_table()
    s = t.create()
    ids = jnp.array([3, 7, 3, 11, 7, 3], jnp.int32)
    s, res = t.lookup_unique(s, ids, step=1)
    assert int(t.size(s)) == 3
    # all real ids resolved to distinct slots
    valid = np.asarray(res.valid)
    slots = np.asarray(res.slot_ix)[valid]
    assert (slots >= 0).all()
    assert len(set(slots.tolist())) == len(slots)
    # counts reflect duplication
    uids = np.asarray(res.uids)
    counts = {int(u): int(c) for u, c, v in zip(uids, np.asarray(res.counts), valid) if v}
    assert counts == {3: 3, 7: 2, 11: 1}


def test_lookup_is_stable_across_calls():
    t = make_table()
    s = t.create()
    ids = jnp.arange(32, dtype=jnp.int32)
    s, r1 = t.lookup_unique(s, ids, step=1)
    s, r2 = t.lookup_unique(s, ids, step=2)
    np.testing.assert_array_equal(np.asarray(r1.slot_ix), np.asarray(r2.slot_ix))
    np.testing.assert_allclose(
        np.asarray(r1.embeddings), np.asarray(r2.embeddings), rtol=1e-6
    )
    assert int(t.size(s)) == 32


def test_initializer_deterministic_per_key():
    t = make_table()
    s1 = t.create()
    s2 = t.create()
    ids = jnp.array([5, 9], jnp.int32)
    # insert in different orders / tables — same key must get same init value
    s1, ra = t.lookup_unique(s1, ids)
    s2, rb = t.lookup_unique(s2, jnp.array([9, 100, 5], jnp.int32))
    ua, ea = np.asarray(ra.uids), np.asarray(ra.embeddings)
    ub, eb = np.asarray(rb.uids), np.asarray(rb.embeddings)
    for k in (5, 9):
        va = ea[list(ua).index(k)]
        vb = eb[list(ub).index(k)]
        np.testing.assert_allclose(va, vb, rtol=1e-6)
    # init values look like N(0, 0.05): nonzero, small
    assert 0 < np.abs(ea).mean() < 0.2


def test_padding_ignored():
    t = make_table()
    s = t.create()
    ids = jnp.array([[1, 2, -1], [3, -1, -1]], jnp.int32)
    s, res = t.lookup_unique(s, ids, step=0)
    assert int(t.size(s)) == 3
    assert int(jnp.sum(res.counts)) == 3


def test_collision_heavy_insert_all_resolve():
    # capacity 64, insert 48 ids (75% load) — all must land via probing
    t = make_table(capacity=64)
    s = t.create()
    ids = jnp.arange(48, dtype=jnp.int32) * 7919  # scattered hashes
    s, res = t.lookup_unique(s, ids)
    assert int(t.size(s)) == 48
    assert int(s.insert_fails) == 0
    slots = np.asarray(res.slot_ix)[np.asarray(res.valid)]
    assert len(set(slots.tolist())) == 48


def test_table_full_reports_fails():
    t = make_table(capacity=16, max_probes=16)
    s = t.create()
    s, _ = t.lookup_unique(s, jnp.arange(16, dtype=jnp.int32) * 13)
    s, res = t.lookup_unique(s, (jnp.arange(8, dtype=jnp.int32) + 100) * 17)
    assert int(s.insert_fails) > 0
    # failed ids serve the no-permission default (0) and slot -1
    failed = np.asarray(res.slot_ix) < 0
    assert failed.any()


def test_freq_and_version_tracking():
    t = make_table()
    s = t.create()
    s, r1 = t.lookup_unique(s, jnp.array([42, 42, 7], jnp.int32), step=5)
    s, r2 = t.lookup_unique(s, jnp.array([42], jnp.int32), step=9)
    slot42 = int(np.asarray(r2.slot_ix)[list(np.asarray(r2.uids)).index(42)])
    assert int(s.freq[slot42]) == 3
    assert int(s.version[slot42]) == 9


def test_counter_filter_blocks_until_threshold():
    t = make_table(
        ev=EmbeddingVariableOption(counter_filter=CounterFilter(filter_freq=3))
    )
    s = t.create()
    ids = jnp.array([77], jnp.int32)
    s, r1 = t.lookup_unique(s, ids, step=0)  # freq 1: blocked
    s, r2 = t.lookup_unique(s, ids, step=1)  # freq 2: blocked
    s, r3 = t.lookup_unique(s, ids, step=2)  # freq 3: admitted
    i = list(np.asarray(r1.uids)).index(77)
    assert not bool(r1.admitted[i]) and not bool(r2.admitted[i])
    assert bool(r3.admitted[i])
    np.testing.assert_allclose(np.asarray(r1.embeddings[i]), 0.0)
    assert np.abs(np.asarray(r3.embeddings[i])).max() > 0


def test_cbf_filter_defers_slot_allocation():
    t = make_table(
        ev=EmbeddingVariableOption(
            cbf_filter=CBFFilter(filter_freq=2, max_element_size=1 << 12)
        )
    )
    s = t.create()
    ids = jnp.array([123], jnp.int32)
    s, r1 = t.lookup_unique(s, ids)
    assert int(t.size(s)) == 0  # below threshold: no slot consumed
    s, r2 = t.lookup_unique(s, ids)
    assert int(t.size(s)) == 1  # sketch count reached 2: admitted + created
    i = list(np.asarray(r2.uids)).index(123)
    assert int(r2.slot_ix[i]) >= 0


def test_global_step_eviction():
    t = make_table(
        ev=EmbeddingVariableOption(global_step_evict=GlobalStepEvict(steps_to_live=10))
    )
    s = t.create()
    s, _ = t.lookup_unique(s, jnp.array([1, 2], jnp.int32), step=0)
    s, _ = t.lookup_unique(s, jnp.array([2], jnp.int32), step=50)
    s = t.evict(s, step=55)
    assert int(t.size(s)) == 1  # key 1 (version 0) expired; key 2 survives
    # survivor still resolvable with its value intact
    s2, res = t.lookup_unique(s, jnp.array([2], jnp.int32), step=55)
    i = list(np.asarray(res.uids)).index(2)
    assert int(res.slot_ix[i]) >= 0


def test_l2_eviction():
    t = make_table(
        ev=EmbeddingVariableOption(l2_weight_evict=L2WeightEvict(l2_weight_threshold=0.5))
    )
    s = t.create()
    s, res = t.lookup_unique(s, jnp.array([1, 2], jnp.int32))
    # force key 1 tiny, key 2 large
    ix = {int(u): int(sl) for u, sl in zip(np.asarray(res.uids), np.asarray(res.slot_ix))}
    # Write through scatter_update so the (possibly packed) layout is honored.
    dim = t.cfg.dim
    s = t.scatter_update(
        s,
        jnp.array([ix[1], ix[2]], jnp.int32),
        jnp.stack([jnp.full((dim,), 0.001), jnp.full((dim,), 1.0)]),
    )
    s = t.evict(s, step=0)
    assert int(t.size(s)) == 1


def test_rebuild_preserves_values_and_grow():
    t = make_table(capacity=64)
    s = t.create()
    ids = jnp.arange(40, dtype=jnp.int32) * 3 + 1
    s, r1 = t.lookup_unique(s, ids, step=2)
    before = {
        int(u): np.asarray(r1.embeddings)[i]
        for i, u in enumerate(np.asarray(r1.uids))
        if bool(r1.valid[i])
    }
    s = t.grow(s, 256)
    assert s.capacity == 256
    assert int(t.size(s)) == 40
    t2 = EmbeddingTable(TableConfig(name="t", dim=8, capacity=256))
    s, r2 = t2.lookup_unique(s, ids, step=3)
    for i, u in enumerate(np.asarray(r2.uids)):
        if bool(r2.valid[i]):
            np.testing.assert_allclose(
                np.asarray(r2.embeddings)[i], before[int(u)], rtol=1e-6
            )


def test_scatter_update_and_dirty_tracking():
    t = make_table()
    s = t.create()
    s, res = t.lookup_unique(s, jnp.array([5, 6], jnp.int32))
    s = s.replace_meta(dirty=jnp.zeros_like(s.dirty))  # simulate post-save reset
    new_vals = jnp.ones_like(res.embeddings)
    s = t.scatter_update(s, res.slot_ix, new_vals, mask=res.valid)
    assert int(jnp.sum(s.dirty)) == 2
    emb = t.lookup_readonly(s, jnp.array([5], jnp.int32))
    np.testing.assert_allclose(np.asarray(emb[0]), 1.0)


def test_readonly_missing_serves_initializer():
    t = make_table()
    s = t.create()
    emb = t.lookup_readonly(s, jnp.array([999, -1], jnp.int32))
    assert np.abs(np.asarray(emb[0])).max() > 0  # initializer value
    np.testing.assert_allclose(np.asarray(emb[1]), 0.0)  # padding -> zeros


def test_combiners():
    emb_u = jnp.array([[1.0, 1.0], [2.0, 2.0], [0.0, 0.0]])
    inverse = jnp.array([[0, 1], [1, 2]])
    mask = jnp.array([[True, True], [True, False]])
    np.testing.assert_allclose(
        np.asarray(combine(emb_u, inverse, mask, "sum")), [[3, 3], [2, 2]]
    )
    np.testing.assert_allclose(
        np.asarray(combine(emb_u, inverse, mask, "mean")), [[1.5, 1.5], [2, 2]]
    )
    np.testing.assert_allclose(
        np.asarray(combine(emb_u, inverse, mask, "sqrtn")),
        [[3 / np.sqrt(2), 3 / np.sqrt(2)], [2, 2]],
    )


def test_lookup_jits_and_donates():
    t = make_table()

    @jax.jit
    def step(s, ids):
        s, res = t.lookup_unique(s, ids, step=0)
        return s, res.embeddings

    s = t.create()
    s, e1 = step(s, jnp.array([1, 2, 3], jnp.int32))
    s, e2 = step(s, jnp.array([3, 4, 5], jnp.int32))
    assert int(t.size(s)) == 5


# ------------------------------------------ the probe: find, then claim
#
# `EmbeddingTable._probe` against a plain linear-probing table (a list of
# slots and a dict of where each key stands). A new key may win another slot
# than the plain table gives it (the claim loop races all absent ids at
# once), so what is compared is what every placement shares: the slots of
# the resident ids, the SET of keys, and the invariant that every key is
# reached from its hash without crossing an empty slot.


def hashes(ids):
    from deeprec_tpu.utils import hashing

    return np.asarray(hashing.mix32(hashing.fold64(
        jnp.asarray(ids, jnp.int32)))).astype(np.uint64)


class PlainTable:
    def __init__(self, t, resident=()):
        from deeprec_tpu.embedding.table import empty_key

        self.t, self.empty = t, empty_key(t.cfg)
        self.capacity, self.max_probes = t.cfg.capacity, t.cfg.max_probes
        self.slots = [self.empty] * self.capacity
        self.where = {}
        for key in resident:
            assert self.insert(key) is not None

    def chain(self, key):
        h = int(hashes([key])[0])
        return [(h + off) & (self.capacity - 1)
                for off in range(self.max_probes)]

    def insert(self, key):
        for pos in self.chain(key):
            if self.slots[pos] == self.empty:
                self.slots[pos] = key
                self.where[key] = pos
                return pos
            if self.slots[pos] == key:
                return pos
        return None

    def sees_an_empty_slot(self, key):
        return any(self.slots[pos] == self.empty for pos in self.chain(key))

    def keys(self):
        return jnp.asarray(self.slots, jnp.int32)


def check_probe(plain, uids, want, got):
    """What `_probe` returned for `uids` / `want` on `plain`'s keys, held to
    the plain table; returns (created ids, failed ids)."""
    keys, slot_ix, created, failed = (np.asarray(a) for a in got)
    before = np.asarray(plain.slots)
    # nothing resident moved or vanished
    held = before != plain.empty
    np.testing.assert_array_equal(keys[held], before[held])
    new_ids, failed_ids = set(), set()
    for uid, w, ix, c, f in zip(uids, want, slot_ix, created, failed):
        uid = int(uid)
        if uid == plain.empty:                       # padding
            assert (ix, c, f) == (-1, False, False)
        elif uid in plain.where:                     # resident
            assert (ix, c, f) == (plain.where[uid], False, False), uid
        elif not plain.sees_an_empty_slot(uid):      # a full chain
            assert (ix, c, f) == (-1, False, True), uid
            failed_ids.add(uid)
        elif not w:                                  # absent, may not create
            assert (ix, c, f) == (-1, False, False), uid
        elif f:                                      # lost every race
            assert (ix, c) == (-1, False), uid
            failed_ids.add(uid)
        else:
            assert c and ix >= 0 and keys[ix] == uid, uid
            new_ids.add(uid)
    assert not new_ids & failed_ids
    live = keys[keys != plain.empty]
    assert len(set(live.tolist())) == len(live)
    assert set(live.tolist()) == set(plain.where) | new_ids
    # the invariant the find loop rests on
    after = PlainTable(plain.t)
    after.slots = keys.tolist()
    for pos, key in enumerate(after.slots):
        if key != after.empty:
            chain = after.chain(key)
            assert pos in chain, key
            walked = chain[:chain.index(pos)]
            assert all(after.slots[p] != after.empty for p in walked), key
    return new_ids, failed_ids


def _mix(name):
    """(table kwargs, resident ids, uids, want_create) of a named case."""
    empty = int(np.iinfo(np.int32).min)
    old = (np.arange(40) * 7919 + 3).tolist()        # 62 % of 64 slots
    new = (np.arange(24) * 104729 + 11).tolist()
    kw = dict(capacity=64)
    if name == "all_resident":
        return kw, old, old[:32], [True] * 32
    if name == "all_new":
        return kw, [], old + new[:8], [True] * 48
    if name == "resident_and_new":
        uids = old[::2] + new[:12]
        return kw, old, uids, [True] * len(uids)
    if name == "absent_and_not_creatable":
        uids = old[:10] + new[:10]
        return kw, old, uids, [True] * 10 + [False] * 5 + [True] * 5
    if name == "sentinel_padding":
        uids = [empty, old[0], empty, new[0], new[1], empty, empty, empty]
        return kw, old, uids, [True] * 8
    if name == "duplicates_that_both_create":
        uids = [new[0], new[1], new[0], old[5], new[1], new[2], new[0]]
        return kw, old, uids, [True] * 7
    if name == "a_full_table":
        # 14 of 16 slots held and every id sees every slot: of five new
        # ids two are created and three run out of probes
        return (dict(capacity=16, max_probes=16), old[:14], old[:4] + new[:5],
                [True] * 9)
    if name == "probes_run_out_before_an_empty_slot":
        # chains cut at 2 slots in a table at 62 %: some ids see none empty
        return (dict(capacity=64, max_probes=2), [], old + new[:8],
                [True] * 48)
    raise KeyError(name)


MIXES = ["all_resident", "all_new", "resident_and_new",
         "absent_and_not_creatable", "sentinel_padding",
         "duplicates_that_both_create", "a_full_table",
         "probes_run_out_before_an_empty_slot"]


@pytest.mark.parametrize("name", MIXES)
def test_probe_agrees_with_a_plain_linear_probing_table(name):
    from deeprec_tpu.embedding.table import probe_jit

    kw, resident, uids, want = _mix(name)
    t = make_table(**kw)
    plain = PlainTable(t, resident)
    got = probe_jit(t, plain.keys(), jnp.asarray(uids, jnp.int32),
                    jnp.asarray(want))
    new_ids, failed_ids = check_probe(plain, uids, want, got)
    absent = {u for u, w in zip(uids, want)
              if w and u != plain.empty and u not in plain.where}
    assert new_ids | failed_ids == absent
    if name == "a_full_table":
        assert (len(new_ids), len(failed_ids)) == (2, 3)
    elif name == "probes_run_out_before_an_empty_slot":
        assert new_ids and failed_ids
    else:
        assert not failed_ids
    if name == "duplicates_that_both_create":
        slot_ix, created = np.asarray(got[1]), np.asarray(got[2])
        assert created[[0, 2, 6]].all() and len(set(slot_ix[[0, 2, 6]])) == 1
    # probing the result again finds every id where it was put, creates
    # nothing and leaves the keys alone
    again = probe_jit(t, got[0], jnp.asarray(uids, jnp.int32),
                      jnp.asarray(want))
    placed = np.asarray(got[1]) >= 0
    np.testing.assert_array_equal(np.asarray(again[1])[placed],
                                  np.asarray(got[1])[placed])
    assert not np.asarray(again[2]).any()
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(got[0]))


def test_a_full_table_counts_its_failed_inserts():
    kw, resident, uids, _ = _mix("a_full_table")
    t = make_table(**kw)
    s = t.create()
    s, _ = t.lookup_unique(s, jnp.asarray(resident, jnp.int32))
    assert (int(t.size(s)), int(s.insert_fails)) == (14, 0)
    s, res = t.lookup_unique(s, jnp.asarray(uids, jnp.int32))
    assert (int(t.size(s)), int(s.insert_fails)) == (16, 3)
    assert int(np.sum(np.asarray(res.slot_ix)[np.asarray(res.valid)] < 0)) == 3


def test_probe_under_vmap_over_tables_with_different_chains():
    """Three tables in one vmap: empty, half full and nearly full, so the
    find loop of one is done while another's still walks, and one table
    creates rows while another creates none."""
    t = make_table(capacity=64)
    ids = (np.arange(60) * 7919 + 3).tolist()
    new = (np.arange(8) * 104729 + 11).tolist()
    plains = [PlainTable(t), PlainTable(t, ids[:32]), PlainTable(t, ids[:58])]
    uids = [ids[:20] + new[:4], ids[:24], ids[30:50] + new[4:8]]
    want = [[True] * 24, [True] * 24, [True] * 20 + [False] * 4]
    got = jax.jit(jax.vmap(t._probe))(
        jnp.stack([p.keys() for p in plains]), jnp.asarray(uids, jnp.int32),
        jnp.asarray(want))
    made = [check_probe(p, u, w, [a[i] for a in got])
            for i, (p, u, w) in enumerate(zip(plains, uids, want))]
    assert [len(n) for n, _ in made] == [24, 0, 0]
    assert not any(f for _, f in made)


def _loops(jaxpr):
    """The `while`s of a jaxpr, in order, through the calls it makes."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _loops(sub)
    return found


def _primitives(jaxpr):
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def test_the_find_loop_carries_no_keys_and_writes_nothing():
    """The first `while` of `_probe` closes over the key array (a constant
    of its body, not a carry: what a batched predicate makes `vmap` select
    every pass is the carry) and scatters nothing; the claim loop, which
    carries and scatters the keys, is the second."""
    C, U = 256, 24
    t = make_table(capacity=C)
    jaxpr = jax.make_jaxpr(t._probe)(
        jnp.zeros((C,), jnp.int32), jnp.zeros((U,), jnp.int32),
        jnp.zeros((U,), bool)).jaxpr
    find, claim = _loops(jaxpr)

    def carry(loop):
        skip = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
        return [v.aval.shape for v in loop.invars[skip:]]

    assert (C,) not in carry(find) and (C,) in carry(claim)
    assert all(shape in ((), (U,)) for shape in carry(find))
    body = _primitives(find.params["body_jaxpr"].jaxpr)
    assert "gather" in body and not {"scatter", "sort"} & body
    # off the TPU the window read is XLA's row gather, [U, 128] keys a pass
    assert not {"pallas_call", "custom_vmap_call"} & body
    assert [e.outvars[0].aval.shape
            for e in find.params["body_jaxpr"].jaxpr.eqns
            if e.primitive.name == "gather"] == [(U, 128)]
    assert "scatter" in _primitives(claim.params["body_jaxpr"].jaxpr)


@pytest.mark.parametrize("name, creates", [
    ("all_resident", False), ("absent_and_not_creatable", True),
    ("resident_and_new", True), ("read_only", False)])
def test_the_claim_loop_runs_no_pass_when_no_row_is_to_be_created(
        monkeypatch, name, creates):
    """The passes of the two loops, counted by running them eagerly: the
    find loop takes as many as the windows the longest walk of any id (to
    its key or to its chain's first empty slot) reaches into from its home
    lane, the claim loop none unless some id is absent AND may create (what
    `probe_claim_passes_per_step` reads in a trace)."""
    if name == "read_only":   # the serving path: absent ids, none may create
        kw, resident, uids, want = _mix("resident_and_new")
        want = [False] * len(uids)
    else:
        kw, resident, uids, want = _mix(name)
    t = make_table(**kw)
    plain = PlainTable(t, resident)
    passes = []

    def counted(cond, body, carry):
        n = 0
        while cond(carry):
            carry, n = body(carry), n + 1
        passes.append(n)
        return carry

    monkeypatch.setattr(jax.lax, "while_loop", counted)
    with jax.disable_jit():
        got = t._probe(plain.keys(), jnp.asarray(uids, jnp.int32),
                       jnp.asarray(want))
    check_probe(plain, uids, want, got)
    find, claim = passes

    W = min(128, plain.capacity)

    def windows(uid):
        chain = plain.chain(uid)
        walked = next(i for i, pos in enumerate(chain)
                      if plain.slots[pos] in (uid, plain.empty))
        return 1 + (chain[0] % W + walked) // W

    assert find == max(windows(uid) for uid in uids)
    assert (claim > 0) == creates


# ------------------------------- the find loop's windows, slot for slot
#
# `_probe` against the walk it replaced, written out in NumPy: one slot a
# step down every id's chain, then the claim loop's rounds. Every case is
# built so that no two new ids want one slot in one round (the walk checks
# it), so the race has one outcome and ALL of what `_probe` returns is
# compared: the new keys, `slot_ix`, `created`, `failed`.


def scalar_probe(plain, uids, want):
    empty, cap, probes = plain.empty, plain.capacity, plain.max_probes
    keys = np.asarray(plain.slots, np.int64)
    uids = np.asarray(uids, np.int64)
    home = hashes(uids).astype(np.int64)
    n = len(uids)
    slot_ix = np.full(n, -1, np.int64)
    empty_at = np.full(n, -1, np.int64)
    unresolved = np.zeros(n, bool)
    for i, uid in enumerate(uids):
        if uid == empty:
            continue
        for off in range(probes):
            pos = (home[i] + off) & (cap - 1)
            if keys[pos] == uid:
                slot_ix[i] = pos
                break
            if keys[pos] == empty:
                empty_at[i] = off
                break
        else:
            unresolved[i] = True
    claiming = (empty_at >= 0) & np.asarray(want, bool)
    pending, off = claiming.copy(), empty_at.copy()
    while pending.any():
        pos = (home + off) & (cap - 1)
        wants = pending & (keys[pos] == empty)
        for i in np.flatnonzero(wants):
            assert keys[pos[i]] in (empty, uids[i]), (
                "two ids race for one slot: the case is not deterministic")
            keys[pos[i]] = uids[i]
        won = wants & (keys[pos] == uids)
        slot_ix[won] = pos[won]
        off += 1
        pending &= ~won & (off < probes)
    created = claiming & (slot_ix >= 0)
    return keys, slot_ix, created, unresolved | (claiming & ~created)


_POOL = {}


def homed(capacity, slot, n, first=0):
    """n ids whose home slot in a table of `capacity` is `slot` (from the
    `first`-th such id on), by the table's own hash."""
    if capacity not in _POOL:
        pool = np.arange(1, 300_000)
        _POOL[capacity] = pool, hashes(pool) & np.uint64(capacity - 1)
    pool, home = _POOL[capacity]
    got = pool[home == slot][first:first + n].tolist()
    assert len(got) == n
    return got


def _window_case(name):
    """(table kwargs, resident ids in the order they were inserted, uids,
    want_create, what the case must show: f(slot_ix, failed))
    of a named case. W is min(128, capacity)."""
    empty = int(np.iinfo(np.int32).min)
    if name == "a_chain_crosses_a_windows_end":
        # 12 keys from lanes 122-127 of the first window: slots 122-133
        cap = 512
        old = sum((homed(cap, s, 3) for s in (122, 124, 126, 127)), [])
        uids = old + homed(cap, 123, 1) + homed(cap, 200, 1)
        return (dict(capacity=cap), old, uids, [True] * len(uids),
                lambda slot_ix, failed: (
                    sum(slot_ix[:12] >= 128) >= 4 and slot_ix[12] == 134
                    and slot_ix[13] == 200 and not failed.any()))
    if name == "a_chain_wraps_the_tables_end":
        cap = 256
        old = sum((homed(cap, s, 3) for s in range(250, 256)), [])
        uids = old + homed(cap, 253, 1, first=3)
        return (dict(capacity=cap), old, uids, [True] * len(uids),
                lambda slot_ix, failed: (
                    sum(slot_ix[:18] < 12) == 12 and slot_ix[18] == 12
                    and not failed.any()))
    if name == "a_home_slot_in_a_windows_last_lane":
        cap = 512
        old = sum((homed(cap, s, 2) for s in (127, 255, 511)), [])
        uids = old + homed(cap, 255, 1, first=2) + homed(cap, 383, 1)
        return (dict(capacity=cap), old, uids, [True] * len(uids),
                lambda slot_ix, failed: (
                    slot_ix.tolist() == [127, 128, 255, 256, 511, 0, 257,
                                         383]))
    if name == "a_table_smaller_than_a_window":
        cap = 32    # W = 32, one row; 64 probes walk the table twice
        old = (np.arange(30) * 7919 + 3).tolist()
        new = (np.arange(2) * 104729 + 11).tolist()
        uids = old[::-1] + new
        return (dict(capacity=cap), old, uids, [True] * 30 + [True, False],
                lambda slot_ix, failed: (
                    (slot_ix[:31] >= 0).all() and slot_ix[31] == -1
                    and not failed.any()))
    if name == "a_full_table_smaller_than_a_window":
        cap = 16    # full: an absent id sees all 16 slots four times over
        old = (np.arange(16) * 7919 + 3).tolist()
        new = (np.arange(3) * 104729 + 11).tolist()
        return (dict(capacity=cap), old, old + new, [True] * 19,
                lambda slot_ix, failed: (
                    (slot_ix[:16] >= 0).all() and failed[16:].all()))
    if name == "a_table_of_one_window":
        cap = 128   # 100 keys of 128: long chains, and they wrap in the row
        old = (np.arange(100) * 7919 + 3).tolist()
        new = homed(cap, 5, 1) + [empty] + homed(cap, 77, 1)
        return (dict(capacity=cap), old, old + new, [True] * 103,
                lambda slot_ix, failed: (
                    (slot_ix[:100] >= 0).all() and not failed.any()))
    if name == "a_full_region_runs_out_of_probes":
        # slots 100-169 held: 64 keys homed at 100, then 6 homed at 150
        cap = 512
        old = homed(cap, 100, 64) + homed(cap, 150, 6)
        new = (homed(cap, 100, 1, first=64) + homed(cap, 107, 1)
               + homed(cap, 106, 1))
        return (dict(capacity=cap), old, old + new, [True] * 73,
                lambda slot_ix, failed: (
                    slot_ix[63] == 163 and slot_ix[69] == 169
                    and failed[70] and slot_ix[71] == 170 and failed[72]
                    and failed.sum() == 2))
    if name == "max_probes_smaller_than_a_window":
        cap = 512
        old = homed(cap, 126, 5)                     # slots 126-130
        new = (homed(cap, 126, 1, first=5) + homed(cap, 127, 1)
               + homed(cap, 125, 1))
        return (dict(capacity=cap, max_probes=5), old, old + new, [True] * 8,
                lambda slot_ix, failed: (
                    slot_ix.tolist() == [126, 127, 128, 129, 130, -1, 131,
                                         125] and failed.tolist()
                    == [False] * 5 + [True, False, False]))
    if name == "max_probes_of_one_window":
        cap = 512
        old = homed(cap, 60, 128)                    # slots 60-187
        new = homed(cap, 60, 1, first=128) + homed(cap, 61, 1)
        return (dict(capacity=cap, max_probes=128), old, old + new,
                [True] * 130,
                lambda slot_ix, failed: (
                    slot_ix[127] == 187 and failed[128]
                    and slot_ix[129] == 188 and failed.sum() == 1))
    if name == "max_probes_not_a_multiple_of_a_window":
        cap = 1024  # 200 probes from lane 100 reach into a third window
        old = homed(cap, 100, 200)                   # slots 100-299
        new = (homed(cap, 100, 1, first=200) + homed(cap, 101, 1)
               + homed(cap, 99, 1))
        return (dict(capacity=cap, max_probes=200), old, old + new,
                [True] * 203,
                lambda slot_ix, failed: (
                    slot_ix[199] == 299 and failed[200]
                    and slot_ix[201] == 300 and slot_ix[202] == 99
                    and failed.sum() == 1))
    if name == "sentinel_ids":
        cap = 512
        old = sum((homed(cap, s, 3) for s in (122, 124, 126, 127)), [])
        uids = [empty, old[11], empty, empty] + homed(cap, 123, 1) + [empty]
        return (dict(capacity=cap), old, uids, [True] * 6,
                lambda slot_ix, failed: (
                    slot_ix.tolist() == [-1, 133, -1, -1, 134, -1]
                    and not failed.any()))
    if name == "absent_ids_that_may_not_create":
        cap = 512
        old = sum((homed(cap, s, 3) for s in (122, 124, 126, 127)), [])
        new = (homed(cap, 123, 1) + homed(cap, 200, 1)
               + homed(cap, 127, 1, first=3))
        return (dict(capacity=cap), old, old + new,
                [False] * 12 + [False, True, False],
                lambda slot_ix, failed: (
                    (slot_ix[:12] >= 0).all()
                    and slot_ix[12:].tolist() == [-1, 200, -1]
                    and not failed.any()))
    raise KeyError(name)


WINDOW_CASES = [
    "a_chain_crosses_a_windows_end", "a_chain_wraps_the_tables_end",
    "a_home_slot_in_a_windows_last_lane", "a_table_smaller_than_a_window",
    "a_full_table_smaller_than_a_window", "a_table_of_one_window",
    "a_full_region_runs_out_of_probes", "max_probes_smaller_than_a_window",
    "max_probes_of_one_window", "max_probes_not_a_multiple_of_a_window",
    "sentinel_ids", "absent_ids_that_may_not_create"]


@pytest.mark.parametrize("name", WINDOW_CASES)
def test_probe_equals_the_scalar_walk_slot_for_slot(name):
    from deeprec_tpu.embedding.table import probe_jit

    kw, resident, uids, want, shows = _window_case(name)
    t = make_table(**kw)
    plain = PlainTable(t, resident)
    assert len(plain.where) == len(resident)
    expected = scalar_probe(plain, uids, want)
    got = probe_jit(t, plain.keys(), jnp.asarray(uids, jnp.int32),
                    jnp.asarray(want))
    for a, b, what in zip(got, expected,
                          ("keys", "slot_ix", "created", "failed")):
        np.testing.assert_array_equal(np.asarray(a), b, err_msg=what)
    # the case is the case its name says
    assert shows(expected[1], expected[3]), (expected[1], expected[3])


@pytest.mark.parametrize("capacity", [64, 512])
def test_probe_equals_the_scalar_walk_under_vmap_over_tables(capacity):
    """The table axis: an empty table, one filled to a half and one filled
    to three quarters in one vmap, so the tables' find loops want different
    numbers of passes and one table creates rows where another creates
    none; each table's results are its own scalar walk's."""
    t = make_table(capacity=capacity)
    n = capacity * 3 // 4
    ids = (np.arange(n) * 7919 + 3).tolist()
    plains = [PlainTable(t), PlainTable(t, ids[:capacity // 2]),
              PlainTable(t, ids)]
    new = homed(capacity, 7, 1) + homed(capacity, capacity - 1, 1)
    apart = sum((homed(capacity, s, 1, first=9) for s in range(0, 48, 2)),
                [])                     # 24 ids, no two with one home slot
    uids = [apart, ids[:24], ids[-22:] + new]
    want = [[True] * 24, [True] * 24, [True] * 23 + [False]]
    got = jax.jit(jax.vmap(t._probe))(
        jnp.stack([p.keys() for p in plains]), jnp.asarray(uids, jnp.int32),
        jnp.asarray(want))
    for i, plain in enumerate(plains):
        expected = scalar_probe(plain, uids[i], want[i])
        for a, b in zip(got, expected):
            np.testing.assert_array_equal(np.asarray(a[i]), b)
    created = np.asarray(got[2]).sum(axis=1).tolist()
    assert created == [24, 0, 1] and not np.asarray(got[3]).any()


@pytest.mark.parametrize("name", ["a_chain_crosses_a_windows_end",
                                  "a_full_region_runs_out_of_probes",
                                  "sentinel_ids"])
def test_probe_through_the_row_kernel_equals_the_scalar_walk(monkeypatch,
                                                             name):
    """What a TPU runs, interpreted: the window read through `gather_rows`
    (rows of 128 int32 keys, a settled id's row skipped) inside the find
    loop, alone and with the table `vmap` folded into the kernel's own
    table axis."""
    import functools

    from deeprec_tpu.ops import fused_lookup

    monkeypatch.setattr(fused_lookup, "gather_rows", functools.partial(
        fused_lookup.gather_rows, interpret=True))
    kw, resident, uids, want, _ = _window_case(name)
    t = make_table(**kw)
    plain, other = PlainTable(t, resident), PlainTable(t, resident[::3])
    uids_, want_ = jnp.asarray(uids, jnp.int32), jnp.asarray(want)
    probe = lambda *a: t._probe(*a)   # noqa: E731 — a jit cache of its own
    jaxpr = jax.make_jaxpr(probe)(plain.keys(), uids_, want_).jaxpr
    find = _loops(jaxpr)[0]   # (the kernel's own loops stand inside it)
    assert "custom_vmap_call" in _primitives(find.params["body_jaxpr"].jaxpr)
    got = jax.jit(probe)(plain.keys(), uids_, want_)
    for a, b in zip(got, scalar_probe(plain, uids, want)):
        np.testing.assert_array_equal(np.asarray(a), b)
    # two tables, read-only (the ids absent from the second would race)
    nowhere = [False] * len(uids)
    got = jax.jit(jax.vmap(probe, in_axes=(0, None, None)))(
        jnp.stack([plain.keys(), other.keys()]), uids_, jnp.asarray(nowhere))
    for i, p in enumerate((plain, other)):
        for a, b in zip(got, scalar_probe(p, uids, nowhere)):
            np.testing.assert_array_equal(np.asarray(a[i]), b)


@pytest.mark.parametrize("arm", ["xla", "kernel"])
@pytest.mark.parametrize("name", ["a_chain_crosses_a_windows_end",
                                  "a_full_region_runs_out_of_probes",
                                  "sentinel_ids"])
def test_more_ids_than_a_slice_walk_the_find_loop_in_slices(monkeypatch,
                                                            name, arm):
    """A caller that probes a whole table's slots at once (rebuild, a
    restore) has more ids than `_PROBE_SLICE`: the find loop then runs a
    slice at a time inside ONE scan, the last slice padded, so the window
    a pass holds is [slice, W] whatever the table's size. The slice is cut
    to 5 ids here (14, 73 and 6 ids: none a multiple of it); each arm,
    alone and under the table vmap, gives the scalar walk's results."""
    import functools

    from deeprec_tpu.embedding import table as table_module
    from deeprec_tpu.ops import fused_lookup

    monkeypatch.setattr(table_module, "_PROBE_SLICE", 5)
    if arm == "kernel":
        monkeypatch.setattr(fused_lookup, "gather_rows", functools.partial(
            fused_lookup.gather_rows, interpret=True))
    kw, resident, uids, want, _ = _window_case(name)
    t = make_table(kernel="pallas" if arm == "kernel" else "xla", **kw)
    plain, other = PlainTable(t, resident), PlainTable(t, resident[::3])
    uids_, want_ = jnp.asarray(uids, jnp.int32), jnp.asarray(want)
    probe = lambda *a: t._probe(*a)   # noqa: E731 — a jit cache of its own
    jaxpr = jax.make_jaxpr(probe)(plain.keys(), uids_, want_).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == -(-len(uids) // 5)
    body = scans[0].params["jaxpr"].jaxpr
    assert len(_loops(body)) >= 1 and (
        ("custom_vmap_call" in _primitives(body)) == (arm == "kernel"))
    got = jax.jit(probe)(plain.keys(), uids_, want_)
    for a, b in zip(got, scalar_probe(plain, uids, want)):
        np.testing.assert_array_equal(np.asarray(a), b)
    nowhere = [False] * len(uids)
    got = jax.jit(jax.vmap(probe, in_axes=(0, None, None)))(
        jnp.stack([plain.keys(), other.keys()]), uids_, jnp.asarray(nowhere))
    for i, p in enumerate((plain, other)):
        for a, b in zip(got, scalar_probe(p, uids, nowhere)):
            np.testing.assert_array_equal(np.asarray(a[i]), b)


@pytest.mark.parametrize("capacity", [256, 1024])
def test_rebuild_in_slices_is_the_rebuild(monkeypatch, capacity):
    """rebuild probes all C slots of the old table into the fresh one: cut
    into slices of 64 ids (4 and 16 of them) it leaves the state, slot for
    slot, that the one-slice find loop leaves."""
    from deeprec_tpu.embedding import table as table_module

    t = make_table(capacity=capacity)
    s = t.create()
    ids = jnp.asarray(np.arange(capacity // 2) * 7919 + 3, jnp.int32)
    s, _ = t.lookup_unique(s, ids, step=1)
    keep = jnp.asarray(np.arange(capacity) % 3 != 0)
    whole = jax.jit(lambda s: t.rebuild(s, keep=keep))(s)
    monkeypatch.setattr(table_module, "_PROBE_SLICE", 64)
    sliced = jax.jit(lambda s: t.rebuild(s, keep=keep))(s)
    assert 0 < int(t.size(whole)) < capacity // 2
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(sliced)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kw", [dict(capacity=64),
                                dict(capacity=512, key_dtype="int64")])
def test_a_key_window_the_row_kernel_cannot_take_is_no_fallback_of_the_gathers(
        monkeypatch, kw):
    """`deeprec_pallas_fallback_total{kernel="gather_rows"}` is read as the
    VALUE gather's fallbacks: a table under one lane tile, or int64 keys,
    under kernel="pallas" on a TPU takes XLA's row gather for its key
    windows by `_key_window`'s own decision and notes nothing."""
    from deeprec_tpu.ops import fused_lookup
    from deeprec_tpu.utils import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    with jax.enable_x64(kw.get("key_dtype") == "int64"):
        t = make_table(kernel="pallas", **kw)
        kdt = jnp.dtype(kw.get("key_dtype", "int32"))
        noted = set(fused_lookup._fallback_noted)
        jaxpr = jax.make_jaxpr(lambda *a: t._probe(*a))(
            jnp.zeros((t.cfg.capacity,), kdt), jnp.arange(1, 9, dtype=kdt),
            jnp.ones((8,), bool)).jaxpr
    assert fused_lookup._fallback_noted == noted
    assert "custom_vmap_call" not in _primitives(jaxpr)
