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
    assert "scatter" in _primitives(claim.params["body_jaxpr"].jaxpr)


@pytest.mark.parametrize("name, creates", [
    ("all_resident", False), ("absent_and_not_creatable", True),
    ("resident_and_new", True), ("read_only", False)])
def test_the_claim_loop_runs_no_pass_when_no_row_is_to_be_created(
        monkeypatch, name, creates):
    """The passes of the two loops, counted by running them eagerly: the
    find loop takes as many as the longest walk of any id (to its key or to
    its chain's first empty slot), the claim loop none unless some id is
    absent AND may create (what `probe_claim_passes_per_step` reads in a
    trace)."""
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

    def walk(uid):
        chain = plain.chain(uid)
        return 1 + next(i for i, pos in enumerate(chain)
                        if plain.slots[pos] in (uid, plain.empty))

    assert find == max(walk(uid) for uid in uids)
    assert (claim > 0) == creates
